"""Share (%) of its HBM roofline that the Pallas pack kernel reaches: the
bytes its calls in the traced rounds must move (``kernels.PackCall``, from
the plans' shuffle shapes) over the chip's HBM bandwidth, divided by the
kernel's device time, per chip.

The pack is the only Pallas kernel of these plans, so its calls are the
``tpu_custom_call`` ops.  Where the trace holds another number of calls
than the plans make, the bytes would not belong to the time: no reading."""

KERNEL = "tpu_custom_call"


def read(view):
    t = view.trace
    lo, hi = t.window
    calls = sum(n * len(view.pack_calls.get(name, ())) for name, n in view.traced.items())
    bytes_per_chip = sum(
        n * sum(c.bytes for c in view.pack_calls.get(name, ()))
        for name, n in view.traced.items()
    )
    kernel_s = []
    for ops in t.devices.values():
        mine = [e for e in ops if KERNEL in e.name and lo <= e.start and e.end <= hi]
        if len(mine) != calls:
            return None
        kernel_s.append(sum(e.end - e.start for e in mine) / 1e9)
    if not calls or not kernel_s:
        return None
    return 100.0 * bytes_per_chip / view.peaks["hbm_bytes_per_s"] / (sum(kernel_s) / len(kernel_s))
