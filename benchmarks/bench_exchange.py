"""Paper Fig 5 / Fig 12(b) analogue + the MoE exchange A/B.

Fig 12(b): how much each engine speeds up as link bandwidth rises —
transports that burn CPU per byte (TCP-like) or suffer contention
(unscheduled) cannot convert bandwidth into query throughput; the
scheduled zero-copy transport can.  Same model as bench_scaling.

MoE A/B: per-device collective bytes of the expert-parallel dispatch from
the dry-run artifacts — scheduled round-robin phases (collective-permute)
vs XLA's monolithic all-to-all, modeled at ICI rates with/without the
contention factor.  This is the paper's technique applied to its LM-era
workload (DESIGN.md §4).

Pack A/B (``pack_ab``): the partition/pack hot path — XLA one-hot/cumsum
reference vs the fused Pallas partition+pack kernel — with HLO-level
evidence that the fused path's intermediates are independent of the
destination count (no ``[rows, num_dest]`` one-hot), plus a bit-exactness
check between the two implementations.
"""

import json
import os
import re

from repro.core import topology as T
from .bench_scaling import query_time
from .common import emit, time_jit


def fig12b() -> list[dict]:
    n = 6
    records = []
    for name, sched, cpu in (
        ("memsql_like_tcp", False, 0.45),
        ("vortex_like_tcp", False, 0.20),
        ("hyper_rdma_sched", True, 0.02),
    ):
        base = query_time(n, 0.125, sched, cpu)
        for gbps in (0.125, 1.0, 2.0, 4.0):
            s = base / query_time(n, gbps, sched, cpu)
            emit(f"fig12b/{name}", f"{s:.2f}", "x", f"link={gbps}GB/s")
            records.append({"engine": name, "link_gbps": gbps,
                            "speedup_x": round(s, 2)})
    emit("fig12b/paper_claim", "12", "x", "HyPer RDMA 4xQDR vs GbE (paper)")
    return records


def moe_exchange_ab(art_dir: str = "artifacts/dryrun_final"):
    """Scheduled (ppermute phases) vs unscheduled (monolithic a2a) dispatch."""
    for arch in ("olmoe-1b-7b", "deepseek-v2-lite-16b"):
        f = os.path.join(art_dir, f"{arch}__train_4k__16x16.json")
        if not os.path.exists(f):
            continue
        art = json.load(open(f))
        coll = art["collective_bytes"]
        cp = coll.get("collective-permute", 0)  # the scheduled phases
        a2a = coll.get("all-to-all", 0)
        link = T.V5E.ici_link_bandwidth
        contention = T.contention_factor(16)
        t_sched = cp / link
        t_unsched = (cp + a2a) / link / contention
        emit(f"moe_ab/{arch}/sched_dispatch", f"{t_sched*1e3:.1f}", "ms/step",
             f"{cp/1e9:.1f}GB ppermute phases")
        emit(f"moe_ab/{arch}/unsched_dispatch", f"{t_unsched*1e3:.1f}", "ms/step",
             f"contention={contention:.2f}")
        if t_sched > 0:
            emit(f"moe_ab/{arch}/sched_gain", f"{t_unsched/t_sched:.2f}", "x", "")


def pack_ab(rows: int = 8192, width: int = 4,
            dests: tuple = (8, 64, 256)) -> list[dict]:
    """Partition/pack hot path: XLA one-hot vs the fused Pallas kernel.

    The XLA reference ranks rows with a ``[rows, num_dest + 1]``
    one-hot/cumsum — O(rows x destinations).  The Pallas path's largest
    intermediate is the per-block ``[block, bins]`` tile plus the
    ``[nblocks, bins]`` histogram.  Evidence emitted per destination count:

    * whether the optimized HLO materializes a ``[rows, num_dest + 1]``
      tensor (it must for xla, must NOT for pallas),
    * the largest 2-D s32 intermediate in the program,
    * compiled cost analysis (flops), wall time, and a bit-exactness check.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import exchange

    key = jax.random.PRNGKey(0)
    keys = jax.random.randint(key, (rows,), 0, 1 << 30)
    data = jax.random.randint(
        jax.random.fold_in(key, 1), (rows, width), 0, 1000, dtype=jnp.int32
    )
    records = []
    for n_dest in dests:
        cap = max(rows // n_dest * 2, 16)  # 2x fair share
        dest = (keys % n_dest).astype(jnp.int32)
        outs = {}
        for impl in ("xla", "pallas"):
            fn = jax.jit(
                lambda d, r, impl=impl: exchange.pack_by_destination(
                    d, r, n_dest, cap, impl=impl
                )
            )
            compiled = fn.lower(dest, data).compile()
            hlo = compiled.as_text()
            onehot_shape = f"[{rows},{n_dest + 1}]"
            materializes = onehot_shape in hlo
            two_d = [
                int(a) * int(b) for a, b in re.findall(r"s32\[(\d+),(\d+)\]", hlo)
            ]
            peak2d = max(two_d, default=0)
            try:
                flops = (compiled.cost_analysis() or {}).get("flops", float("nan"))
            except Exception:
                flops = float("nan")
            wall = time_jit(fn, dest, data)
            outs[impl] = fn(dest, data)
            emit(f"pack_ab/ndest{n_dest}/{impl}/materializes_onehot",
                 str(materializes).lower(), "", f"shape s32{onehot_shape}")
            emit(f"pack_ab/ndest{n_dest}/{impl}/peak_2d_s32", peak2d, "elements", "")
            emit(f"pack_ab/ndest{n_dest}/{impl}/flops", f"{flops:.0f}", "", "")
            emit(f"pack_ab/ndest{n_dest}/{impl}/wall", f"{wall*1e3:.2f}", "ms",
                 "CPU interpret mode — HLO shape evidence is the signal")
            records.append({
                "rows": rows, "n_dest": n_dest, "impl": impl,
                "materializes_onehot": materializes, "peak_2d_s32": peak2d,
                "wall_ms": round(wall * 1e3, 3),
            })
        import numpy as np

        for a, b in zip(outs["xla"], outs["pallas"]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        emit(f"pack_ab/ndest{n_dest}/bit_exact", "true", "", "xla == pallas")
    return records


def overlap_audit(rows: int = 512, d: int = 32) -> list[dict]:
    """Overlap audit of the two-level EP dispatch, from lowered HLO.

    Compiles dispatch -> expert matmul -> combine through the two-level
    fabric on a (pod, model) mesh, then extracts per-collective bytes and
    dot FLOPs from the optimized HLO (``launch.hlo_cost``) and reports the
    roofline ``overlap_fraction``: the share of collective time the
    latency-hiding scheduler may hide behind compute (async -start/-done
    pairs, capped by available compute) on a TPU.  Needs >= 4 devices (real or
    ``--xla_force_host_platform_device_count`` fakes); on a single-device
    run it emits a skip marker — the modeled audit in
    ``bench_serve.ep_overlap_audit`` is then the signal.
    """
    import jax

    n = jax.device_count()
    if n < 4:
        emit("overlap_audit/hlo", "skipped", "",
             f"{n} device(s) — modeled audit in bench_serve is the signal")
        return []

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.compat import shard_map
    from repro.core import exchange
    from repro.launch import hlo_cost
    from repro.core.topology import chip_spec
    from repro.launch.roofline import RooflineTerms

    # Device time is priced for the chip that compiled the HLO; fake CPU
    # devices have no ChipSpec, so there the audit records bytes only.
    dev = jax.devices()[0]
    chip = chip_spec(dev.device_kind) if dev.platform == "tpu" else None
    records = []
    for pods in (1, 2):
        mesh = Mesh(
            np.array(jax.devices()[:n]).reshape(pods, n // pods),
            ("pod", "model"),
        )
        pod = "pod" if pods > 1 else None

        def body(x, w, pod=pod):
            # leading dim of the exchanged tensor == joint unit count n
            t = x.reshape((n, x.shape[0] // n) + x.shape[1:])
            if pod is None:
                y = exchange.all_to_all(t, "model")
            else:
                y = exchange.dispatch_two_level(t, "model", pod)
            y = jnp.einsum("ncd,df->ncf", y, w)  # the expert FFN stand-in
            if pod is None:
                y = exchange.all_to_all(y, "model")
            else:
                y = exchange.combine_two_level(y, "model", pod)
            return y.reshape(x.shape)

        fn = shard_map(
            body, mesh=mesh,
            in_specs=(P(("pod", "model")), P()),
            out_specs=P(("pod", "model")),
            axis_names={"pod", "model"}, check_vma=False,
        )
        x = jnp.zeros((rows, d), jnp.float32)
        w = jnp.zeros((d, d), jnp.float32)
        sh = NamedSharding(mesh, P(("pod", "model")))
        compiled = (
            jax.jit(fn, in_shardings=(sh, NamedSharding(mesh, P())))
            .lower(x, w).compile()
        )
        cost = hlo_cost.analyze(compiled.as_text())
        mesh_name = f"{pods}x{n // pods}"
        frac = None
        if chip is not None:
            frac = RooflineTerms(
                arch="two_level_ep", shape=f"{rows}x{d}", mesh=mesh_name,
                flops_per_chip=cost["flops"], bytes_per_chip=cost["bytes"],
                coll_bytes_per_chip=cost["collective_bytes"],
                model_flops_global=0.0, chips=n, chip=chip,
                async_coll_bytes_per_chip=cost["async_collective_bytes"],
            ).overlap_fraction
        coll_total = sum(cost["collective_bytes"].values())
        coll_async = sum(cost["async_collective_bytes"].values())
        emit(f"overlap_audit/{mesh_name}/collective_bytes", coll_total, "B",
             ",".join(sorted(cost["collective_bytes"])))
        emit(f"overlap_audit/{mesh_name}/async_bytes", coll_async, "B",
             "-start/-done pairs the scheduler may overlap")
        emit(f"overlap_audit/{mesh_name}/overlap_fraction",
             "not measured" if frac is None else f"{frac:.3f}", "",
             f"HLO-derived, priced for {dev.device_kind}" if chip else
             f"no chip spec for {dev.platform}: bytes only")
        records.append({
            "mesh": mesh_name,
            "collective_bytes": cost["collective_bytes"],
            "async_collective_bytes": cost["async_collective_bytes"],
            "flops_per_chip": cost["flops"],
            "overlap_fraction": None if frac is None else round(frac, 4),
        })
    return records


def run(smoke: bool = False) -> dict:
    """Full mode emits CSV only; smoke mode also returns the JSON record
    (reduced sizes) that ``benchmarks.run --smoke`` writes to
    ``BENCH_exchange.json``."""
    if smoke:
        return {
            "fig12b": fig12b(),
            "pack_ab": pack_ab(rows=2048, dests=(8, 64)),
            "overlap_audit": overlap_audit(),
        }
    fig12b()
    moe_exchange_ab()
    pack_ab()
    overlap_audit()
    return {}


if __name__ == "__main__":
    run()
