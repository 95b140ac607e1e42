"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

* device ops, per chip: the ``XLA Ops`` line of each ``/device:TPU:<n>``
  plane, each op tagged with the program (``XLA Modules`` line) it ran in;
* the benchmark's host spans, ``jax.profiler.TraceAnnotation`` events
  named ``bench.*``, and the other host events on their thread (the Python
  tracer's frames among them);
* the traced window: from the first ``bench.round`` span to the last one's end.

Busy time is the union of a chip's op intervals inside the window.  An op's
self time leaves out the ops nested in it (a loop and its body).  An idle
gap is a stretch of the window in which the chip runs no op, labelled by
what the host thread was doing at its middle.
"""

from __future__ import annotations

import dataclasses
import gzip
import re
from collections import defaultdict
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
ROUND_SPAN = "bench.round"


@dataclasses.dataclass(frozen=True)
class Event:
    start: int  # ns
    end: int  # ns
    name: str
    module: str = ""  # the program a device op ran in


@dataclasses.dataclass
class Trace:
    devices: dict[int, list[Event]]  # chip index -> ops, by start
    spans: list[Event]  # bench.* host spans, by start
    host: list[Event]  # the other events on the threads that hold bench spans

    @property
    def rounds(self) -> list[Event]:
        return [s for s in self.spans if s.name == ROUND_SPAN]

    @property
    def window(self) -> tuple[int, int] | None:
        r = self.rounds
        return (r[0].start, max(s.end for s in r)) if r else None

    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) / 1e9

    def busy_ns(self, chip: int) -> int:
        lo, hi = self.window
        return sum(b - a for a, b in union(self.devices[chip], lo, hi))

    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the chips."""
        return sum(self.busy_ns(c) for c in self.devices) / len(self.devices) / 1e9

    def op_seconds(self, chip: int | None = None) -> dict[tuple[str, str], float]:
        """Self seconds per ``(program, op)`` inside the window: on ``chip``,
        or averaged over the chips."""
        lo, hi = self.window
        chips = list(self.devices) if chip is None else [chip]
        out: dict[tuple[str, str], float] = defaultdict(float)
        for c in chips:
            for e, self_ns in self_times(self.devices[c], lo, hi):
                out[e.module, e.name] += self_ns / 1e9 / len(chips)
        return dict(out)

    def gaps(self, chip: int) -> list[tuple[int, int]]:
        lo, hi = self.window
        out, t = [], lo
        for a, b in union(self.devices[chip], lo, hi):
            if a > t:
                out.append((t, a))
            t = b
        if t < hi:
            out.append((t, hi))
        return out

    def label(self, t: int, program_files: frozenset = frozenset()) -> str:
        """What the host was doing at ``t``: the innermost ``bench.*`` span,
        the innermost frame of a file in ``program_files`` and the innermost
        host event, joined by ``/``."""
        at = [e for e in self.host if e.start <= t < e.end]
        span = _innermost([e for e in self.spans if e.start <= t < e.end])
        mine = _innermost([e for e in at if _file(e.name) in program_files])
        leaf = _innermost(at)
        parts = [span.name if span else "outside bench spans"]
        for e in (mine, leaf):
            if e is not None and e.name not in parts:
                parts.append(e.name)
        return "/".join(parts)


def _innermost(events):
    return min(events, key=lambda e: e.end - e.start) if events else None


def _file(name: str) -> str:
    """``foo.py`` of a Python-tracer event name such as ``$foo.py:12 f``."""
    return name[1:].split(":", 1)[0] if name.startswith("$") else ""


def union(events, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged ``[start, end)`` intervals of ``events`` clipped to ``[lo, hi)``."""
    out: list[list[int]] = []
    for e in sorted(events, key=lambda e: e.start):
        a, b = max(e.start, lo), min(e.end, hi)
        if a >= b:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def self_times(events, lo: int, hi: int) -> list[tuple[Event, int]]:
    """Each event inside ``[lo, hi)`` with its clipped duration less the
    clipped durations of the events nested directly in it."""
    out: list[list] = []
    stack: list[list] = []
    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        a, b = max(e.start, lo), min(e.end, hi)
        if a >= b:
            continue
        while stack and stack[-1][0].end <= e.start:
            stack.pop()
        entry = [e, b - a]
        if stack:
            stack[-1][1] -= b - a
        stack.append(entry)
        out.append(entry)
    return [(e, max(ns, 0)) for e, ns in out]


def _events(line) -> list[Event]:
    out = []
    for e in line.events:
        start = int(e.start_ns)
        out.append(Event(start, start + int(e.duration_ns), str(e.name)))
    return out


def _in_modules(ops: list[Event], modules: list[Event]) -> list[Event]:
    """``ops`` (by start) tagged with the module event that contains each."""
    out, j = [], 0
    modules = sorted(modules, key=lambda m: m.start)
    for e in ops:
        while j < len(modules) and modules[j].end <= e.start:
            j += 1
        inside = j < len(modules) and modules[j].start <= e.start
        out.append(dataclasses.replace(e, module=modules[j].name if inside else ""))
    return out


def from_profile(profile) -> Trace:
    """A :class:`Trace` of a ``jax.profiler.ProfileData``."""
    devices: dict[int, list[Event]] = {}
    spans: list[Event] = []
    host: list[Event] = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: _events(line) for line in plane.lines}
            ops = sorted(lines.get(OPS_LINE, []), key=lambda e: e.start)
            devices[int(m.group(1))] = _in_modules(ops, lines.get(MODULES_LINE, []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = _events(line)
                mine = [e for e in events if e.name.startswith(SPAN_PREFIX)]
                if mine:
                    spans += mine
                    host += [e for e in events if not e.name.startswith(SPAN_PREFIX)]
    return Trace(devices, sorted(spans, key=lambda e: e.start), host)


def load(path: str | Path) -> Trace:
    from jax.profiler import ProfileData

    path = Path(path)
    if path.name.endswith(".pbtxt.gz"):
        text = gzip.decompress(path.read_bytes()).decode()
        return from_profile(ProfileData.from_text_proto(text))
    return from_profile(ProfileData.from_file(str(path)))


def find_xplane(directory: str | Path) -> Path:
    found = sorted(Path(directory).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def breakdown(trace: Trace, program_files: frozenset = frozenset(), top: int = 10) -> dict:
    """The device ops with the most self time (seconds, averaged over the
    chips; named ``program: op``) and the longest idle gaps of the busiest
    chip, labelled by :meth:`Trace.label`."""
    ops = sorted(trace.op_seconds().items(), key=lambda kv: -kv[1])[:top]
    chip = max(trace.devices, key=trace.busy_ns)
    gaps = sorted(trace.gaps(chip), key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[f"{mod}: {name}"[:300], s] for (mod, name), s in ops],
        "idle_gaps": [
            [trace.label((a + b) // 2, program_files), (b - a) / 1e9] for a, b in gaps
        ],
    }


def text_proto(trace: Trace, rounds: int, min_host_ns: int = 0) -> str:
    """The first ``rounds`` traced rounds of ``trace``, host events shorter
    than ``min_host_ns`` left out, as an XSpace text proto that :func:`load`
    reads back gzipped: how a recorded trace is kept small."""
    lo, hi = trace.rounds[0].start, trace.rounds[rounds - 1].end

    def inside(events):
        return [e for e in events if e.start < hi and e.end > lo]

    planes = []
    for c, ops in sorted(trace.devices.items()):
        ops = inside(ops)
        mods: dict[str, list[int]] = {}
        for e in ops:
            if e.module:
                m = mods.setdefault(e.module, [e.start, e.end])
                m[0], m[1] = min(m[0], e.start), max(m[1], e.end)
        modules = [Event(a, b, name) for name, (a, b) in mods.items()]
        planes.append((f"/device:TPU:{c}", [(OPS_LINE, ops), (MODULES_LINE, modules)]))
    host = [e for e in inside(trace.host) if e.end - e.start >= min_host_ns]
    planes.append(("/host:CPU", [("python", inside(trace.spans) + host)]))
    out = []
    for pid, (plane, lines) in enumerate(planes, 1):
        every = [e for _, events in lines for e in events]
        names = {n: i for i, n in enumerate(sorted({e.name for e in every}), 1)}
        text = [f"planes {{\n  id: {pid}\n  name: {_quote(plane)}"]
        for lid, (line, events) in enumerate(lines, 1):
            text.append(f"  lines {{\n    id: {lid}\n    name: {_quote(line)}\n"
                        f"    timestamp_ns: {lo}")
            for e in events:
                text.append(f"    events {{ metadata_id: {names[e.name]} offset_ps: "
                            f"{(e.start - lo) * 1000} duration_ps: {(e.end - e.start) * 1000} }}")
            text.append("  }")
        text += [f"  event_metadata {{ key: {i} value {{ id: {i} name: {_quote(n)} }} }}"
                 for n, i in names.items()]
        out.append("\n".join(text) + "\n}")
    return "\n".join(out) + "\n"


def _quote(v) -> str:
    return '"' + str(v).replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n") + '"'


if __name__ == "__main__":
    import argparse

    p = argparse.ArgumentParser(description="Cut a recorded trace to its first rounds.")
    p.add_argument("xplane")
    p.add_argument("out", help="a .pbtxt.gz file")
    p.add_argument("--rounds", type=int, default=1)
    p.add_argument("--min-host-ns", type=int, default=1_000_000)
    a = p.parse_args()
    text = text_proto(load(a.xplane), a.rounds, a.min_host_ns)
    Path(a.out).write_bytes(gzip.compress(text.encode(), mtime=0))
