"""Reduce a JAX profiler trace to the device's busy and idle time, the device
time per program and per operation, and the longest idle gaps, each labelled
by the benchmark's host span that was open during it.

:func:`record` starts and stops the profiler (never at import).  :func:`load`
reads the ``.xplane.pb`` it wrote into plain tuples: per device plane the
events of its ``XLA Ops`` line (operations) and ``XLA Modules`` line
(compiled programs), and the host's ``bench.*`` spans, all on the
profiler's one clock.  :func:`reduce` works on those tuples alone, so a test
can hand it a small recorded trace.
"""
from __future__ import annotations

import contextlib
import dataclasses
from pathlib import Path
from typing import Dict, Iterator, List, Tuple

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[str, int, int]          # (name, start_ns, end_ns)


@dataclasses.dataclass
class Events:
    ops: Dict[str, List[Interval]]       # device plane -> its operations
    modules: Dict[str, List[Interval]]   # device plane -> its programs
    spans: List[Interval]                # host spans named bench.*


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                        # mean over devices
    ops: List[Tuple[str, float]]         # name -> device seconds, longest first
    modules: List[Tuple[str, float]]
    gaps: List[Tuple[str, float]]        # longest idle gaps, with their span

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_s(self, prefix: str) -> float:
        return sum(s for name, s in self.modules if name.startswith(prefix))


@contextlib.contextmanager
def record(directory: Path) -> Iterator[None]:
    """Profile the block into ``directory``, without the Python tracer."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(directory), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(directory: Path) -> Events:
    from jax.profiler import ProfileData

    files = sorted(Path(directory).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(str(files[-1]))
    ev = Events({}, {}, [])
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                dest = {OPS_LINE: ev.ops, MODULES_LINE: ev.modules}.get(line.name)
                if dest is not None:
                    dest[plane.name] = [(e.name, int(e.start_ns), int(e.end_ns))
                                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ev.spans.extend((e.name, int(e.start_ns), int(e.end_ns))
                                for e in line.events
                                if e.name.startswith(SPAN_PREFIX))
    return ev


def window(ev: Events, span: str) -> Tuple[int, int]:
    """The first host span named ``span``: the traced window."""
    for name, a, b in ev.spans:
        if name == span:
            return a, b
    raise ValueError(f"the trace holds no host span {span!r}")


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _clip(iv: List[Interval], t0: int, t1: int) -> List[Interval]:
    return [(n, max(a, t0), min(b, t1)) for n, a, b in iv if b > t0 and a < t1]


def _sums(per_device: Dict[str, List[Interval]], t0: int, t1: int,
          n_dev: int) -> List[Tuple[str, float]]:
    tot: Dict[str, float] = {}
    for iv in per_device.values():
        for n, a, b in _clip(iv, t0, t1):
            tot[n] = tot.get(n, 0.0) + (b - a) / 1e9 / n_dev
    return sorted(tot.items(), key=lambda kv: -kv[1])


def _label(spans: List[Interval], a: int, b: int) -> str:
    """The innermost bench span holding the gap's middle."""
    mid = (a + b) // 2
    inside = [(e - s, n) for n, s, e in spans if s <= mid <= e]
    return min(inside)[1] if inside else "outside any bench span"


def reduce(ev: Events, t0: int, t1: int) -> Reduction:
    """Busy time is the union of a device's operations within [t0, t1], as
    seconds averaged over the devices; an idle gap is a stretch of that
    window in which no operation ran on the first device."""
    if t1 <= t0:
        raise ValueError("empty window")
    devices = sorted(ev.ops)
    if not devices:
        raise ValueError("the trace holds no device operations")
    window_s = (t1 - t0) / 1e9
    busy = []
    gaps: List[Tuple[str, float]] = []
    for d in devices:
        merged = union([(a, b) for _, a, b in _clip(ev.ops[d], t0, t1)])
        busy.append(sum(b - a for a, b in merged) / 1e9)
        if d == devices[0]:
            edges = [t0] + [x for iv in merged for x in iv] + [t1]
            gaps = [(_label(ev.spans, a, b), (b - a) / 1e9)
                    for a, b in zip(edges[::2], edges[1::2]) if b > a]
    busy_s = sum(busy) / len(busy)
    if busy_s > window_s:
        raise ValueError(f"busy {busy_s} s exceeds the window {window_s} s")
    gaps.sort(key=lambda g: -g[1])
    return Reduction(window_s, busy_s, _sums(ev.ops, t0, t1, len(devices)),
                     _sums(ev.modules, t0, t1, len(devices)), gaps)


def op_name(hlo: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` → ``%fusion.3``."""
    return hlo.split(" = ", 1)[0]


def breakdown(red: Reduction, top: int = 10) -> dict:
    return {"device_ops": [[op_name(n), s] for n, s in red.ops[:top]],
            "idle_gaps": [[n, s] for n, s in red.gaps[:top]]}


def summary(ev: Events) -> str:
    """One line per device line and host span count, for a first look."""
    parts = [f"{d}: {len(ev.ops[d])} ops, {len(ev.modules.get(d, []))} modules"
             for d in sorted(ev.ops)]
    return "; ".join(parts + [f"{len(ev.spans)} bench spans"])
