"""The program's own host spans (``aquifer.*``, ``src/repro/spans.py``)
in a JAX profiler trace, on the clock of the device events that
``trace.py`` reduces.

:func:`load` reads every ``aquifer.*`` event of the host planes, keyed by
its host line (one line per thread), so that nesting is found per thread.
:func:`self_times` gives each span name's summed self time inside a window:
a span's duration minus the part of it its child spans on the same line
cover.  :func:`idle_gaps` gives the stretches of a window in which the
first device ran nothing, by ``trace.reduce``'s rule, which keeps only
their lengths.  :func:`idle_by_span` charges every idle nanosecond, not
each gap whole, to the shortest span open at it, among whatever spans it is
given (the benchmark's ``bench.*`` and the program's ``aquifer.*``
together): a gap that spans several host phases is split between them.

``restore_spans.py`` drives a traced cold start and reads it with these.
``trace.py`` and the per-layer metrics do not read them yet.
"""
from __future__ import annotations

import heapq
from pathlib import Path
from typing import Dict, List, Tuple

PREFIX = "aquifer."
OUTSIDE = "outside any span"               # idle time no span holds

Interval = Tuple[str, int, int]          # (name, start_ns, end_ns)
Lines = Dict[str, List[Interval]]        # host line -> its aquifer.* spans


def load(directory: Path) -> Lines:
    """The ``aquifer.*`` spans of the newest ``.xplane.pb`` under
    ``directory``, per host line."""
    from jax.profiler import ProfileData

    files = sorted(Path(directory).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    data = ProfileData.from_file(str(files[-1]))
    lines: Lines = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        # threads share line names ("python"): the index tells them apart
        for i, line in enumerate(plane.lines):
            spans = [(e.name, int(e.start_ns), int(e.end_ns))
                     for e in line.events if e.name.startswith(PREFIX)]
            if spans:
                lines[f"{plane.name}#{i} {line.name}"] = spans
    return lines


def flatten(lines: Lines) -> List[Interval]:
    return [s for spans in lines.values() for s in spans]


def self_times(lines: Lines, t0: int, t1: int) -> Dict[str, float]:
    """Span name -> seconds of self time inside ``[t0, t1]``, summed over
    its spans on every line.  A span's self time is its clipped duration
    less the part of it that its children on the same line cover."""
    out: Dict[str, float] = {}

    def close(frame: list) -> None:
        name, a, b, child = frame
        out[name] = out.get(name, 0.0) + (b - a - child) / 1e9

    for spans in lines.values():
        clipped = sorted((max(a, t0), -min(b, t1), n)
                         for n, a, b in spans if b > t0 and a < t1)
        stack: List[list] = []               # [name, start, end, child_ns]
        for a, neg_b, n in clipped:          # by start, the longer first
            b = -neg_b
            while stack and stack[-1][2] <= a:
                close(stack.pop())
            if stack:
                stack[-1][3] += min(b, stack[-1][2]) - a
            stack.append([n, a, b, 0])
        while stack:
            close(stack.pop())
    return out


def idle_gaps(ops: List[Interval], t0: int, t1: int) -> List[Tuple[int, int]]:
    """The stretches of ``[t0, t1]`` in which none of ``ops`` (one device's
    operations) ran, ascending."""
    gaps: List[Tuple[int, int]] = []
    at = t0                                  # covered up to here
    for _, a, b in sorted(ops, key=lambda o: o[1]):
        if b <= t0 or a >= t1:
            continue
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if at < t1:
        gaps.append((at, t1))
    return gaps


def idle_by_span(spans: List[Interval],
                 gaps: List[Tuple[int, int]]) -> Dict[str, float]:
    """Seconds of ``gaps`` (disjoint, ascending ``(start, end)``) charged to
    the shortest span open at each instant (ties by name), spans taken as
    half-open ``[start, end)``, and to :data:`OUTSIDE` where none is: one
    sweep over the elementary intervals between every span and gap edge."""
    edges = sorted({t for _, a, b in spans for t in (a, b)}
                   | {t for a, b in gaps for t in (a, b)})
    order = sorted(spans, key=lambda s: s[1])
    heap: List[Tuple[int, str, int]] = []    # (duration, name, end)
    out: Dict[str, float] = {}
    j = g = 0
    for lo, hi in zip(edges, edges[1:]):
        while j < len(order) and order[j][1] <= lo:
            n, a, b = order[j]
            heapq.heappush(heap, (b - a, n, b))
            j += 1
        while heap and heap[0][2] <= lo:
            heapq.heappop(heap)
        while g < len(gaps) and gaps[g][1] <= lo:
            g += 1
        if g < len(gaps) and gaps[g][0] <= lo:
            label = heap[0][1] if heap else OUTSIDE
            out[label] = out.get(label, 0.0) + (hi - lo) / 1e9
    return out
