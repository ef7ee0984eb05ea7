"""Reduction of a JAX profiler trace to device time, program time and the
idle gaps of the device, with what the host was doing in each gap.

The trace is the ``ProfileData`` of a profiler session. On a TPU each
chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event
per operation and whose line ``XLA Modules`` holds one event per program
run, named ``jit_<function>(<fingerprint>)``. Host threads are planes
``/host:...``, one line per thread; the benchmark's own spans
(``bench.<what>``) and the program's (``dacapo.<what>``, opened at each
layer boundary of its phase loop) appear there. The engine line is the one
that holds the ``dacapo.phase`` spans. All events share one clock in
nanoseconds.
"""
from __future__ import annotations

import dataclasses
import re
from collections import Counter, defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # (start_ns, end_ns)
Line = Tuple[str, int]  # (host plane, index of the line in it): one thread
ProgramSpan = Tuple[str, float, float, Line]  # (name, start_ns, end_ns, line)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
PROGRAM_PREFIX = "dacapo."
PHASE = "dacapo.phase"


@dataclasses.dataclass
class Trace:
    """What the metrics read from one trace."""

    ops: Dict[str, List[Interval]]  # device plane -> operation intervals
    modules: Dict[str, List[Tuple[str, float, float]]]  # plane -> programs
    spans: List[Tuple[str, float, float]]  # host spans of the benchmark
    program_spans: List[ProgramSpan] = dataclasses.field(
        default_factory=list)  # the program's spans, with their line


def load(data) -> Trace:
    """The metrics' view of a ``jax.profiler.ProfileData``."""
    ops: Dict[str, List[Interval]] = defaultdict(list)
    modules: Dict[str, List[Tuple[str, float, float]]] = defaultdict(list)
    spans: List[Tuple[str, float, float]] = []
    program: List[ProgramSpan] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] += [(e.start_ns, e.start_ns
                                         + e.duration_ns)
                                        for e in line.events]
                elif line.name == "XLA Modules":
                    modules[plane.name] += [(e.name, e.start_ns, e.start_ns
                                             + e.duration_ns)
                                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif e.name.startswith(PROGRAM_PREFIX):
                        program.append((e.name, e.start_ns,
                                        e.start_ns + e.duration_ns,
                                        (plane.name, i)))
    return Trace(dict(ops), dict(modules), spans, program)


def chip_ops(trace: Trace, chips: int) -> List[List[Interval]]:
    """Operation intervals of the ``chips`` lowest-numbered device planes,
    the chips a cell runs on; a chip that ran nothing counts as idle, as
    ``mfu`` counts every chip the cell holds."""
    planes = sorted(trace.ops, key=lambda p: int(p.rsplit(":", 1)[1]))
    return [trace.ops[p] for p in planes[:chips]] + [
        [] for _ in range(chips - len(planes))]


def window(trace: Trace) -> Optional[Interval]:
    """The measured window, from the benchmark's ``bench.window`` span."""
    found = [(s, e) for name, s, e in trace.spans if name == WINDOW_SPAN]
    return max(found, key=lambda iv: iv[1] - iv[0]) if found else None


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint, sorted intervals covering the same points."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside [lo, hi]."""
    return sum(e - s for s, e in union(clip(intervals, lo, hi)))


def gaps(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def program_name(module_event_name: str) -> str:
    """``jit__sgd_step(123)`` -> ``_sgd_step``; other names unchanged."""
    name = module_event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def program_times(modules: Sequence[Tuple[str, float, float]], lo: float,
                  hi: float) -> Dict[str, Tuple[int, float]]:
    """Program name -> (runs, device ns) of the runs that start in
    [lo, hi], their time clipped to it."""
    out: Dict[str, List[float]] = defaultdict(lambda: [0, 0.0])
    for name, s, e in modules:
        if lo <= s < hi:
            acc = out[program_name(name)]
            acc[0] += 1
            acc[1] += min(e, hi) - s
    return {k: (int(v[0]), v[1]) for k, v in out.items()}


def engine_line(trace: Trace) -> Optional[Line]:
    """The line that holds the ``dacapo.phase`` spans (the most of them)."""
    lines = Counter(line for name, _, _, line in trace.program_spans
                    if name == PHASE)
    return lines.most_common(1)[0][0] if lines else None


def on_line(trace: Trace, line: Line) -> List[Tuple[str, float, float]]:
    return [(n, s, e) for n, s, e, ln in trace.program_spans if ln == line]


def self_time(spans) -> List[Tuple[float, float, str]]:
    """Disjoint, sorted pieces ``(start, end, name)``: each instant covered
    by one thread's nested ``spans`` goes to the innermost span open then."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[str, float]] = []  # (name, end) of the open spans
    t = 0.0
    for name, s, e in sorted(spans, key=lambda sp: (sp[1], -sp[2])):
        while stack and stack[-1][1] <= s:
            top, end = stack.pop()
            if end > t:
                out.append((t, end, top))
                t = end
        if stack and s > t:
            out.append((t, s, stack[-1][0]))
        stack.append((name, e))
        t = s
    while stack:
        top, end = stack.pop()
        if end > t:
            out.append((t, end, top))
            t = end
    return out


def overlap_by_name(pieces, intervals) -> Dict[str, float]:
    """Nanoseconds of each name's ``pieces`` (as :func:`self_time` gives
    them) inside the union of ``intervals``."""
    ivs = union(intervals)
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for s, e, name in pieces:
        while j < len(ivs) and ivs[j][1] <= s:
            j += 1
        k = j
        while k < len(ivs) and ivs[k][0] < e:
            out[name] += min(e, ivs[k][1]) - max(s, ivs[k][0])
            k += 1
    return dict(out)


def host_activity(gap: Interval, trace: Trace) -> str:
    """What the host was doing in an idle gap: the program span holding
    most of it by self time on the engine line (``dacapo.phase`` itself
    excluded), else the benchmark's own span (:func:`bench_activity`)."""
    line = engine_line(trace)
    if line is not None:
        held = overlap_by_name(self_time(on_line(trace, line)), [gap])
        held.pop(PHASE, None)
        if held:
            return max(sorted(held), key=held.get)
    return bench_activity(gap, trace.spans)


def bench_activity(gap: Interval, spans: Sequence[Tuple[str, float, float]]
                   ) -> str:
    """The benchmark span that covers most of ``gap``, the innermost
    (shortest) on a tie; ``other`` where none does. The window span itself
    is not an activity."""
    s0, e0 = gap
    best, best_key = "other", (0.0, 0.0)
    for name, s, e in spans:
        if name == WINDOW_SPAN:
            continue
        cover = min(e, e0) - max(s, s0)
        key = (cover, -(e - s))
        if cover > 0 and key > best_key:
            best, best_key = name[len(SPAN_PREFIX):], key
    return best
