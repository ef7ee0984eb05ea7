"""Reduction of a JAX profiler trace to device time, program time and the
idle gaps of the device, with what the host was doing in each gap.

The trace is the ``ProfileData`` of a profiler session. On a TPU each
chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event
per operation and whose line ``XLA Modules`` holds one event per program
run, named ``jit_<function>(<fingerprint>)``. Host threads are planes
``/host:...``; the benchmark's own spans (``bench.<what>``) appear there.
All events share one clock in nanoseconds.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # (start_ns, end_ns)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Trace:
    """What the metrics read from one trace."""

    ops: Dict[str, List[Interval]]  # device plane -> operation intervals
    modules: Dict[str, List[Tuple[str, float, float]]]  # plane -> programs
    spans: List[Tuple[str, float, float]]  # host spans of the benchmark


def load(data) -> Trace:
    """The metrics' view of a ``jax.profiler.ProfileData``."""
    ops: Dict[str, List[Interval]] = defaultdict(list)
    modules: Dict[str, List[Tuple[str, float, float]]] = defaultdict(list)
    spans: List[Tuple[str, float, float]] = []
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
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return Trace(dict(ops), dict(modules), spans)


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


def host_activity(gap: Interval, spans: Sequence[Tuple[str, float, float]]
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
