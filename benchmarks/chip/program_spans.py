"""The program's own profiler spans, read beside a run of a cell (not part
of a benchmark run).

    python3 benchmarks/chip/program_spans.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1> [--out <file.json>]

The program opens ``dacapo.*`` spans at each layer boundary of its phase
loop (``src/repro/core/trace.py``), and each kernel counts the bytes of the
host arrays it hands to the device (``h2d_bytes``). ``trace_reduce.load``
keeps the spans with their line, ``trace_reduce.host_activity`` names an
idle gap by their self time, and the harness puts the window's
``h2d_bytes`` in its context. This module holds:

* :data:`METRICS`: five per-layer metrics, each ``read(ctx)`` on the
  harness's context (``None`` where there is nothing to read); each has a
  reader of its own in ``metrics/`` that calls it;
* :func:`run`: one run of a cell as ``run.py`` makes it, which also prints
  the idle gaps with their start, the engine line's time by span, the wall
  time of each phase of the window (and, traced, of each ``dacapo.phase``
  span), and the interpreter's garbage-collection pauses
  (:func:`gc_spans`), which stop every thread and so no program span can
  name.

The last line of standard output is one JSON object, also written to
``--out``. Its ``metrics`` are ``run.py``'s readers on the run's context,
and ``program_metrics`` the five of :data:`METRICS` on the same context.
"""
import time

T_START = time.perf_counter()  # set-up is timed from the process's start

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from collections import Counter, defaultdict  # noqa: E402
from typing import Any, Dict, List, Optional, Tuple  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]

import trace_reduce  # noqa: E402

def idle_gaps(ctx: Dict[str, Any], n: int = 10) -> List[list]:
    """The ``n`` longest idle gaps of the traced window, as
    ``harness.trace_summary`` picks them: ``[name, seconds, start]``, named
    by ``trace_reduce.host_activity``, ``start`` in seconds from the window's."""
    tr = ctx["trace"]
    win = trace_reduce.window(tr) if tr else None
    if win is None:
        return []
    found = [(g[1] - g[0], g)
             for iv in trace_reduce.chip_ops(tr, ctx["chips"])
             for g in trace_reduce.gaps(iv, *win)]
    return [[trace_reduce.host_activity(g, tr), d / 1e9,
             (g[0] - win[0]) / 1e9]
            for d, g in sorted(found, key=lambda f: -f[0])[:n]]


def _traced(tr):
    """The traced window and the engine line, or ``None``."""
    win = trace_reduce.window(tr) if tr else None
    line = trace_reduce.engine_line(tr) if win else None
    return None if line is None else (win, line)


def engine_self_s(tr) -> Dict[str, float]:
    """Seconds of the traced window the engine line spent in each program
    span by self time (longest first); ``dacapo.phase`` holds what no
    inner span covers."""
    got = _traced(tr)
    if got is None:
        return {}
    win, line = got
    held = trace_reduce.overlap_by_name(
        trace_reduce.self_time(trace_reduce.on_line(tr, line)), [win])
    return {k: v / 1e9 for k, v in sorted(held.items(), key=lambda kv: -kv[1])}


# ---------------------------------------------------------------- metrics


def _seconds_in(tr, win, name: str,
                line: Optional[trace_reduce.Line]) -> float:
    """Seconds of the window inside spans ``name``: on ``line``, or summed
    over each line where ``line`` is None."""
    per_line: Dict[trace_reduce.Line, list] = defaultdict(list)
    for n, s, e, ln in tr.program_spans:
        if n == name and (line is None or ln == line):
            per_line[ln].append((s, e))
    return sum(trace_reduce.busy_ns(iv, *win)
               for iv in per_line.values()) / 1e9


def _share(ctx, name: str, engine_only: bool = True):
    """Seconds in spans ``name`` (on the engine line, or on every line over
    the lanes) over the traced window."""
    tr = ctx.get("trace")
    got = _traced(tr)
    if got is None:
        return None
    win, line = got
    lanes = 1 if engine_only else ctx["lanes"]
    return _seconds_in(tr, win, name, line if engine_only else None) / (
        lanes * (win[1] - win[0]) / 1e9)


def data_wait_share(ctx):
    """Engine-line seconds in ``dacapo.data.wait`` (waiting on a prefetched
    window) over the traced window."""
    return _share(ctx, "dacapo.data.wait")


def data_synth_share(ctx):
    """Seconds in ``dacapo.data.synthesize`` on every thread (the prefetch
    workers and inline misses) over lanes x the traced window."""
    return _share(ctx, "dacapo.data.synthesize", engine_only=False)


def dispatch_collect_wait_share(ctx):
    """Engine-line seconds in ``dacapo.collect`` (materializing results on
    the host) over the traced window."""
    return _share(ctx, "dacapo.collect")


def device_idle_unattributed_share(ctx):
    """Share of the device's idle time in the traced window during which
    the innermost engine-line program span is ``dacapo.phase`` itself, or
    there is none; summed over the chips in use."""
    tr = ctx.get("trace")
    got = _traced(tr)
    if got is None:
        return None
    win, line = got
    chips = trace_reduce.chip_ops(tr, ctx["chips"])
    if not any(chips):
        return None
    pieces = trace_reduce.self_time(trace_reduce.on_line(tr, line))
    idle = attributed = 0.0
    for iv in chips:
        gaps = trace_reduce.gaps(iv, *win)
        idle += sum(e - s for s, e in gaps)
        held = trace_reduce.overlap_by_name(pieces, gaps)
        held.pop(trace_reduce.PHASE, None)
        attributed += sum(held.values())
    return 1.0 - attributed / idle if idle > 0 else None


def dispatch_h2d_bytes_per_cam_s(ctx):
    """Bytes of host arrays the kernels handed to the device in the window
    (``h2d_bytes``), per camera-second."""
    if ctx.get("h2d_bytes") is None or ctx["camera_s"] <= 0:
        return None
    return ctx["h2d_bytes"] / ctx["camera_s"]


# name -> (reader, unit)
METRICS = {
    "data.wait_share": (data_wait_share, "fraction"),
    "data.synth_share": (data_synth_share, "fraction"),
    "dispatch.collect_wait_share": (dispatch_collect_wait_share,
                                    "fraction"),
    "device.idle_unattributed_share": (device_idle_unattributed_share,
                                       "fraction"),
    "dispatch.h2d_bytes_per_cam_s": (dispatch_h2d_bytes_per_cam_s,
                                     "bytes/cam-s"),
}


def read_all(ctx) -> Dict[str, Dict[str, Any]]:
    """The five metrics that find something to read, as ``run.py`` prints
    metrics."""
    out = {}
    for name, (read, unit) in METRICS.items():
        value = read(ctx)
        if value is not None:
            out[name] = {"value": float(value), "unit": unit}
    return out


def phase_spans(tr) -> Dict[str, Any]:
    """The ``dacapo.phase`` spans inside the traced window: their wall
    seconds, and how many program spans (every line) start per phase."""
    got = _traced(tr)
    if got is None:
        return {}
    (lo, hi), _ = got
    inside = [(n, s, e) for n, s, e, _ in tr.program_spans if lo <= s < hi]
    phases = [(e - s) / 1e9 for n, s, e in inside
              if n == trace_reduce.PHASE and e <= hi]
    return {"phase_s": phases,
            "spans_per_phase": len(inside) / max(1, len(phases)),
            "spans_by_name": dict(Counter(n for n, _, _ in inside))}


# -------------------------------------------------------------------- gc
GC_SPAN = trace_reduce.SPAN_PREFIX + "gc"


@contextlib.contextmanager
def gc_spans():
    """Time each garbage collection while the block runs, as a span
    ``bench.gc`` in a running profiler's trace. Yields the list of pauses,
    ``(start, seconds)`` on ``time.perf_counter``."""
    import jax

    pauses: List[Tuple[float, float]] = []
    open_: Dict[str, Any] = {}

    def callback(phase, info):
        if phase == "start":
            open_["span"] = jax.profiler.TraceAnnotation(GC_SPAN)
            open_["span"].__enter__()
            open_["t"] = time.perf_counter()
        elif "span" in open_:
            open_.pop("span").__exit__(None, None, None)
            t = open_.pop("t")
            pauses.append((t, time.perf_counter() - t))

    gc.callbacks.append(callback)
    try:
        yield pauses
    finally:
        gc.callbacks.remove(callback)


def gc_idle_share(ctx) -> Optional[float]:
    """Share of the device's idle time in the traced window covered by a
    garbage-collection pause (``bench.gc`` on any line)."""
    tr = ctx.get("trace")
    win = trace_reduce.window(tr) if tr else None
    chips = trace_reduce.chip_ops(tr, ctx["chips"]) if win else []
    if not any(chips):
        return None
    pauses = [(s, e) for n, s, e in tr.spans if n == GC_SPAN]
    idle = covered = 0.0
    for iv in chips:
        gaps = trace_reduce.gaps(iv, *win)
        idle += sum(e - s for s, e in gaps)
        covered += sum(
            trace_reduce.busy_ns(pauses, s, e) for s, e in gaps)
    return covered / idle if idle > 0 else None


# ------------------------------------------------------------------- run
def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        log=print) -> Dict[str, Any]:
    """One run of ``cell`` through ``harness.run_cell``. Returns its result
    with ``phase_wall_s``: the wall seconds of each ``FleetRun.step`` in
    the window, and ``gc_pause_s``: the garbage-collection pauses in it."""
    import harness
    from repro.core.fleet import FleetRun

    walls: List[float] = []
    mark: Dict[str, Any] = {}

    class MarkingRecorder(harness.Recorder):
        """``harness.run_cell`` sets ``in_window`` at the window's start and
        clears it at its close: the phase walls and pauses are cut at
        both."""

        @property
        def in_window(self):
            return self._in_window

        @in_window.setter
        def in_window(self, value):
            self._in_window = value
            if value:
                mark.update(steps=len(walls), t0=time.perf_counter())
            elif "t0" in mark:
                mark["walls"] = walls[mark["steps"]:]
                mark["gc"] = [d for t, d in pauses if t >= mark["t0"]]

    step = FleetRun.step

    def timed_step(self):
        t0 = time.perf_counter()
        try:
            return step(self)
        finally:
            walls.append(time.perf_counter() - t0)

    saved = harness.Recorder
    harness.Recorder = MarkingRecorder
    FleetRun.step = timed_step
    try:
        with gc_spans() as pauses:
            out = harness.run_cell(cell, seed, seconds, trace, t_start,
                                   log=log)
    finally:
        harness.Recorder = saved
        FleetRun.step = step
    out["phase_wall_s"] = mark.get("walls", [])
    out["gc_pause_s"] = mark.get("gc", [])
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax

    import harness

    cell = harness.load_cell(args.workload)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"JAX's devices are {dev.platform!r}, not TPUs")
    harness.env_setup()
    out = run(cell, args.seed, args.seconds, bool(args.trace), T_START,
              log=lambda s: print(s, flush=True))
    ctx = out["ctx"]
    result = {"workload": args.workload, "seed": args.seed,
              "trace": args.trace, "correct": bool(out["correct"]),
              "metrics": harness.metrics_of(cell, ctx, bool(args.trace)),
              "program_metrics": read_all(ctx),
              "h2d_bytes": ctx["h2d_bytes"], "camera_s": ctx["camera_s"],
              "phases": ctx["phases"], "phase_wall_s": out["phase_wall_s"],
              "gc_pause_s": {"count": len(out["gc_pause_s"]),
                             "total": sum(out["gc_pause_s"]),
                             "longest": sorted(out["gc_pause_s"])[-5:]},
              "memory_peak_bytes": out["memory_peak"]}
    if args.trace:
        summary = harness.trace_summary(ctx) or {}
        result.update(
            busy_s=summary.get("busy_s"), window_s=summary.get("window_s"),
            device_ops=summary.get("device_ops"),
            idle_gaps_bench=summary.get("idle_gaps"),
            idle_gaps=idle_gaps(ctx),
            engine_self_s=engine_self_s(ctx["trace"]),
            gc_idle_share=gc_idle_share(ctx), **phase_spans(ctx["trace"]))
    line = json.dumps(result)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")
    print(line, flush=True)


if __name__ == "__main__":
    main()
