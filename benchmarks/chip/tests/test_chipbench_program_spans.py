"""The program's spans and host-to-device counter as the benchmark reads
them, on the CPU: the loader and the self-time rule that names an idle gap
(``trace_reduce``), the five metric readers of ``program_spans.py`` on a
hand-built trace with program spans on two lines, and a traced run of the
reduced cell, read by ``run.py``'s readers and by ``program_spans``."""
import threading
import time

import _chipbench_path  # noqa: F401
import jax
import numpy as np
import pytest

import harness
import program_spans
import trace_reduce
from test_chipbench_harness import SEED, reduced_cell

MS = 1_000_000  # ns
ENGINE, WORKER = ("/host:CPU", 1), ("/host:CPU", 2)


def hand_built(lanes=1):
    """A 100-ms traced window. The engine line: a phase with the frames of
    a window (mostly waiting on the prefetch) and a barrier (collect, then
    the score flush); the worker line: two syntheses, and a span the engine
    line's metrics must not count. The chip is busy at 0-10, 40-50 and
    90-95 ms, so idle over 10-40, 50-90 and 95-100 ms."""
    bench = [("bench.window", 0, 100 * MS), ("bench.phase", 0, 100 * MS)]
    engine = [("dacapo.phase", 0, 100), ("dacapo.plan", 0, 5),
              ("dacapo.data.frames", 10, 40), ("dacapo.data.wait", 12, 38),
              ("dacapo.barrier", 50, 90), ("dacapo.collect", 55, 65),
              ("dacapo.issue.score", 70, 88)]
    worker = [("dacapo.data.synthesize", 5, 45),
              ("dacapo.data.synthesize", 50, 60),
              ("dacapo.collect", 0, 50)]
    spans = ([(n, s * MS, e * MS, ENGINE) for n, s, e in engine]
             + [(n, s * MS, e * MS, WORKER) for n, s, e in worker])
    ops = {"/device:TPU:0": [(0, 10 * MS), (40 * MS, 50 * MS),
                             (90 * MS, 95 * MS)]}
    tr = trace_reduce.Trace(ops, {}, bench, spans)
    return {"trace": tr, "chips": 1, "lanes": lanes}


def test_trace_keeps_the_three_argument_constructor():
    tr = trace_reduce.Trace({}, {}, [("bench.window", 0, 5)])
    assert tr.program_spans == [] and trace_reduce.window(tr) == (0, 5)
    assert trace_reduce.engine_line(tr) is None


def test_self_time_gives_each_instant_to_the_innermost_span():
    spans = [("a", 0, 100), ("b", 10, 40), ("c", 12, 38), ("d", 40, 60),
             ("e", 200, 210)]
    assert trace_reduce.self_time(spans) == [
        (0, 10, "a"), (10, 12, "b"), (12, 38, "c"), (38, 40, "b"),
        (40, 60, "d"), (60, 100, "a"), (200, 210, "e")]
    pieces = trace_reduce.self_time(spans)
    assert trace_reduce.overlap_by_name(pieces, [(5, 15), (35, 45)]) == {
        "a": 5, "b": 4, "c": 6, "d": 5}


def test_idle_gap_is_named_by_self_time_of_program_spans():
    tr = hand_built()["trace"]
    # wait holds 26 of the 30 ms; frames only 4.
    assert trace_reduce.host_activity((10 * MS, 40 * MS),
                                      tr) == "dacapo.data.wait"
    # barrier 12, collect 10, the score flush 18.
    assert trace_reduce.host_activity((50 * MS, 90 * MS),
                                      tr) == "dacapo.issue.score"
    # Only dacapo.phase covers it: the benchmark's rule, unchanged.
    assert trace_reduce.host_activity((95 * MS, 100 * MS), tr) == "phase"
    assert trace_reduce.host_activity(
        (95 * MS, 100 * MS), trace_reduce.Trace({}, {}, [])) == "other"


def test_gap_rule_without_program_spans_is_the_benchmarks_rule():
    spans = [("bench.window", 0, 100), ("bench.phase", 0, 100),
             ("bench.frames", 40, 60), ("bench.fit", 58, 70)]
    for gap in ((42, 58), (60, 70), (80, 90)):
        assert (trace_reduce.host_activity(
            gap, trace_reduce.Trace({}, {}, spans))
            == trace_reduce.bench_activity(gap, spans))


def test_idle_gaps_are_the_longest_named_by_the_rule():
    ctx = hand_built()
    assert program_spans.idle_gaps(ctx, 2) == [
        ["dacapo.issue.score", 0.04, 0.05], ["dacapo.data.wait", 0.03, 0.01]]


def test_engine_self_time_splits_the_window_by_innermost_span():
    got = program_spans.engine_self_s(hand_built()["trace"])
    assert list(got)[:2] == ["dacapo.data.wait", "dacapo.phase"]
    assert got == pytest.approx({
        "dacapo.phase": 0.025, "dacapo.data.wait": 0.026,
        "dacapo.plan": 0.005, "dacapo.data.frames": 0.004,
        "dacapo.barrier": 0.012, "dacapo.collect": 0.01,
        "dacapo.issue.score": 0.018})
    assert sum(got.values()) == pytest.approx(0.1)


@pytest.mark.parametrize("name,lanes,want", [
    ("data.wait_share", 1, 26 / 100),
    ("data.synth_share", 1, 50 / 100),
    ("data.synth_share", 2, 50 / 200),
    ("dispatch.collect_wait_share", 1, 10 / 100),
    ("device.idle_unattributed_share", 1, 5 / 75),
])
def test_span_metric_on_a_hand_built_trace(name, lanes, want):
    read = program_spans.METRICS[name][0]
    assert read(hand_built(lanes)) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "data.wait_share", "data.synth_share", "dispatch.collect_wait_share",
    "device.idle_unattributed_share"])
def test_span_metric_reads_nothing_without_program_spans(name):
    tr = hand_built()["trace"]
    read = program_spans.METRICS[name][0]
    assert read({"trace": trace_reduce.Trace(tr.ops, {}, tr.spans),
                 "chips": 1, "lanes": 1}) is None
    assert read({"trace": None, "chips": 1, "lanes": 1}) is None


def test_h2d_metric_reads_the_window_counter_per_camera_second():
    read = program_spans.METRICS["dispatch.h2d_bytes_per_cam_s"][0]
    assert read({"h2d_bytes": 2.5e9, "camera_s": 100.0}) == 2.5e7
    assert read({"h2d_bytes": None, "camera_s": 100.0}) is None
    assert read({"camera_s": 100.0}) is None


def test_load_keeps_benchmark_and_program_spans_with_their_lines():
    prof = harness.Profiler()

    def worker():
        with jax.profiler.TraceAnnotation("dacapo.data.synthesize"):
            time.sleep(0.002)

    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("dacapo.phase"):
                with jax.profiler.TraceAnnotation("dacapo.plan"):
                    th = threading.Thread(target=worker)
                    th.start()
                    th.join()
    finally:
        tr = prof.stop()
    assert [n for n, _, _ in tr.spans] == ["bench.window"]
    names = {n: ln for n, _, _, ln in tr.program_spans}
    assert set(names) == {"dacapo.phase", "dacapo.plan",
                          "dacapo.data.synthesize"}
    assert names["dacapo.phase"] == names["dacapo.plan"]
    assert names["dacapo.data.synthesize"] != names["dacapo.phase"]
    assert trace_reduce.engine_line(tr) == names["dacapo.phase"]


def test_gc_pauses_are_timed_and_traced():
    import gc

    callbacks = list(gc.callbacks)
    prof = harness.Profiler()
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with program_spans.gc_spans() as pauses:
                gc.collect()
    finally:
        tr = prof.stop()
    assert len(pauses) >= 1 and all(d >= 0 for _, d in pauses)
    assert program_spans.GC_SPAN in {n for n, _, _ in tr.spans}
    assert gc.callbacks == callbacks


def test_gc_idle_share_is_the_idle_time_under_pauses():
    tr = trace_reduce.Trace({"/device:TPU:0": [(0, 10)]}, {},
                            [("bench.window", 0, 100), ("bench.gc", 5, 25)])
    got = program_spans.gc_idle_share({"trace": tr, "chips": 1})
    assert got == pytest.approx(15 / 90)
    assert program_spans.gc_idle_share({"trace": None, "chips": 1}) is None


def test_traced_run_reads_the_program_metrics_and_h2d_bytes():
    """The reduced cell, traced on the CPU: no device plane here, so the
    device's metric reads nothing; the counter equals the frames handed to
    the kernels in the window (served, labeled, SGD) and the SGD labels;
    ``run.py``'s readers give the same five numbers."""
    cell = reduced_cell()
    out = program_spans.run(cell, SEED, 0.5, True, time.perf_counter(),
                            log=lambda s: None)
    assert out["correct"], out["readings"]
    ctx = out["ctx"]
    got = program_spans.read_all(ctx)
    assert set(got) == set(program_spans.METRICS) - {
        "device.idle_unattributed_share"}
    assert 0 < got["data.synth_share"]["value"] <= 1
    assert 0 <= got["data.wait_share"]["value"] < 1
    assert 0 < got["dispatch.collect_wait_share"]["value"] < 1
    img = cell.config["student"]["img_size"]
    frame = img * img * 3 * np.dtype(np.float32).itemsize
    sgd = ctx["sgd_steps"] * ctx["sgd_batch"]
    assert ctx["h2d_bytes"] == (
        (ctx["rows_served"] + ctx["rows_labeled"] + sgd) * frame + sgd * 4)
    assert len(out["phase_wall_s"]) >= ctx["phases"] >= 1
    assert all(d >= 0 for d in out["gc_pause_s"])
    phases = program_spans.phase_spans(ctx["trace"])
    assert phases["phase_s"] and phases["spans_per_phase"] > 10
    # The readers' arithmetic, as if the counts had come from a chip.
    read = harness.metrics_of(cell, dict(ctx, device_kind="TPU v5 lite"),
                              trace=True)
    assert {k: read[k] for k in got} == got
