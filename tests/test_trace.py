"""Trace & replay subsystem tests: off-by-default bit-identity, traced-run
bit-identity in both dispatch modes, bitwise-exact phase replay, JSON
round-trip, the per-role dependency DAG, kernel-path capture, calibration,
the "dacapo-replay" allocation policy, and deterministic merged manager
traces under overlapped (parallel) shard stepping."""
import dataclasses
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.dacapo_pairs import RESNET18, WIDERESNET50
from repro.core.allocation import ALLOCATORS, CLHyperParams, ReplayAllocator
from repro.core.estimator import CalibratedEstimator, DaCapoEstimator
from repro.core.fleet import FleetSpec
from repro.core.manager import ManagerSpec
from repro.core.replay import TraceReplayer
from repro.core.session import CLSystemSpec, pretrain_model
from repro.core.trace import SessionTrace, TraceEvent, TraceRecorder
from repro.data.stream import DriftStream, scenario
from repro.kernels import ops
from repro.models.registry import make_vision_model


@pytest.fixture(scope="module")
def pretrained():
    stream = DriftStream(scenario("S1", 2), seed=5, img=24)
    hp = CLHyperParams(n_t=32, n_l=16, c_b=128, epochs=1)
    rng = np.random.default_rng(0)
    tp = pretrain_model(make_vision_model(WIDERESNET50.reduced()), stream,
                        10, 32, rng)
    sp = pretrain_model(make_vision_model(RESNET18.reduced()), stream, 8,
                        32, rng, segments=stream.segments[:1], seed=8)
    return hp, tp, sp


def _run(pretrained, dispatch, trace, allocator="dacapo-spatiotemporal",
         duration=30.0, eval_fps=0.5):
    hp, tp, sp = pretrained
    stream = DriftStream(scenario("S1", 2), seed=5, img=24)
    spec = CLSystemSpec(student=RESNET18, teacher=WIDERESNET50,
                        allocator=allocator, hp=hp, apply_mx=False, seed=0,
                        eval_fps=eval_fps, dispatch=dispatch, trace=trace)
    session = spec.build()
    session.set_pretrained(tp, sp)
    res = session.run(stream, duration=duration)
    return res, session.dispatcher.recorder


@pytest.fixture(scope="module")
def traced_runs(pretrained):
    """One traced + one untraced run per dispatch mode, shared by the
    identity/replay tests below."""
    runs = {}
    for mode in ("sequential", "concurrent"):
        runs[mode, False] = _run(pretrained, mode, None)
        runs[mode, True] = _run(pretrained, mode, True)
    return runs


# ------------------------------------------------------------- off-switch
def test_trace_off_by_default(traced_runs):
    """trace=None leaves the dispatcher recorder-free: no trace objects,
    no events, nothing on the hot path."""
    for mode in ("sequential", "concurrent"):
        _, recorder = traced_runs[mode, False]
        assert recorder is None


@pytest.mark.parametrize("mode", ["sequential", "concurrent"])
def test_traced_run_bit_identical(traced_runs, mode):
    """Recording is observation-only: accuracy, ledgers and the phase log
    are bitwise identical with tracing on and off."""
    r_off, _ = traced_runs[mode, False]
    r_on, recorder = traced_runs[mode, True]
    assert recorder is not None and len(recorder) > 0
    assert r_off.avg_accuracy == r_on.avg_accuracy
    assert r_off.retrain_time == r_on.retrain_time
    assert r_off.label_time == r_on.label_time
    assert r_off.phase_log == r_on.phase_log


# ----------------------------------------------------------- exact replay
@pytest.mark.parametrize("mode", ["sequential", "concurrent"])
def test_replay_bitwise_exact(traced_runs, mode):
    """predict() with no candidate reconstructs every phase-end clock
    bit-for-bit in both dispatch semantics — including after a JSON
    round trip."""
    _, recorder = traced_runs[mode, True]
    trace = recorder.trace
    rep = TraceReplayer(trace)
    for i, ph in enumerate(trace.phases):
        assert rep.phase_time(i) == ph.end
    rep2 = TraceReplayer(SessionTrace.from_json(trace.to_json()))
    for i, ph in enumerate(trace.phases):
        assert rep2.phase_time(i) == ph.end


def test_replay_from_units_within_mape(traced_runs):
    """Histogram-priced (from_units) predictions stay within 5% MAPE of
    the recorded concurrent phase times."""
    _, recorder = traced_runs["concurrent", True]
    trace = recorder.trace
    rep = TraceReplayer(trace)
    errs = [abs(rep.predict(i, from_units=True) - ph.end) / ph.end
            for i, ph in enumerate(trace.phases) if ph.end > 0]
    assert errs
    assert 100.0 * sum(errs) / len(errs) < 5.0


def test_replay_cross_mode_what_if(traced_runs):
    """Replaying a sequential trace under mode="concurrent" predicts the
    concurrent run's first phase end exactly (virtual costs are
    deterministic, and the two runs share a history of zero phases), and
    never predicts less than the recorded sequential end for any phase:
    concurrent adds the ``start + t_BSA`` arm to the same max, while the
    sequential clock is the T-SA chain alone (seed semantics)."""
    _, rec_seq = traced_runs["sequential", True]
    _, rec_con = traced_runs["concurrent", True]
    rep = TraceReplayer(rec_seq.trace)
    assert rep.predict(0, mode="concurrent") == pytest.approx(
        rec_con.phases[0].end, rel=1e-6)
    for i, ph in enumerate(rec_seq.phases):
        assert rep.predict(i, mode="concurrent") >= ph.end


def test_replay_dag_structure(traced_runs):
    """Sequential: one serial chain. Concurrent: per-role chains joined
    at the phase-end barrier."""
    _, rec_seq = traced_runs["sequential", True]
    rep = TraceReplayer(rec_seq.trace)
    d = rep.dag(0)
    events = rec_seq.phases[0].events
    assert len(d["nodes"]) == len(events)
    for node in d["nodes"][1:]:
        assert node.deps == (node.id - 1,)
    assert d["tails"] == [len(events) - 1]

    _, rec_con = traced_runs["concurrent", True]
    rep = TraceReplayer(rec_con.trace)
    d = rep.dag(0)
    roles = {e.role for e in rec_con.phases[0].events}
    assert len(d["tails"]) == len(roles)
    for node in d["nodes"]:
        for dep in node.deps:
            assert d["nodes"][dep].event.role == node.event.role


# ------------------------------------------------------------ trace model
def test_trace_json_rejects_wrong_format():
    with pytest.raises(ValueError):
        SessionTrace.from_dict({"format": "not-a-trace", "phases": []})


def test_trace_event_round_trip():
    e = TraceEvent(kind="program", role="t_sa", label="valid", cost_s=0.25,
                   lane=3, wall_s=0.01, path="pallas", units=48.0, fan=2)
    assert TraceEvent.from_dict(e.as_dict()) == e


def test_dominant_path_capture():
    """paths_before/dominant_path bracket an issue: the kernel path whose
    counter moved is recorded (eager ref-mode op so the counter moves on
    every call, not only at jit trace time)."""
    rec = TraceRecorder()
    ops.reset_kernel_stats()
    before = rec.paths_before()
    prev = os.environ.get("REPRO_KERNEL_MODE")
    os.environ["REPRO_KERNEL_MODE"] = "ref"
    try:
        x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 32)),
                        jnp.float32)
        ops.mx_quantize(x, "mx6")
    finally:
        if prev is None:
            os.environ.pop("REPRO_KERNEL_MODE", None)
        else:
            os.environ["REPRO_KERNEL_MODE"] = prev
    assert rec.dominant_path(before) == "ref"
    # No movement -> empty path; capture_paths=False -> no snapshots.
    assert rec.dominant_path(rec.paths_before()) == ""
    assert TraceRecorder(capture_paths=False).paths_before() is None


# ------------------------------------------------------------- calibration
def test_calibrate_scales_estimator(traced_runs):
    _, recorder = traced_runs["concurrent", True]
    cal = TraceReplayer(recorder.trace).calibrate()
    assert "retrain" in cal.scales and cal.scales["retrain"] > 0
    assert cal.global_scale > 0
    assert cal.seconds("retrain", 2.0) == 2.0 * cal.scales["retrain"]
    est = cal.estimator(DaCapoEstimator())
    assert isinstance(est, CalibratedEstimator)
    base = DaCapoEstimator()
    cfg = RESNET18.reduced()
    assert est.forward_time(cfg, 8, "mx9") == pytest.approx(
        est.forward_scale * base.forward_time(cfg, 8, "mx9"))
    assert est.train_step_time(cfg, 8, "mx9", 16) == pytest.approx(
        est.train_scale * base.train_step_time(cfg, 8, "mx9", 16))
    assert est.total_rows == base.total_rows


# ----------------------------------------------------- replay-scored policy
def test_replay_allocator_registered():
    assert ALLOCATORS["dacapo-replay"] is ReplayAllocator
    assert ReplayAllocator.needs_trace


def test_replay_allocator_runs_and_charges_profile(pretrained):
    """dacapo-replay auto-enables the recorder, scores candidates by
    replay, and charges the measured replay wall to profile_cost_s."""
    res, recorder = _run(pretrained, "concurrent", None,
                         allocator="dacapo-replay", eval_fps=2.0)
    assert recorder is not None  # needs_trace flipped the default on
    assert len(recorder) > 0
    costs = [ph.decisions[0].get("profile_cost_s")
             for ph in recorder.phases if ph.decisions]
    assert any(c and c > 0 for c in costs[1:])
    assert res.avg_accuracy >= 0.0


# ----------------------------------------- manager merged-trace determinism
def _manager_trace(pretrained, workers):
    hp, tp, sp = pretrained
    fleet = FleetSpec(student=RESNET18, teacher=WIDERESNET50, hp=hp,
                      fleet_mode="drift-weighted", apply_mx=False, seed=0,
                      eval_fps=0.5, dispatch="concurrent")
    mgr = ManagerSpec(fleet=fleet, n_shards=3, placement="static",
                      migration=False, parallel_shards=workers,
                      trace=True).build()
    mgr.set_pretrained(tp, sp)
    streams = [DriftStream(scenario(name, 2), seed=seed, img=24)
               for name, seed in [("S1", 5), ("S3", 6), ("ES1", 7)]]
    result = mgr.run(streams, duration=40.0)
    return result, mgr.trace


def test_manager_parallel_trace_deterministic(pretrained):
    """Under parallel_shards the merged manager trace is drained at the
    round barrier in shard-index order: identical — phase for phase,
    event for event, shard stamp for shard stamp — to serial stepping,
    and the traced parallel run stays bit-identical to the untraced
    serial result."""
    res_serial, tr_serial = _manager_trace(pretrained, workers=0)
    res_par, tr_par = _manager_trace(pretrained, workers=3)
    assert res_par.parallel_rounds > 0
    assert res_serial.fleet_avg_accuracy == res_par.fleet_avg_accuracy
    assert res_serial.ledger == res_par.ledger
    assert len(tr_serial.phases) == len(tr_par.phases) > 0
    for a, b in zip(tr_serial.phases, tr_par.phases):
        assert a.shard == b.shard
        assert a.start == b.start and a.end == b.end
        assert len(a.events) == len(b.events)
        for ea, eb in zip(a.events, b.events):
            # wall_s is measured host time — everything else is virtual
            # and must be bitwise identical across stepping modes.
            assert dataclasses.replace(ea, wall_s=0.0) \
                == dataclasses.replace(eb, wall_s=0.0)
    assert {ph.shard for ph in tr_par.phases} == {0, 1, 2}


# --------------------------------------------- profiler spans and h2d bytes
# Every span of the phase loop that nests under ``dacapo.phase`` on the
# engine's thread (``dacapo.data.synthesize`` runs on the prefetch worker).
PHASE_SPANS = (
    "plan", "retrain", "score", "label", "barrier", "decide",
    "issue.valid", "issue.label", "issue.acc_label", "issue.score",
    "collect", "data.frames", "data.wait", "buffer.update", "buffer.get",
    "fit", "fit.gather", "fit.step", "quantize")


def _profiled(fn):
    """Run ``fn`` under an in-memory profiler session; returns its result
    and the program's spans as ``(line, name, start_ns, end_ns)``, where
    ``line`` is (plane name, line index): one line per host thread."""
    import jax
    from jax._src.lib import _profiler

    from repro.core.trace import SPAN_PREFIX

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    session = _profiler.ProfilerSession(opts)
    try:
        out = fn()
    finally:
        data = session.stop_and_get_profile_data()
    spans = [((plane.name, i), e.name[len(SPAN_PREFIX):], e.start_ns,
              e.start_ns + e.duration_ns)
             for plane in data.planes for i, line in enumerate(plane.lines)
             for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return out, spans


class _FrameCounter:
    """Counts the host bytes of the frames and labels handed to the
    kernels' entry points, read off each call's arrays."""

    def __init__(self, session):
        self.nbytes = 0
        inf, lab, ret = session.inference, session.labeling, session.retrain
        predict, label, step = inf.predict_async, lab.label_async, ret._step

        def count(*arrays):
            self.nbytes += sum(a.nbytes for a in arrays
                               if isinstance(a, np.ndarray))

        def predict_counted(params, x):
            count(x)
            return predict(params, x)

        def label_counted(params, x, precision, microbatch=None):
            count(x)
            return label(params, x, precision, microbatch)

        def step_counted(params, opt, x, y):
            count(x, y)
            return step(params, opt, x, y)

        inf.predict_async, lab.label_async = predict_counted, label_counted
        ret._step = step_counted


def _engine_run(pretrained, engine, profile):
    """A concurrent run of one camera on the reduced twins, with the MX
    serving copies on: through the fleet engine or the session engine."""
    hp, tp, sp = pretrained
    stream = DriftStream(scenario("S1", 2), seed=5, img=24)
    kw = dict(student=RESNET18, teacher=WIDERESNET50, hp=hp, apply_mx=True,
              seed=0, eval_fps=0.5, dispatch="concurrent")
    spec = FleetSpec(**kw) if engine == "fleet" else CLSystemSpec(**kw)
    session = spec.build()
    session.set_pretrained(tp, sp)
    counter = _FrameCounter(session)

    def go():
        result = session.run(stream, duration=40.0)
        return result.streams[0] if engine == "fleet" else result

    if not profile:
        return go(), None, session, counter
    result, spans = _profiled(go)
    return result, spans, session, counter


@pytest.fixture(scope="module", params=["fleet", "session"])
def profiled_runs(request, pretrained):
    return (request.param,
            _engine_run(pretrained, request.param, profile=True),
            _engine_run(pretrained, request.param, profile=False))


def _engine_line(spans):
    lines = {line for line, name, _, _ in spans if name == "phase"}
    assert len(lines) == 1, lines
    return lines.pop()


def test_span_helper_names_a_trace_annotation():
    import jax

    from repro.core.trace import span
    with span("phase") as s:
        assert isinstance(s, jax.profiler.TraceAnnotation)


def test_program_spans_nest_under_the_phase_on_one_line(profiled_runs):
    """Every span of the phase loop appears inside a ``dacapo.phase`` span
    on the engine's line."""
    _, (result, spans, _, _), _ = profiled_runs
    assert sum(r.spec_hits for r in result.records) > 0
    engine = _engine_line(spans)
    phases = [(s, e) for line, name, s, e in spans
              if line == engine and name == "phase"]
    # A phase that reaches the duration mid-phase leaves no record.
    assert len(result.records) >= 3
    assert len(phases) - len(result.records) in (0, 1)
    nested = {name for line, name, s, e in spans if line == engine
              and any(p0 <= s and e <= p1 for p0, p1 in phases)}
    assert set(PHASE_SPANS) <= nested, set(PHASE_SPANS) - nested


def test_frame_synthesis_runs_on_the_prefetch_worker_line(profiled_runs):
    _, (_, spans, _, _), _ = profiled_runs
    engine = _engine_line(spans)
    worker = {line for line, name, _, _ in spans
              if name == "data.synthesize" and line != engine}
    assert len(worker) == 1
    assert not {name for line, name, _, _ in spans
                if line in worker} - {"data.synthesize"}


def test_profiled_run_is_bit_identical_to_an_unprofiled_one(profiled_runs):
    _, (r_on, _, s_on, _), (r_off, _, s_off, _) = profiled_runs
    assert r_on.avg_accuracy == r_off.avg_accuracy
    assert r_on.accuracy_timeline == r_off.accuracy_timeline
    assert r_on.phase_log == r_off.phase_log
    for k_on, k_off in zip(s_on.kernels, s_off.kernels):
        assert k_on.n_apply_calls == k_off.n_apply_calls
        assert k_on.h2d_bytes == k_off.h2d_bytes


def test_h2d_bytes_count_the_frames_and_labels_handed_over(profiled_runs):
    """Frames served, labeled and trained on, and the SGD labels: the
    kernels' counters against the arrays the run handed them."""
    _, (_, _, session, counter), _ = profiled_runs
    frame = 24 * 24 * 3 * np.dtype(np.float32).itemsize
    inf, lab, ret = session.kernels
    assert inf.h2d_bytes > 0 and lab.h2d_bytes > 0 and ret.h2d_bytes > 0
    assert inf.h2d_bytes % frame == 0 and lab.h2d_bytes % frame == 0
    # Each SGD batch brings its labels as int32.
    n_sgd = ret.n_apply_calls * session.hp.sgd_batch
    assert ret.h2d_bytes == n_sgd * (frame + 4)
    assert sum(k.h2d_bytes for k in session.kernels) == counter.nbytes


def test_h2d_bytes_count_padded_fleet_batches_not_device_arrays():
    import jax
    import jax.numpy as jnp

    from repro.core.kernel import InferenceKernel

    vc = RESNET18.reduced()
    model = make_vision_model(vc)
    kernel = InferenceKernel(model, RESNET18, DaCapoEstimator(),
                             apply_mx=False)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    frames = [rng.normal(size=(n, 24, 24, 3)).astype(np.float32)
              for n in (3, 5)]
    kernel.predict_fleet_async([params, params], frames)
    assert kernel.h2d_bytes == 2 * frames[1].nbytes  # padded to 5 rows
    kernel.predict_async(params, jnp.asarray(frames[0]))
    assert kernel.h2d_bytes == 2 * frames[1].nbytes
    kernel.predict_batched(params, frames)
    assert kernel.h2d_bytes == 3 * frames[1].nbytes + frames[0].nbytes


def test_pooled_window_renders_in_chunks_inside_its_synthesis(monkeypatch):
    """A window above the fan-out threshold is one ``data.synthesize`` span
    on the asking thread and ``data.render`` spans on the render pool's
    threads, each inside it in time."""
    import repro.data.stream as stream_mod
    from repro.data.pipeline import FramePipeline
    from repro.data.stream import DriftStream, scenario

    monkeypatch.setattr(stream_mod, "_render_threads", lambda: 4)
    monkeypatch.setattr(stream_mod, "_pool", None)
    pipe = FramePipeline(DriftStream(scenario("S1", 1), seed=5, img=224),
                         speculative=False)
    try:
        (x, _), spans = _profiled(lambda: pipe.frames(0.0, 0.5))
    finally:
        stream_mod._pool.shutdown(wait=True)
    assert len(x) == 15 and pipe.stream.frames_pooled == 15
    synth = [(line, s, e) for line, name, s, e in spans
             if name == "data.synthesize"]
    assert len(synth) == 1
    line, s0, e0 = synth[0]
    render = [(ln, s, e) for ln, name, s, e in spans if name == "data.render"]
    assert len(render) == 8  # two chunks for each of the four threads
    assert all(ln != line and s0 <= s and e <= e0 for ln, s, e in render)
