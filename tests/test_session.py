"""Kernel/Session API tests: golden equivalence vs. the seed monolith,
per-kernel unit tests, the AllocationPolicy contract over all four
allocators, observer delivery, and engine-driven mesh partitioning.

The GOLDEN constants below were captured by running the pre-refactor
``ContinuousLearningSystem.run()`` (the ~110-line monolithic loop) on this
exact fixture before the decomposition; the compat wrapper and the new
``CLSession`` must reproduce them to 1e-6.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.dacapo_pairs import RESNET18, WIDERESNET50
from repro.core.allocation import (
    ALLOCATORS,
    AllocationDecision,
    CLHyperParams,
    PhaseFeedback,
)
from repro.core.cl_system import ContinuousLearningSystem
from repro.core.estimator import DaCapoEstimator
from repro.core.kernel import (
    InferenceKernel,
    Kernel,
    LabelingKernel,
    RetrainKernel,
    ServingParamsCache,
)
from repro.core.session import CLSession, CLSystemSpec, pretrain_model
from repro.data.stream import DriftStream, scenario
from repro.models.registry import make_vision_model

# Seed-capture: scenario("S1", 3) seed=5 img=24; hp(48, 24, c_b=192);
# pretrain rng(0), teacher 25x32, student 15x32 on segments[:1] seed=8;
# duration 90 s; apply_mx False; eval_fps 0.5.
GOLDEN = {
    "dacapo-spatiotemporal": dict(
        avg_accuracy=0.32608695652173914, phases=23, drifts=9,
        retrain_time=54.54179220000003, label_time=36.060292799999985),
    "ekya": dict(avg_accuracy=0.6704545454545454, phases=1, drifts=0),
    "eomu": dict(avg_accuracy=0.42857142857142855, phases=9, drifts=0),
}
GOLDEN_MX_ST_45S = 0.4166666666666667


@pytest.fixture(scope="module")
def golden_setup():
    stream = DriftStream(scenario("S1", 3), seed=5, img=24)
    hp = CLHyperParams(n_t=48, n_l=24, c_b=192, epochs=1)
    rng = np.random.default_rng(0)
    teacher_model = make_vision_model(WIDERESNET50.reduced())
    student_model = make_vision_model(RESNET18.reduced())
    tp = pretrain_model(teacher_model, stream, 25, 32, rng)
    sp = pretrain_model(student_model, stream, 15, 32, rng,
                        segments=stream.segments[:1], seed=8)
    return stream, hp, tp, sp


def _build(hp, allocator, apply_mx=False, mesh=None) -> CLSession:
    return CLSystemSpec(
        student=RESNET18, teacher=WIDERESNET50, allocator=allocator,
        hp=hp, apply_mx=apply_mx, seed=0, eval_fps=0.5, mesh=mesh).build()


# ------------------------------------------------------------------ golden
@pytest.mark.parametrize("allocator", sorted(GOLDEN))
def test_golden_equivalence_via_spec(golden_setup, allocator):
    """CLSession reproduces the seed monolith bit-for-bit (1e-6)."""
    stream, hp, tp, sp = golden_setup
    session = _build(hp, allocator)
    session.set_pretrained(tp, sp)
    res = session.run(stream, duration=90.0)
    gold = GOLDEN[allocator]
    assert abs(res.avg_accuracy - gold["avg_accuracy"]) < 1e-6
    assert len(res.phase_log) == gold["phases"]
    assert res.drift_events == gold["drifts"]
    if "retrain_time" in gold:
        assert abs(res.retrain_time - gold["retrain_time"]) < 1e-6
        assert abs(res.label_time - gold["label_time"]) < 1e-6


def test_golden_equivalence_compat_wrapper(golden_setup):
    """The legacy ContinuousLearningSystem facade hits the same goldens."""
    stream, hp, tp, sp = golden_setup
    sys_ = ContinuousLearningSystem(
        RESNET18, WIDERESNET50, hp=hp, allocator="dacapo-spatiotemporal",
        apply_mx_numerics=False, seed=0, eval_fps=0.5)
    sys_.set_pretrained(tp, sp)
    res = sys_.run(stream, duration=90.0)
    gold = GOLDEN["dacapo-spatiotemporal"]
    assert abs(res.avg_accuracy - gold["avg_accuracy"]) < 1e-6
    assert res.drift_events == gold["drifts"]
    # Legacy attribute surface still reachable through the facade.
    assert sys_.r_tsa + sys_.r_bsa == sys_.estimator.total_rows
    assert sys_.scheduler.name == "dacapo-spatiotemporal"


def test_golden_equivalence_mx_numerics(golden_setup):
    """The MX6-serving quantization path also matches the seed capture."""
    stream, hp, tp, sp = golden_setup
    session = _build(hp, "dacapo-spatiotemporal", apply_mx=True)
    session.set_pretrained(tp, sp)
    res = session.run(stream, duration=45.0)
    assert abs(res.avg_accuracy - GOLDEN_MX_ST_45S) < 1e-6


# ----------------------------------------------------------------- kernels
@pytest.fixture(scope="module")
def kernel_setup():
    est = DaCapoEstimator()
    hp = CLHyperParams(n_t=32, n_l=16, sgd_batch=8, epochs=1)
    model = make_vision_model(RESNET18.reduced())
    params = model.init(jax.random.PRNGKey(0))
    x = np.asarray(
        jax.random.normal(jax.random.PRNGKey(1), (12, 24, 24, 3)),
        np.float32)
    return est, hp, model, params, x


def test_inference_kernel(kernel_setup):
    est, hp, model, params, x = kernel_setup
    k = InferenceKernel(model, RESNET18, est, apply_mx=False)
    assert isinstance(k, Kernel) and k.role == "b_sa"
    pred = k.predict(params, x)
    assert pred.shape == (12,)
    assert np.all((0 <= pred) & (pred < RESNET18.reduced().num_classes))
    # Cost comes straight from the estimator; fewer rows -> slower.
    assert k.time_per_sample(4, "mx6") == est.forward_time(
        RESNET18, 4, "mx6", batch=1)
    assert k.time_per_sample(2, "mx6") > k.time_per_sample(8, "mx6")
    assert 0.0 < k.keep_frac(1, "mx6", target_fps=30.0) <= 1.0
    assert k.keep_frac(est.total_rows, "mx4", target_fps=1e-6) == 1.0
    # No MX -> serving params pass through untouched.
    assert k.serving_params(params, "mx6") is params
    # MX -> same tree structure, weights fake-quantized.
    kq = InferenceKernel(model, RESNET18, est, apply_mx=True)
    q = kq.serving_params(params, "mx6")
    assert (jax.tree_util.tree_structure(q)
            == jax.tree_util.tree_structure(params))


def test_labeling_kernel(kernel_setup):
    est, hp, model, params, x = kernel_setup
    k = LabelingKernel(model, WIDERESNET50, est, apply_mx=False)
    assert isinstance(k, Kernel) and k.role == "t_sa"
    y = k.label(params, x, "mx6")
    assert y.shape == (12,) and y.dtype.kind == "i"
    # Labeling cost uses the teacher's (bigger) GEMM list.
    k_small = LabelingKernel(model, RESNET18, est, apply_mx=False)
    assert k.time_per_sample(8, "mx6") > k_small.time_per_sample(8, "mx6")


def test_retrain_kernel(kernel_setup):
    est, hp, model, params, x = kernel_setup
    k = RetrainKernel(model, RESNET18, est, hp)
    assert isinstance(k, Kernel) and k.role == "t_sa"
    opt = k.init_state(params)
    y = np.zeros((12,), np.int32)
    rng = np.random.default_rng(0)
    new_params, new_opt, n_batches = k.fit(params, opt, x, y, rng)
    # Charged batches == executed batches (see test_dispatch for the
    # sub-batch D_t case, which executes — and charges — zero steps).
    assert n_batches == (len(x) // hp.sgd_batch) * hp.epochs
    # Parameters actually moved and stayed finite.
    leaves_before = jax.tree_util.tree_leaves(params)
    leaves_after = jax.tree_util.tree_leaves(new_params)
    assert any(not np.allclose(a, b)
               for a, b in zip(leaves_before, leaves_after))
    assert all(np.all(np.isfinite(np.asarray(leaf)))
               for leaf in leaves_after)
    # Training costs 3x a forward per sample (fwd + dX + dW GEMMs).
    assert k.time_per_batch(8, "mx9") == pytest.approx(
        3.0 * est.forward_time(RESNET18, 8, "mx9", hp.sgd_batch))


# --------------------------------------------- serving-copy cache (PR 7) --
def test_serving_cache_hits_and_misses(kernel_setup):
    est, hp, model, params, x = kernel_setup
    k = InferenceKernel(model, RESNET18, est, apply_mx=True)
    q1 = k.serving_params(params, "mx6")
    assert k.serving_cache.stats() == {"hits": 0, "misses": 1, "entries": 1}
    # Same tree, same precision -> hit, SAME quantized object.
    q2 = k.serving_params(params, "mx6")
    assert q2 is q1
    assert k.serving_cache.stats() == {"hits": 1, "misses": 1, "entries": 1}
    # Same tree, other precision -> miss, shares the entry.
    k.serving_params(params, "mx9")
    assert k.serving_cache.stats() == {"hits": 1, "misses": 2, "entries": 1}
    # A fresh tree (what fit returns) -> miss under a new entry.
    params2 = jax.tree_util.tree_map(lambda p: p + 0, params)
    k.serving_params(params2, "mx6")
    assert k.serving_cache.stats() == {"hits": 1, "misses": 3, "entries": 2}
    # apply_mx=False bypasses the cache entirely.
    k_raw = InferenceKernel(model, RESNET18, est, apply_mx=False)
    assert k_raw.serving_params(params, "mx6") is params
    assert k_raw.serving_cache.stats()["misses"] == 0


def test_serving_cache_maxsize_zero_disables(kernel_setup):
    from repro.core.kernel import ServingParamsCache

    est, hp, model, params, x = kernel_setup
    cache = ServingParamsCache(maxsize=0)
    q1 = cache.get(params, "mx6")
    q2 = cache.get(params, "mx6")
    assert q1 is not q2  # re-quantized every call
    assert cache.stats() == {"hits": 0, "misses": 2, "entries": 0}
    # LRU eviction at maxsize=1: the older tree's entry is dropped.
    small = ServingParamsCache(maxsize=1)
    params2 = jax.tree_util.tree_map(lambda p: p + 0, params)
    small.get(params, "mx6")
    small.get(params2, "mx6")
    assert len(small) == 1
    small.get(params, "mx6")
    assert small.stats()["misses"] == 3  # evicted -> re-quantize


def test_serving_cache_concurrent_gets_count_exactly(kernel_setup):
    """Under overlapped shard stepping the cache is shared process-global
    state: 8 threads hammering the same (tree, precision) must lose no
    counter increments, and — because each slot carries its own fill
    guard — quantize the tree exactly once, even though the cache-wide
    lock is no longer held across the fill."""
    import threading

    est, hp, model, params, x = kernel_setup
    cache = ServingParamsCache(maxsize=8)
    n_threads, per_thread = 8, 50
    start = threading.Barrier(n_threads)
    fills = []

    def fake_quantize(tree, precision):
        fills.append(precision)
        return {"q": precision}

    def worker():
        start.wait()
        for _ in range(per_thread):
            cache.get(params, "mx9", quantize=fake_quantize)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stats = cache.stats()
    assert stats["hits"] + stats["misses"] == n_threads * per_thread
    assert stats["misses"] == 1 and len(fills) == 1
    assert stats == {"hits": 399, "misses": 1, "entries": 1}


def test_serving_cache_fill_not_under_cache_lock(kernel_setup):
    """PR 9 regression: a slow fill of one tree must NOT serialize lookups
    of a different tree. The old cache quantized under its RLock, so lane
    B's first serving request waited on lane A's whole-tree quantization;
    now only the per-slot guard is held across the fill."""
    import threading

    est, hp, model, params, x = kernel_setup
    params_b = jax.tree_util.tree_map(lambda p: p + 0, params)
    cache = ServingParamsCache(maxsize=8)
    entered = threading.Event()
    release = threading.Event()
    order = []

    def slow_quantize(tree, precision):
        entered.set()
        release.wait(timeout=10.0)
        order.append("a")
        return {"tree": "a"}

    def fast_quantize(tree, precision):
        order.append("b")
        return {"tree": "b"}

    t = threading.Thread(
        target=lambda: cache.get(params, "mx6", quantize=slow_quantize))
    t.start()
    assert entered.wait(timeout=10.0)
    # Lane A's fill is in flight. Under the old lock-across-fill design
    # this get would deadlock until the release below; now it completes
    # immediately on its own slot.
    got_b = cache.get(params_b, "mx6", quantize=fast_quantize)
    assert got_b == {"tree": "b"}
    assert order == ["b"]
    release.set()
    t.join(timeout=10.0)
    assert not t.is_alive()
    assert order == ["b", "a"]
    assert cache.stats() == {"hits": 0, "misses": 2, "entries": 2}
    assert cache.fills == 2
    # Both slots memoized: repeat gets are hits on the same objects.
    assert cache.get(params, "mx6", quantize=slow_quantize) == {"tree": "a"}
    assert cache.stats()["hits"] == 1


def test_serving_cache_resident_quantized_storage(kernel_setup):
    """The default fill stores the RESIDENT quantized rep (MXLeaf weight
    leaves); `get` lazily dequantizes — once — to a tree bit-identical to
    the legacy ``quantize_tree`` output, and ``get_quantized`` hands the
    resident copy out without ever dequantizing."""
    from repro.core import mx as mx_lib

    est, hp, model, params, x = kernel_setup
    cache = ServingParamsCache(maxsize=8)
    value = cache.get(params, "mx6")
    legacy = mx_lib.quantize_tree(params, "mx6")
    for v, l in zip(jax.tree_util.tree_leaves(value),
                    jax.tree_util.tree_leaves(legacy)):
        np.testing.assert_array_equal(np.asarray(v), np.asarray(l))
    # The resident copy shares the slot: no second whole-tree quantize.
    resident = cache.get_quantized(params, "mx6")
    assert any(isinstance(leaf, mx_lib.MXLeaf)
               for leaf in jax.tree_util.tree_leaves(
                   resident, is_leaf=lambda p: isinstance(p, mx_lib.MXLeaf)))
    assert cache.fills == 1
    assert cache.stats() == {"hits": 1, "misses": 1, "entries": 1}
    # Repeat gets return the SAME memoized dequantized tree.
    assert cache.get(params, "mx6") is value
    assert cache.fills == 1


def test_inference_serving_prequant_matches_fake_quant(kernel_setup):
    """Prequant serving == fake-quant serving bit-for-bit: predictions off
    the cache's lazily-dequantized resident copy equal predictions off a
    fresh ``quantize_tree`` tree, and the resident copy's head weight
    round-trips to exactly the served fake-quant head."""
    from repro.core import mx as mx_lib
    from repro.kernels import ops

    est, hp, model, params, x = kernel_setup
    k = InferenceKernel(model, RESNET18, est, apply_mx=True)
    serving = k.serving_params(params, "mx6")
    legacy = mx_lib.quantize_tree(params, "mx6")
    np.testing.assert_array_equal(k.predict(serving, x),
                                  k.predict(legacy, x))
    resident = k.serving_quantized(params, "mx6")
    back = mx_lib.dequantize_tree_mx(resident)
    for b, l in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(legacy)):
        np.testing.assert_array_equal(np.asarray(b), np.asarray(l))
    # One fill total: serving_params and serving_quantized share the slot.
    assert k.serving_cache.fills == 1


def test_labeling_cache_repeated_bursts_hit(kernel_setup):
    est, hp, model, params, x = kernel_setup
    k = LabelingKernel(model, WIDERESNET50, est, apply_mx=True)
    y1 = k.label(params, x, "mx6")
    y2 = k.label(params, x, "mx6")
    np.testing.assert_array_equal(y1, y2)
    # One quantize for N bursts: the teacher tree never changes.
    assert k.serving_cache.stats() == {"hits": 1, "misses": 1, "entries": 1}


def test_retrain_fit_invalidates_serving_caches(kernel_setup):
    est, hp, model, params, x = kernel_setup
    inf = InferenceKernel(model, RESNET18, est, apply_mx=True)
    ret = RetrainKernel(model, RESNET18, est, hp)
    ret.invalidates = (inf.serving_cache,)
    inf.serving_params(params, "mx6")
    assert len(inf.serving_cache) == 1
    y = np.zeros((12,), np.int32)
    new_params, _, _ = ret.fit(params, ret.init_state(params), x, y,
                               np.random.default_rng(0))
    # The superseded tree's entry is reclaimed; the new tree misses fresh.
    assert len(inf.serving_cache) == 0
    inf.serving_params(new_params, "mx6")
    assert inf.serving_cache.stats()["misses"] == 2
    assert inf.serving_cache.stats()["hits"] == 0


def test_session_wires_retrain_invalidation(golden_setup):
    stream, hp, tp, sp = golden_setup
    session = _build(hp, "dacapo-spatiotemporal", apply_mx=True)
    assert session.inference.serving_cache in session.retrain.invalidates


# ------------------------------------------------------- policy contract --
@pytest.mark.parametrize("name", sorted(ALLOCATORS))
def test_allocation_policy_contract(name):
    """Every allocator: binds, emits complete decisions, stays in bounds."""
    hp = CLHyperParams(n_t=64, n_l=32, v_thr=-0.05)
    est = DaCapoEstimator()
    pol = ALLOCATORS[name](hp).bind(est, RESNET18)
    assert pol.name == name
    decisions = [pol.initial_decision()]
    # A healthy stretch, a drift-y cliff, then recovery.
    feedback = [(0.8, 0.82), (0.8, 0.81), (0.9, 0.3), (0.5, 0.55),
                (0.6, 0.62)]
    for i, (av, al) in enumerate(feedback):
        decisions.append(pol.next_decision(
            PhaseFeedback(acc_valid=av, acc_label=al, t=float(i))))
    for d in decisions:
        assert isinstance(d, AllocationDecision)
        # Spatial rows: bound policies always carry a full split.
        assert d.rows_tsa is not None and d.rows_bsa is not None
        assert d.rows_tsa + d.rows_bsa == est.total_rows
        # Temporal budgets within Table I bounds.
        assert 0 <= d.retrain_samples <= hp.n_t
        assert d.valid_samples == hp.n_v
        assert hp.n_l <= d.total_label_samples <= hp.n_ldd
        # Per-kernel precisions travel on the decision.
        assert d.precisions.inference == "mx6"
        assert d.precisions.retraining == "mx9"
    resets = [d.reset_buffer for d in decisions]
    # dacapo-replay is DC-ST plus replay-scored boosts, so it shares the
    # drift-reactive contract: the cliff must flush the merged buffer.
    if name.startswith("dacapo-spatiotemporal") or name == "dacapo-replay":
        assert any(resets)  # the cliff at (0.9, 0.3) must fire
    else:
        assert not any(resets)


def test_all_allocators_run_through_session(golden_setup):
    """Acceptance: all four allocators execute via CLSystemSpec/CLSession."""
    stream, hp, tp, sp = golden_setup
    for name in sorted(ALLOCATORS):
        session = _build(hp, name)
        assert isinstance(session, CLSession)
        session.set_pretrained(tp, sp)
        res = session.run(stream, duration=30.0)
        assert res.name == name
        assert res.avg_accuracy > 0.0
        ts = [t for t, _ in res.accuracy_timeline]
        assert ts == sorted(ts)


# -------------------------------------------------------------- observers --
def test_observers_receive_structured_records(golden_setup):
    stream, hp, tp, sp = golden_setup
    session = _build(hp, "dacapo-spatiotemporal")
    session.set_pretrained(tp, sp)
    seen = []
    session.add_observer(seen.append)
    extra = []
    res = session.run(stream, duration=30.0, observers=(extra.append,))
    assert len(seen) == len(res.phase_log) == len(extra) == len(res.records)
    for i, rec in enumerate(seen):
        assert rec.index == i
        assert isinstance(rec.decision, AllocationDecision)
        assert rec.as_log_entry() == res.phase_log[i]
        assert 0.0 <= rec.acc_label <= 1.0


# ------------------------------------------------------------ mesh wiring --
def test_engine_partitions_fake_mesh(golden_setup):
    """partition_mesh is invoked by the engine: a fake 2-row mesh is
    fissioned into T-SA/B-SA sub-meshes and each kernel is bound to its
    sub-accelerator; the run still reproduces sane results."""
    from jax.sharding import Mesh

    stream, hp, tp, sp = golden_setup
    devs = np.array(jax.devices() * 2).reshape(2, 1)  # fake 2-row mesh
    mesh = Mesh(devs, ("data", "model"))
    session = _build(hp, "dacapo-spatiotemporal", mesh=mesh)
    assert not session.partition.time_shared
    assert session.partition.t_sa.devices.shape == (1, 1)
    assert session.partition.b_sa.devices.shape == (1, 1)
    # Kernel placement follows the roles.
    assert session.inference.submesh is session.partition.b_sa
    assert session.labeling.submesh is session.partition.t_sa
    assert session.retrain.submesh is session.partition.t_sa
    session.set_pretrained(tp, sp)
    res = session.run(stream, duration=30.0)
    assert res.avg_accuracy > 0.0
    # Single-device sessions degenerate to time-sharing (no sub-meshes).
    flat = _build(hp, "dacapo-spatiotemporal")
    assert flat.partition.time_shared
    assert flat.inference.submesh is None


# Runs in a child process: the CPU device count is fixed when JAX starts.
_FISSION_CHILD = r"""
import json
import jax
import numpy as np
from repro.configs.dacapo_pairs import RESNET18, WIDERESNET50
from repro.core.allocation import CLHyperParams
from repro.core.partition import forced_row_mesh
from repro.core.session import CLSystemSpec, pretrain_model
from repro.data.stream import DriftStream, scenario
from repro.models.registry import make_vision_model

assert jax.device_count() == 4, jax.devices()
stream = DriftStream(scenario("S1", 3), seed=5, img=24)
hp = CLHyperParams(n_t=48, n_l=24, c_b=192, epochs=1)
rng = np.random.default_rng(0)
tp = pretrain_model(make_vision_model(WIDERESNET50.reduced()), stream, 10,
                    16, rng)
sp = pretrain_model(make_vision_model(RESNET18.reduced()), stream, 5, 16,
                    rng, segments=stream.segments[:1], seed=8)
out = {}
for name, mesh in (("fission", forced_row_mesh(4)), ("flat", None)):
    session = CLSystemSpec(student=RESNET18, teacher=WIDERESNET50, hp=hp,
                           eval_fps=0.5, dispatch="concurrent",
                           mesh=mesh).build()
    session.set_pretrained(tp, sp)
    res = session.run(stream, duration=30.0)
    out[name] = {"phases": [[r.acc_valid, r.acc_label] for r in res.records],
                 "timeline": res.accuracy_timeline}
    if mesh is None:
        continue
    x = stream.frames(0.0, 1.0, max_frames=8)[0]
    prec = session.policy
    served = session.inference.predict_async(
        session.inference.serving_params(session.student_params,
                                         prec.inference), x)
    labels = session.labeling.label_async(session.teacher_params, x,
                                          prec.labeling)
    leaves = jax.tree_util.tree_leaves(session.student_params)
    ids = lambda arrays: sorted({d.id for a in arrays for d in a.devices()})
    out["devices"] = {
        "t_sa": session.partition.t_sa.devices.flat[0].id,
        "b_sa": session.partition.b_sa.devices.flat[0].id,
        "served": ids([served]), "labels": ids([labels]),
        "retrained": ids(leaves + [session.retrain.last_loss])}
print(json.dumps(out))
"""


def test_fission_on_four_devices_matches_one_device():
    """T-SA/B-SA fission on four distinct (virtual CPU) devices: each
    kernel's parameters follow it to its sub-accelerator's device, outputs
    land there, and per-phase accuracies equal the same session on one
    device (the same programs, only placed elsewhere)."""
    import json
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   p for p in (src, os.environ.get("PYTHONPATH")) if p),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4"))
    proc = subprocess.run([sys.executable, "-c", _FISSION_CHILD], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    dev = out["devices"]
    assert dev["t_sa"] != dev["b_sa"]
    assert dev["served"] == [dev["b_sa"]]
    assert dev["labels"] == [dev["t_sa"]]
    assert dev["retrained"] == [dev["t_sa"]]
    assert len(out["fission"]["phases"]) >= 3
    assert out["fission"] == out["flat"]


def test_spec_published_widths_builds_unreduced():
    """``reduced=False`` executes the configs as given (published widths,
    224-px frames, 1000-class heads); the estimator prices the same full
    configs either way, so the offline row split is unchanged. Built only:
    nothing is initialized or run at this size on a CPU."""
    spec = CLSystemSpec(student=RESNET18, teacher=WIDERESNET50,
                        reduced=False, dispatch="concurrent")
    session = spec.build()
    assert session.student_cfg == RESNET18
    assert session.teacher_cfg == WIDERESNET50
    assert session.student.cfg.num_classes == 1000
    assert session.teacher.cfg.img_size == 224
    assert session.retrain.model is session.student
    assert session.labeling.model is session.teacher
    twins = dataclasses.replace(spec, reduced=True).build()
    assert twins.student_cfg == RESNET18.reduced()
    assert twins.teacher_cfg == WIDERESNET50.reduced()
    assert (session.r_tsa, session.r_bsa) == (twins.r_tsa, twins.r_bsa)
    assert session.full_student == twins.full_student == RESNET18


def test_spec_is_declarative_and_replaceable(golden_setup):
    """Benchmark-style partial specs are completed via dataclasses.replace."""
    stream, hp, tp, sp = golden_setup
    partial = CLSystemSpec(allocator="eomu", apply_mx=False)
    with pytest.raises(ValueError):
        partial.build()
    spec = dataclasses.replace(partial, student=RESNET18,
                               teacher=WIDERESNET50, hp=hp, eval_fps=0.5)
    session = spec.build()
    assert session.allocator.name == "eomu"
    assert session.allocator.pace_window_s == 10.0
