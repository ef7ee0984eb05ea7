"""The benchmark's yardstick on the CPU: trace reduction, operation
counts, the peak table, the reference against the program, and finding a
new cell's files by name."""
import dataclasses
import json

import _chipbench_path  # noqa: F401
import jax
import numpy as np
import pytest

import flops
import harness
import peaks
import reference
import trace_reduce

MS = 1_000_000  # ns

# Paper Table III: forward GFLOPs (multiply-accumulates, in fact) per frame.
TABLE_III_GMACS = {"resnet18": 1.82, "resnet34": 3.67, "vit-b32": 4.37,
                   "wideresnet50": 11.43, "vit-b16": 16.87,
                   "wideresnet101": 22.80}


def _cfg(name):
    from repro.configs.dacapo_pairs import VISION_MODELS
    return dataclasses.asdict(VISION_MODELS[name])


def test_interval_union_clip_and_gaps():
    ops = [(0, 10), (5, 20), (30, 40), (38, 45), (90, 120)]
    assert trace_reduce.union(ops) == [(0, 20), (30, 45), (90, 120)]
    assert trace_reduce.clip(ops, 8, 100) == [(8, 10), (8, 20), (30, 40),
                                              (38, 45), (90, 100)]
    assert trace_reduce.busy_ns(ops, 8, 100) == 12 + 15 + 10
    assert trace_reduce.gaps(ops, 8, 100) == [(20, 30), (45, 90)]
    assert trace_reduce.gaps([], 0, 5) == [(0, 5)]
    assert trace_reduce.busy_ns([], 0, 5) == 0


def test_program_times_sum_runs_that_start_in_the_window():
    mods = [("jit_apply(11)", 0 * MS, 2 * MS),
            ("jit__sgd_step(7)", 3 * MS, 8 * MS),
            ("jit_apply(12)", 9 * MS, 12 * MS),
            ("jit__sgd_step(7)", 14 * MS, 30 * MS)]
    got = trace_reduce.program_times(mods, 1 * MS, 20 * MS)
    assert got == {"_sgd_step": (2, 5 * MS + 6 * MS), "apply": (1, 3 * MS)}
    assert trace_reduce.program_name("jit__quant_leaf(3)") == "_quant_leaf"


def test_idle_gap_goes_to_the_innermost_span_covering_it():
    spans = [("bench.window", 0, 100), ("bench.phase", 0, 100),
             ("bench.frames", 40, 60), ("bench.fit", 58, 70)]
    tr = trace_reduce.Trace({}, {}, spans)  # no program spans in it
    assert trace_reduce.host_activity((42, 58), tr) == "frames"
    assert trace_reduce.host_activity((60, 70), tr) == "fit"
    assert trace_reduce.host_activity((80, 90), tr) == "phase"
    assert trace_reduce.bench_activity((80, 90), spans[:1]) == "other"


def test_trace_window_is_the_longest_window_span():
    tr = trace_reduce.Trace({}, {}, [("bench.window", 5, 9),
                                     ("bench.window", 10, 50)])
    assert trace_reduce.window(tr) == (10, 50)
    assert trace_reduce.window(trace_reduce.Trace({}, {}, [])) is None


def test_idle_share_averages_over_the_cells_chips_only():
    tr = trace_reduce.Trace({"/device:TPU:10": [(0, 100)],
                             "/device:TPU:1": [],
                             "/device:TPU:0": [(0, 50)]}, {},
                            [("bench.window", 0, 100)])
    assert trace_reduce.chip_ops(tr, 1) == [[(0, 50)]]
    assert trace_reduce.chip_ops(tr, 4) == [[(0, 50)], [], [(0, 100)], []]
    read = harness.metric_reader("device.idle_share")
    assert read({"trace": tr, "chips": 1}) == 0.5
    assert read({"trace": tr, "chips": 2}) == 0.75
    assert read({"trace": trace_reduce.Trace({}, {}, tr.spans),
                 "chips": 1}) is None


@pytest.mark.parametrize("name", sorted(TABLE_III_GMACS))
def test_forward_flops_match_table_iii(name):
    gmacs = flops.forward_flops(_cfg(name)) / 2 / 1e9
    assert gmacs == pytest.approx(TABLE_III_GMACS[name], rel=0.05)


def test_sgd_counts_three_forwards_per_sample():
    cfg = _cfg("resnet18")
    assert flops.sgd_flops(cfg, 16) == 48 * flops.forward_flops(cfg)
    assert flops.sgd_bytes(cfg, 16) > 4 * 11.6e6 * 4


def test_peak_table_refuses_an_unknown_device():
    assert peaks.peak("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peak("cpu")


@pytest.mark.parametrize("name", ["resnet18", "wideresnet50", "vit-b32"])
def test_reference_matches_the_program_at_reduced_widths(name):
    from repro.configs.dacapo_pairs import VISION_MODELS
    from repro.core import mx
    from repro.models.registry import make_vision_model

    vc = VISION_MODELS[name].reduced()
    cfg = dataclasses.asdict(vc)
    params = jax.jit(lambda k: reference.init_params(cfg, k))(
        np.array([0, 5], np.uint32))
    program = make_vision_model(vc)
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(
                program.init(jax.random.PRNGKey(0))))
    x = np.random.default_rng(0).normal(
        size=(3, vc.img_size, vc.img_size, 3)).astype(np.float32)
    want = np.asarray(jax.jit(program.apply)(params, x))
    got = reference.Reference(cfg).forward(params, x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for prec in ("mx4", "mx6", "mx9"):
        a = jax.tree_util.tree_leaves(mx.quantize_tree(params, prec))
        b = jax.tree_util.tree_leaves(reference.mx_fake_quant(params, prec))
        assert all(np.array_equal(np.asarray(u), np.asarray(v))
                   for u, v in zip(a, b))


def test_new_cell_and_metric_files_are_found_by_name(tmp_path):
    """A later cell is data: a traffic file, a config file and a metric
    file under their names, plus entries in BENCHMARK.json."""
    for sub in ("traffic", "metrics", "configs"):
        (tmp_path / sub).mkdir()
    traffic = harness.load_json(harness.BENCH_DIR / "traffic"
                                / "cam1-30fps.json")
    traffic["lanes"] = 8
    (tmp_path / "traffic" / "fleet8-30fps.json").write_text(
        json.dumps(traffic))
    config = harness.load_json(harness.BENCH_DIR / "configs"
                               / "resnet18-wrn50.json")
    (tmp_path / "configs" / "new-pair.json").write_text(json.dumps(config))
    (tmp_path / "metrics" / "lanes.count.py").write_text(
        "def read(ctx):\n    return ctx['lanes']\n")
    bench = {
        "configs": [{"name": "new-pair",
                     "file": str(tmp_path / "configs" / "new-pair.json")}],
        "workloads": [{"name": "new-pair.fleet8-30fps", "config": "new-pair",
                       "traffic": "fleet8-30fps", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "lanes.count", "unit": "count"}]}
    cell = harness.load_cell("new-pair.fleet8-30fps", bench,
                             bench_dir=tmp_path)
    assert cell.traffic["lanes"] == 8
    assert cell.config["student"]["name"] == "resnet18"
    assert [m["name"] for m in cell.per_layer] == ["lanes.count"]
    got = harness.metrics_of(cell, {"lanes": 8}, trace=True,
                             bench_dir=tmp_path)
    assert got == {"lanes.count": {"value": 8.0, "unit": "count"}}
    with pytest.raises(KeyError, match="no workload"):
        harness.load_cell("absent", bench, bench_dir=tmp_path)


def test_benchmark_json_names_a_file_for_every_metric_and_cell():
    bench = harness.load_json(harness.BENCHMARK_JSON)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (harness.BENCH_DIR / "metrics" / f"{m['name']}.py").is_file()
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"], bench)
        assert cell.config["reduced"] == []
        assert set(cell.config["limits"]) == {
            "sgd_loss", "sgd_grad", "sgd_update", "label_gap", "serve_gap"}


@pytest.mark.parametrize("kind", ["resnet", "vit"])
def test_reference_sgd_step_matches_the_program(kind):
    """The SGD step, with the max-pool path of frames above 64 px."""
    from repro.configs.dacapo_pairs import RESNET18, VIT_B32
    from repro.core.allocation import CLHyperParams
    from repro.core.kernel import RetrainKernel
    from repro.models.registry import make_vision_model

    if kind == "resnet":
        vc = dataclasses.replace(RESNET18, img_size=72, base=8,
                                 num_classes=10)
    else:
        vc = VIT_B32.reduced()
    cfg = dataclasses.asdict(vc)
    hp = CLHyperParams()
    params = jax.jit(lambda k: reference.init_params(cfg, k))(
        np.array([3, 9], np.uint32))
    mom = jax.tree_util.tree_map(lambda a: 0.1 * a, params)
    rng = np.random.default_rng(1)
    x = rng.normal(size=(4, vc.img_size, vc.img_size, 3)).astype(np.float32)
    y = rng.integers(0, vc.num_classes, 4).astype(np.int32)
    kernel = RetrainKernel(make_vision_model(vc), vc, None, hp)
    want = jax.jit(kernel._sgd_step)(params, mom, x, y)
    got = reference.Reference(cfg).step(hp.lr, params, mom, x, y)
    for w, g in zip(jax.tree_util.tree_leaves(want[:3]),
                    jax.tree_util.tree_leaves(got[:3])):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-6)


def test_training_gaps_compare_vectors_not_norms():
    """A gradient turned at the same per-leaf norms (as half a batch turns
    it) reads about 0 by norms and fails by vectors, under every
    configuration's limits."""
    import check

    rng = np.random.default_rng(7)
    shapes = {"a": (64, 32), "b": (32,), "c": (8, 8, 4)}
    grad = {k: rng.normal(size=s).astype(np.float32)
            for k, s in shapes.items()}
    turned = {k: np.roll(g, 1) for k, g in grad.items()}  # same norms
    p0 = {k: rng.normal(size=s).astype(np.float32) for k, s in shapes.items()}
    lr = 1e-3
    truth = {"loss": [2.0, 1.9, 1.8], "grad1": grad,
             "params3": {k: p0[k] - 3 * lr * g for k, g in grad.items()}}
    got = {"loss": [2.0, 1.9, 1.8], "mom1": turned,
           "params3": {k: p0[k] - 3 * lr * g for k, g in turned.items()}}

    def by_norms(a, b):
        return max(abs(np.linalg.norm(a[k]) - np.linalg.norm(b[k]))
                   / np.linalg.norm(b[k]) for k in shapes)

    assert by_norms(turned, grad) < 1e-6
    gaps = check.training_gaps(truth, p0, got)
    assert gaps["sgd_loss"] == 0
    assert gaps["sgd_grad"] > 1 and gaps["sgd_update"] > 1
    for name in ("vitb32-vitb16", "resnet18-wrn50"):
        limits = harness.load_json(harness.BENCH_DIR / "configs"
                                   / f"{name}.json")["limits"]
        answers = {"label_gap": 0.0, "serve_gap": 0.0}
        assert not check.correct({**gaps, **answers}, limits)
        assert check.correct({**gaps, **answers, "sgd_grad": 0.0,
                              "sgd_update": 0.0}, limits)
    same = check.training_gaps(truth, p0, {**got, "mom1": grad,
                                           "params3": truth["params3"]})
    assert same["sgd_grad"] == 0 and same["sgd_update"] == 0
