"""The harness's run on the CPU at the reduced twins: a sound run is
correct, and each fault the check must catch, planted in the program
underneath, makes ``correct`` false. The bfloat16 control fails too. A
three-lane cell that serves its lanes in one fleet program has that
program's rows recorded and checked.

The look for a chip is ``run.py``'s and is skipped here; everything after
it (weights, set-up phases, window, check) runs as on the chip.
"""
import dataclasses
import time

import _chipbench_path  # noqa: F401
import jax.numpy as jnp
import pytest

import check
import control
import harness
from repro.configs.dacapo_pairs import VisionConfig
from repro.core.kernel import InferenceKernel, LabelingKernel, RetrainKernel

WORKLOAD = "vitb32-vitb16.cam1-30fps"  # its reduced twins are the cheapest
SEED = 2 ** 31 + 4321  # wider than 32 signed bits, as checks pass them


def reduced_cell():
    cell = harness.load_cell(WORKLOAD)
    for role in ("student", "teacher"):
        cell.config[role] = dataclasses.asdict(
            VisionConfig(**cell.config[role]).reduced())
    cell.traffic["hp"].update(n_t=32, n_l=16, c_b=128)  # CPU-sized phases
    cell.traffic["warmup"] = {"min_phases": 2, "max_phases": 3}
    return cell


def fleet_cell():
    """The reduced cell with three lanes, served by one fleet program. Its
    set-up runs phases enough for lane 0's first three SGD steps."""
    cell = reduced_cell()
    cell.traffic.update(lanes=3, serve_batched=True)
    cell.traffic["warmup"] = {"min_phases": 7, "max_phases": 7}
    return cell


def run(cell, seconds=0.5):
    return harness.run_cell(cell, SEED, seconds, False, time.perf_counter(),
                            log=lambda s: None)


@pytest.fixture(scope="module")
def sound():
    cell = reduced_cell()
    return cell, run(cell)


def test_sound_run_is_correct_and_reports_its_metrics(sound):
    cell, out = sound
    assert out["correct"], out["readings"]
    ctx = out["ctx"]
    assert ctx["phases"] >= 1 and ctx["sgd_steps"] > 0
    assert ctx["rows_served"] > 0 and ctx["rows_labeled"] > 0
    assert out["recorded"]["served"] and out["recorded"]["labeled"]
    got = harness.metrics_of(cell, ctx, trace=False)
    assert set(got) == {"camera_s_per_s", "setup_s"}
    assert got["camera_s_per_s"]["value"] > 0


def test_per_layer_metrics_without_a_trace_leave_out_the_device(sound):
    cell, out = sound
    with pytest.raises(KeyError, match="no published peak"):
        harness.metric_reader("mfu")(out["ctx"])
    # The readers' arithmetic, as if the counts had come from a chip.
    ctx = dict(out["ctx"], device_kind="TPU v5 lite")
    got = harness.metrics_of(cell, ctx, trace=True)
    for name in ("device.idle_share", "kernel.sgd_roofline",
                 "kernel.forward_roofline"):
        assert name not in got  # no device trace, nothing to read
    assert got["dispatch.programs_per_cam_s"]["value"] > 0
    assert 0 < got["data.fetch_share"]["value"] < 1
    assert 0 < got["mfu"]["value"] < 100


def test_bfloat16_control_in_the_programs_place_fails(sound):
    cell, out = sound
    s_params, t_params = out["weights"]
    rec = out["recorded"]
    truth = check.Truth(cell.config, out["lr"], s_params, t_params, rec)
    ctrl = check.Truth(cell.config, out["lr"], s_params, t_params, rec,
                       precision="bfloat16")
    sides = control.side_readings(truth, ctrl, s_params, rec, cell.config)
    limits = cell.config["limits"]
    for side in ("control", "half_batch", "state_unchanged",
                 "answer_altered"):
        # Judged as the harness judges the program, with the program's own
        # numbers where the side changes nothing.
        assert not check.correct({**out["readings"], **sides[side]},
                                 limits), side


def test_fleet_serving_is_recorded_for_every_lane(monkeypatch):
    """Rows served by ``predict_fleet_async`` reach the check, from every
    lane, and ``rows_served`` counts the frames of the windows, not the
    padded batch; a one-lane call counts once, in ``predict_async``."""
    seen = {"fleet": 0, "single": 0, "lanes": set(), "calls": 0}

    class Spy(harness.Recorder):
        def __init__(self, session, *args, **kwargs):
            super().__init__(session, *args, **kwargs)
            inf = session.inference
            fleet, single = inf.predict_fleet_async, inf.predict_async

            def fleet_spy(params_list, windows):
                before = len(self.served)
                out = fleet(params_list, windows)
                if self.in_window and len(windows) > 1:
                    seen["calls"] += 1
                    seen["fleet"] += sum(len(w) for w in windows)
                    seen["lanes"].update(c[0] for c in self.served[before:])
                    assert len(self.served) - before == len(windows)
                return out

            def single_spy(params, x):
                if self.in_window:
                    seen["single"] += len(x)
                return single(params, x)

            inf.predict_fleet_async, inf.predict_async = fleet_spy, single_spy

    monkeypatch.setattr(harness, "Recorder", Spy)
    out = run(fleet_cell())
    assert out["correct"], out["readings"]
    assert seen["calls"] >= 1 and seen["lanes"] == {0, 1, 2}
    assert out["ctx"]["rows_served"] == seen["fleet"] + seen["single"]
    assert {lane for lane, *_ in out["recorded"]["served"]} == {0, 1, 2}


def _state_unchanged(self, params, opt, x, y):
    loss = RetrainKernel._sgd_step.__wrapped__(self, params, opt, x, y)[2]
    return params, opt, loss


def _half_batch(self, params, opt, x, y):
    half = x.shape[0] // 2
    return RetrainKernel._sgd_step.__wrapped__(self, params, opt, x[:half],
                                               y[:half])


def _labels_altered(self, params, x, precision, microbatch=None):
    out = LabelingKernel.label_async.__wrapped__(self, params, x, precision,
                                                 microbatch)
    return (out + 1) % self.model.cfg.num_classes


def _served_altered(self, params, x):
    out = InferenceKernel.predict_async.__wrapped__(self, params, x)
    return jnp.where(jnp.arange(out.shape[0]) % 2 == 0,
                     (out + 1) % self.model.cfg.num_classes, out)


def _fleet_served_altered(self, params_list, windows):
    out = InferenceKernel.predict_fleet_async.__wrapped__(self, params_list,
                                                          windows)
    n = self.model.cfg.num_classes
    return [jnp.where(jnp.arange(p.shape[0]) % 2 == 0, (p + 1) % n, p)
            for p in out]


@pytest.mark.parametrize("cls,name,fault,cell", [
    (RetrainKernel, "_sgd_step", _state_unchanged, reduced_cell),
    (RetrainKernel, "_sgd_step", _half_batch, reduced_cell),
    (LabelingKernel, "label_async", _labels_altered, reduced_cell),
    (InferenceKernel, "predict_async", _served_altered, reduced_cell),
    (InferenceKernel, "predict_fleet_async", _fleet_served_altered,
     fleet_cell),
], ids=["state-unchanged", "half-batch", "labels-altered", "served-altered",
        "fleet-served-altered"])
def test_planted_fault_makes_the_run_incorrect(monkeypatch, cls, name,
                                               fault, cell):
    original = getattr(cls, name)
    fault.__wrapped__ = original
    patched = lambda self, *a, **k: fault(self, *a, **k)  # noqa: E731
    patched.__wrapped__ = original
    monkeypatch.setattr(cls, name, patched)
    out = run(cell())
    assert not out["correct"], out["readings"]
