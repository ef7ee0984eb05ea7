"""Multi-camera fleet benchmark: cross-stream T-SA allocation policies.

Runs an N-stream heterogeneous fleet — one camera drifting (label
distribution flips each compressed segment) next to stable cameras parked
in the student's pretraining context — through
:class:`~repro.core.fleet.FleetSession` under three cross-stream split
modes on identical pretrained weights and an identical virtual-clock
budget:

* ``drift-weighted`` — the :class:`~repro.core.allocation.FleetAllocator`
  routes the shared T-SA's labeling/retraining budget to the cameras whose
  accuracy-loss signal (and drift flags) say they need it;
* ``uniform`` — every camera gets ``1/N`` of the budget every phase;
* ``isolated`` — the no-fleet baseline: every camera keeps a full
  per-session budget, so the shared T-SA serializes ~N sessions' worth of
  work per phase (N isolated sessions time-sharing the accelerator) and
  each stream's update cadence is ~N× slower.

A second dimension sweeps the fleet's *spatial* plane: a multi-lane-drift
fleet (two cameras flipping their label distributions on aligned segment
boundaries next to one stable camera) runs under each
:class:`~repro.core.decision.FleetRowPolicy` — ``resolve-max`` (the static
baseline), ``drift-surge`` (grow the fleet T-SA under multi-lane drift,
with hysteresis) and ``weighted-vote`` (rows follow the drift-weighted
temporal shares) — at equal virtual-clock budget and identical weights.

Writes ``BENCH_fleet.json`` with, per mode: mean fleet accuracy,
per-stream accuracies/drifts, fleet phases executed, the per-phase shared
T-SA time (the equal-budget check: uniform and drift-weighted spend ~one
session's T-SA budget per phase, isolated ~N×), speculation counters, and
host wall time; and per row policy: mean fleet accuracy, fleet phases,
rows-over-time stats (mean/max T-SA rows, spatial re-allocations); plus
the batched B-SA serve microbench (PR 7: every lane's score windows in
ONE vmapped program per phase — headline
``fleet_batched_serve_speedup``, the per-phase program reduction).

Acceptance (asserted after the JSON is written): the drift-weighted fleet
beats BOTH uniform and isolated on mean fleet accuracy, and the best
adaptive row policy (drift-surge or weighted-vote) beats resolve-max on
mean fleet accuracy in the multi-lane-drift scenario.

Run:  PYTHONPATH=src python benchmarks/bench_fleet.py [--smoke] [--out F]
          [--streams N] [--row-policy P]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np

MODES = ("drift-weighted", "uniform", "isolated")
ROW_POLICIES = ("resolve-max", "drift-surge", "weighted-vote")


def build_streams(n_streams: int, smoke: bool):
    """One hard-drifting camera + (n-1) static-context cameras.

    All cameras share one stream seed — the same visual world (class
    patterns, textures), the paper's multi-camera deployment — and differ
    only in their segment timelines: camera 0 flips its label distribution
    every (compressed) segment (S1), while the static cameras sit in the
    student's pretraining context. Budget spent on the static cameras is
    mostly wasted; camera 0 is where labeling/retraining pays — the signal
    the drift-weighted allocator should find."""
    from repro.data.stream import DriftStream, Segment, scenario

    seg_s = 30.0 if smoke else 45.0
    n_seg = 3 if smoke else 4
    drifting = [dataclasses.replace(s, duration_s=seg_s)
                for s in scenario("S1", n_seg)]
    streams = [DriftStream(drifting, seed=17, img=24)]
    for _ in range(n_streams - 1):
        stable = [Segment(duration_s=seg_s)] * n_seg
        streams.append(DriftStream(stable, seed=17, img=24))
    return streams


def build_multi_drift_streams(n_streams: int, smoke: bool):
    """The multi-lane-drift scenario for the row-policy sweep.

    Two cameras drift on *aligned* segment boundaries — camera 0 through
    the compressed S1 timeline, camera 1 through S3 with identical segment
    lengths, so their label distributions flip at the same instants but to
    different contexts — next to (n-2) stable cameras. Simultaneous
    multi-lane drift is exactly the regime the adaptive row policies
    (drift-surge quorum, weighted-vote boost) react to and the static
    resolve-max baseline cannot."""
    import dataclasses as _dc

    from repro.data.stream import DriftStream, Segment, scenario

    seg_s = 30.0 if smoke else 45.0
    n_seg = 3 if smoke else 4

    def compressed(name):
        return [_dc.replace(s, duration_s=seg_s)
                for s in scenario(name, n_seg)]

    streams = [DriftStream(compressed("S1"), seed=17, img=24),
               DriftStream(compressed("S3"), seed=17, img=24)]
    for _ in range(max(0, n_streams - 2)):
        streams.append(DriftStream([Segment(duration_s=seg_s)] * n_seg,
                                   seed=17, img=24))
    return streams[:n_streams]


def _hp(smoke: bool):
    from repro.core.allocation import CLHyperParams

    # Retraining-heavy economics: labels (the teacher is the expensive
    # kernel) are detection infrastructure every camera keeps in full
    # (label_floor=1.0); the contended budget the modes split is
    # retraining + the N_ldd drift bursts. v_thr widened for n_l=16 label
    # counts (the default -0.10 was tuned for 32..48-label estimates).
    return (CLHyperParams(n_t=64, n_l=16, c_b=192, epochs=1, v_thr=-0.25)
            if smoke
            else CLHyperParams(n_t=96, n_l=24, c_b=256, epochs=1,
                               v_thr=-0.25))


def _pretrain(streams, smoke: bool):
    """Shared pretraining: teacher across the whole attribute space of the
    (first) drifting camera; student on the stable context only
    (segments[:1]) and to convergence, so stable cameras start at their
    ceiling and budget routed to them is genuinely wasted."""
    import numpy as np

    from repro.configs.dacapo_pairs import RESNET18, WIDERESNET50
    from repro.core.session import pretrain_model
    from repro.models.registry import make_vision_model

    rng = np.random.default_rng(0)
    steps = (30, 40) if smoke else (60, 60)
    tp = pretrain_model(make_vision_model(WIDERESNET50.reduced()),
                        streams[0], steps[0], 32, rng)
    sp = pretrain_model(make_vision_model(RESNET18.reduced()), streams[0],
                        steps[1], 32, rng,
                        segments=streams[0].segments[:1], seed=8)
    return tp, sp


def bench_fleet(n_streams: int, smoke: bool) -> dict:
    from repro.configs.dacapo_pairs import RESNET18, WIDERESNET50
    from repro.core.fleet import FleetSpec

    from repro.core.mx import PrecisionPolicy

    duration = 90.0 if smoke else 180.0
    hp = _hp(smoke)
    streams = build_streams(n_streams, smoke)
    # Deeper pretraining than the other smoke benches: the drift detector
    # compares teacher labels against student predictions, so both must be
    # real models for the drift signal — the thing this bench allocates on
    # — to carry information instead of noise.
    tp, sp = _pretrain(streams, smoke)

    # MX9 serving -> the balanced (8, 8) offline split (the mx6 default
    # would leave the B-SA 2 rows and crush every mode's keep_frac).
    # label_floor=1.0: every camera keeps its full n_l labels per phase so
    # every drift detector stays reliable — only retraining and the drift
    # bursts (extra_label_samples) are re-proportioned across the fleet.
    base = FleetSpec(student=RESNET18, teacher=WIDERESNET50, hp=hp,
                     policy=PrecisionPolicy(inference="mx9"),
                     apply_mx=False, seed=0, eval_fps=1.0,
                     dispatch="concurrent",
                     fleet_kwargs={"label_floor": 1.0, "drift_bias": 3.0,
                                   "gap_eps": 0.01})
    out = {}
    for mode in MODES:
        fleet = dataclasses.replace(base, fleet_mode=mode).build()
        fleet.set_pretrained(tp, sp)
        t0 = time.perf_counter()
        fres = fleet.run(streams, duration=duration)
        wall = time.perf_counter() - t0
        spec_hits = sum(r.spec_hits for lane in fres.streams
                        for r in lane.records)
        spec_misses = sum(r.spec_misses for lane in fres.streams
                          for r in lane.records)
        out[mode] = {
            "fleet_avg_accuracy": round(fres.fleet_avg_accuracy, 6),
            "per_stream_accuracy": [round(r.avg_accuracy, 6)
                                    for r in fres.streams],
            "per_stream_drifts": [r.drift_events for r in fres.streams],
            "fleet_phases": len(fres.fleet_phase_log),
            # Equal-budget check: per-phase shared-T-SA seconds.
            "mean_phase_t_tsa_s": round(float(np.mean(
                [e["t_tsa"] for e in fres.fleet_phase_log])), 6)
            if fres.fleet_phase_log else 0.0,
            "spec_hits": spec_hits,
            "spec_misses": spec_misses,
            "wall_s": round(wall, 3),
        }
    return out


def bench_row_policies(n_streams: int, smoke: bool,
                       only: str = None) -> dict:
    """The spatial-plane dimension: the multi-lane-drift fleet under each
    FleetRowPolicy at equal virtual-clock budget, identical weights, and
    the drift-weighted temporal split throughout — the only variable is
    who resolves the fleet's per-phase row split."""
    from repro.configs.dacapo_pairs import RESNET18, WIDERESNET50
    from repro.core.fleet import FleetSpec
    from repro.core.mx import PrecisionPolicy

    duration = 90.0 if smoke else 180.0
    hp = _hp(smoke)
    streams = build_multi_drift_streams(n_streams, smoke)
    tp, sp = _pretrain(streams, smoke)

    base = FleetSpec(student=RESNET18, teacher=WIDERESNET50, hp=hp,
                     policy=PrecisionPolicy(inference="mx9"),
                     apply_mx=False, seed=0, eval_fps=1.0,
                     dispatch="concurrent", fleet_mode="drift-weighted",
                     fleet_kwargs={"label_floor": 1.0, "drift_bias": 3.0,
                                   "gap_eps": 0.01})
    out = {}
    for rp in (ROW_POLICIES if only is None else (only,)):
        fleet = dataclasses.replace(base, row_policy=rp).build()
        fleet.set_pretrained(tp, sp)
        t0 = time.perf_counter()
        fres = fleet.run(streams, duration=duration)
        wall = time.perf_counter() - t0
        rows = [(e["rows_tsa"], e["rows_bsa"])
                for e in fres.fleet_phase_log]
        out[rp] = {
            "fleet_avg_accuracy": round(fres.fleet_avg_accuracy, 6),
            "per_stream_accuracy": [round(r.avg_accuracy, 6)
                                    for r in fres.streams],
            "per_stream_drifts": [r.drift_events for r in fres.streams],
            "fleet_phases": len(fres.fleet_phase_log),
            "mean_rows_tsa": round(float(np.mean([r for r, _ in rows])), 3)
            if rows else 0.0,
            "max_rows_tsa": max((r for r, _ in rows), default=0),
            "spatial_moves": sum(a != b for a, b in zip(rows, rows[1:])),
            "wall_s": round(wall, 3),
        }
    return out


def bench_batched_serve(smoke: bool) -> dict:
    """Batched fleet serving (PR 7): L lanes' score windows through ONE
    vmapped B-SA program (``InferenceKernel.predict_fleet_async``) vs one
    fused predict per lane. The headline ``fleet_batched_serve_speedup``
    is the per-phase B-SA *program* reduction (L programs → 1) — the
    device-dispatch metric the fused serve targets; host wall times for
    both paths are reported alongside (on a CPU host the vmapped stacked
    apply is not wall-faster — there is no second sub-accelerator to
    overlap with)."""
    from repro.configs.dacapo_pairs import RESNET18
    from repro.core.estimator import DaCapoEstimator
    from repro.core.kernel import InferenceKernel
    from repro.models.registry import make_vision_model

    n_lanes = 3 if smoke else 4
    frames = 16 if smoke else 24
    reps = 5 if smoke else 15
    model = make_vision_model(RESNET18.reduced())
    trees = [model.init(jax.random.PRNGKey(i)) for i in range(n_lanes)]
    rngs = [jax.random.PRNGKey(100 + i) for i in range(n_lanes)]
    wins = [np.asarray(jax.random.normal(r, (frames, 24, 24, 3)),
                       np.float32) for r in rngs]
    kernel = InferenceKernel(model, RESNET18, DaCapoEstimator(),
                             apply_mx=False)

    def per_lane():
        outs = [kernel.predict_async(t, w) for t, w in zip(trees, wins)]
        jax.block_until_ready(outs)
        return outs

    def batched():
        outs = kernel.predict_fleet_async(trees, wins)
        jax.block_until_ready(outs)
        return outs

    preds_pl = [np.asarray(p) for p in per_lane()]  # warm both jit paths
    preds_b = [np.asarray(p) for p in batched()]
    acc_gap = max(float((a != b).mean())
                  for a, b in zip(preds_pl, preds_b))

    kernel.n_apply_calls = 0
    t0 = time.perf_counter()
    for _ in range(reps):
        per_lane()
    wall_pl = (time.perf_counter() - t0) / reps
    calls_pl = kernel.n_apply_calls / reps

    kernel.n_apply_calls = 0
    t0 = time.perf_counter()
    for _ in range(reps):
        batched()
    wall_b = (time.perf_counter() - t0) / reps
    calls_b = kernel.n_apply_calls / reps

    assert calls_b < calls_pl, "batched serve must issue fewer programs"
    return {
        "n_lanes": n_lanes,
        "frames_per_lane": frames,
        "per_lane": {"programs": calls_pl, "wall_s": round(wall_pl, 4)},
        "batched": {"programs": calls_b, "wall_s": round(wall_b, 4)},
        "prediction_disagreement": acc_gap,  # vmapped apply ulp drift
        "fleet_batched_serve_speedup": round(calls_pl / calls_b, 2),
    }


def main(argv=None):
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI")
    ap.add_argument("--streams", type=int, default=3)
    ap.add_argument("--out", default="BENCH_fleet.json")
    ap.add_argument("--row-policy", default=None, choices=ROW_POLICIES,
                    help="run the row-policy sweep for ONE policy only "
                         "(CI matrix entry; skips the cross-policy "
                         "acceptance assert)")
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    # A single-policy run (CI matrix) skips the temporal-mode sweep: the
    # dimension under test is the spatial plane.
    modes = (bench_fleet(args.streams, args.smoke)
             if args.row_policy is None else {})
    row_policies = bench_row_policies(args.streams, args.smoke,
                                      only=args.row_policy)
    batched_serve = bench_batched_serve(args.smoke)
    result = {
        "bench": "fleet",
        "mode": "smoke" if args.smoke else "full",
        "backend": jax.default_backend(),
        "n_streams": args.streams,
        "modes": modes,
        "row_policies": row_policies,
        "batched_serve": batched_serve,
        "fleet_batched_serve_speedup":
            batched_serve["fleet_batched_serve_speedup"],
    }
    if modes:
        result["fleet_accuracy_gain_vs_uniform"] = round(
            modes["drift-weighted"]["fleet_avg_accuracy"]
            - modes["uniform"]["fleet_avg_accuracy"], 6)
        result["fleet_accuracy_gain_vs_isolated"] = round(
            modes["drift-weighted"]["fleet_avg_accuracy"]
            - modes["isolated"]["fleet_avg_accuracy"], 6)
    if len(row_policies) == len(ROW_POLICIES):
        result["row_policy_gain"] = round(
            max(row_policies["drift-surge"]["fleet_avg_accuracy"],
                row_policies["weighted-vote"]["fleet_avg_accuracy"])
            - row_policies["resolve-max"]["fleet_avg_accuracy"], 6)

    # Write BEFORE the acceptance asserts so a failing comparison still
    # leaves the per-mode numbers to diagnose (CI uploads the file).
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result, indent=2))
    print(f"wrote {args.out} in {time.perf_counter() - t0:.1f}s")

    if modes:
        dw = modes["drift-weighted"]["fleet_avg_accuracy"]
        assert dw > modes["uniform"]["fleet_avg_accuracy"], \
            "drift-weighted must beat the uniform split on fleet accuracy"
        assert dw > modes["isolated"]["fleet_avg_accuracy"], \
            "drift-weighted must beat isolated sessions on fleet accuracy"
    if "row_policy_gain" in result:
        assert result["row_policy_gain"] > 0, \
            ("an adaptive row policy (drift-surge or weighted-vote) must "
             "beat resolve-max on mean fleet accuracy under multi-lane "
             "drift")
    return result


def run():
    """Registry entry (benchmarks/run.py): smoke fleet sweep as CSV rows.
    Writes to a distinct file so a full-sweep BENCH_fleet.json survives."""
    result = main(["--smoke", "--out", "BENCH_fleet_smoke.json"])
    return ([(f"fleet/{mode}",
              result["modes"][mode]["wall_s"] * 1e6,
              f"acc={result['modes'][mode]['fleet_avg_accuracy']}")
             for mode in MODES]
            + [(f"fleet/rows/{rp}",
                result["row_policies"][rp]["wall_s"] * 1e6,
                f"acc={result['row_policies'][rp]['fleet_avg_accuracy']}")
               for rp in ROW_POLICIES])


if __name__ == "__main__":
    main()
