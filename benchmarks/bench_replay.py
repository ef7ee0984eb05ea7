"""Trace-replay benchmark: prediction accuracy and the replay-scored policy.

Two sections, one JSON artifact (``BENCH_replay.json``):

* **prediction** — runs a traced DC-ST session under each dispatch
  semantics and replays every recorded phase through
  :class:`~repro.core.replay.TraceReplayer`:

  - ``exact_phases`` / ``replay_sequential_exact`` — phases whose end
    clock the replayer reconstructs *bitwise* (must be all of them, in
    both modes: replay walks the plan's own float-add sequence);
  - ``replay_phase_time_mape`` — mean absolute percentage error of the
    genuinely predictive path: ``predict(from_units=True)`` re-prices
    every program from trace-wide per-label cost histograms (what a
    candidate scorer uses for budgets the trace never ran) against the
    recorded concurrent phase times;
  - ``calibration`` — the per-kernel wall/virtual scale factors
    :meth:`~repro.core.replay.TraceReplayer.calibrate` fits for
    :class:`~repro.core.estimator.CalibratedEstimator`.

* **policy** — DC-ST vs the ``"dacapo-replay"`` allocator on identical
  pretrained weights over a concurrent session with real serving load
  (eval_fps high enough that the B-SA chain bounds the phase): replay
  scores K retrain-budget boosts per phase against the recorded last
  phase and only accepts boosts that fit the B-SA slack. The headline
  ``replay_policy_gain`` is the accuracy delta; the replay arm charges
  its measured scoring wall to ``profile_cost_s`` on the T-SA ledger
  (``charged_profile_s`` reports both arms' totals).

Run:  PYTHONPATH=src python benchmarks/bench_replay.py [--smoke] [--out F]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np


def _pretrained(smoke: bool):
    from repro.configs.dacapo_pairs import RESNET18, WIDERESNET50
    from repro.core.allocation import CLHyperParams
    from repro.core.session import pretrain_model
    from repro.data.stream import DriftStream, scenario
    from repro.models.registry import make_vision_model

    del smoke  # the policy arms need real pretraining to show the gain
    stream = DriftStream(scenario("S1", 3), seed=5, img=24)
    hp = CLHyperParams(n_t=48, n_l=24, c_b=192, epochs=1)
    rng = np.random.default_rng(0)
    tp = pretrain_model(make_vision_model(WIDERESNET50.reduced()), stream,
                        25, 32, rng)
    sp = pretrain_model(make_vision_model(RESNET18.reduced()), stream, 15,
                        32, rng, segments=stream.segments[:1], seed=8)
    return stream, hp, tp, sp


def _session(hp, allocator, dispatch, trace, eval_fps):
    from repro.configs.dacapo_pairs import RESNET18, WIDERESNET50
    from repro.core.session import CLSystemSpec

    return CLSystemSpec(student=RESNET18, teacher=WIDERESNET50,
                        allocator=allocator, hp=hp, apply_mx=False, seed=0,
                        eval_fps=eval_fps, dispatch=dispatch,
                        trace=trace).build()


def bench_prediction(setup, smoke: bool) -> dict:
    from repro.core.replay import TraceReplayer
    from repro.core.trace import SessionTrace

    stream, hp, tp, sp = setup
    duration = 45.0 if smoke else 90.0
    out = {}
    for mode in ("sequential", "concurrent"):
        session = _session(hp, "dacapo-spatiotemporal", mode, True, 0.5)
        session.set_pretrained(tp, sp)
        t0 = time.perf_counter()
        session.run(stream, duration=duration)
        wall = time.perf_counter() - t0
        trace = session.dispatcher.recorder.trace
        # Round-trip through JSON first: the offline-analysis path must be
        # as exact as the in-memory one.
        rep = TraceReplayer(SessionTrace.from_json(trace.to_json()), hp=hp)
        exact = sum(1 for i, ph in enumerate(trace.phases)
                    if rep.phase_time(i) == ph.end)
        errs = [abs(rep.predict(i, from_units=True) - ph.end) / ph.end
                for i, ph in enumerate(trace.phases) if ph.end > 0]
        cal = rep.calibrate()
        out[mode] = {
            "phases": len(trace.phases),
            "events": sum(len(ph.events) for ph in trace.phases),
            "exact_phases": exact,
            "bitwise_exact": exact == len(trace.phases),
            "from_units_mape_pct": round(
                100.0 * float(np.mean(errs)), 6) if errs else 0.0,
            "wall_s": round(wall, 3),
            "calibration": {
                "global_scale": round(cal.global_scale, 6),
                "scales": {k: round(v, 6) for k, v in cal.scales.items()},
            },
        }
    return out


def bench_policy(setup, smoke: bool) -> dict:
    stream, hp, tp, sp = setup
    duration = 60.0 if smoke else 90.0
    out = {}
    for allocator in ("dacapo-spatiotemporal", "dacapo-replay"):
        session = _session(hp, allocator, "concurrent", None, 2.0)
        session.set_pretrained(tp, sp)
        t0 = time.perf_counter()
        res = session.run(stream, duration=duration)
        wall = time.perf_counter() - t0
        charged = sum(r.decision.profile_cost_s for r in res.records)
        boosted = sum(
            1 for r in res.records
            if r.decision.retrain_samples > res.records[0]
            .decision.retrain_samples)
        out[allocator] = {
            "avg_accuracy": round(res.avg_accuracy, 6),
            "phases": len(res.records),
            "drift_events": res.drift_events,
            "retrain_time": round(res.retrain_time, 6),
            "boosted_phases": boosted,
            "charged_profile_s": round(charged, 6),
            "wall_s": round(wall, 3),
        }
    return out


def main(argv=None):
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="shorter sessions for CI")
    ap.add_argument("--out", default="BENCH_replay.json")
    args = ap.parse_args(argv)

    setup = _pretrained(args.smoke)
    result = {
        "bench": "replay",
        "mode": "smoke" if args.smoke else "full",
        "backend": jax.default_backend(),
    }
    t0 = time.perf_counter()
    result["prediction"] = bench_prediction(setup, args.smoke)
    print(f"# prediction done in {time.perf_counter() - t0:.1f}s",
          flush=True)
    t0 = time.perf_counter()
    result["policy"] = bench_policy(setup, args.smoke)
    print(f"# policy done in {time.perf_counter() - t0:.1f}s", flush=True)

    # Headlines (check_artifacts.py requires both).
    result["replay_phase_time_mape"] = result["prediction"]["concurrent"][
        "from_units_mape_pct"]
    result["replay_policy_gain"] = round(
        result["policy"]["dacapo-replay"]["avg_accuracy"]
        - result["policy"]["dacapo-spatiotemporal"]["avg_accuracy"], 6)

    # Write BEFORE the acceptance asserts so a failing run still uploads
    # the numbers needed to diagnose it.
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result, indent=2))
    print(f"wrote {args.out}")

    # Acceptance: replay is exact on the virtual clock in BOTH dispatch
    # semantics, the histogram-priced concurrent predictions land within
    # 5% MAPE, and the replay-scored policy never loses accuracy to DC-ST
    # while paying for its own scoring on the ledger.
    for mode in ("sequential", "concurrent"):
        assert result["prediction"][mode]["bitwise_exact"], \
            f"{mode}: replay not bitwise exact"
    assert result["replay_phase_time_mape"] < 5.0, \
        f"concurrent MAPE {result['replay_phase_time_mape']}% >= 5%"
    assert result["replay_policy_gain"] >= 0.0, \
        f"dacapo-replay lost accuracy: {result['replay_policy_gain']}"
    assert result["policy"]["dacapo-replay"]["boosted_phases"] > 0, \
        "replay policy never accepted a boost"
    assert result["policy"]["dacapo-replay"]["charged_profile_s"] > 0, \
        "replay scoring wall never charged to profile_cost_s"
    return result


def run():
    """Registry entry (benchmarks/run.py): smoke pass as CSV rows. Writes
    to a distinct file so a full BENCH_replay.json survives."""
    result = main(["--smoke", "--out", "BENCH_replay_smoke.json"])
    rows = []
    for mode, stats in result["prediction"].items():
        rows.append((f"replay/predict/{mode}", stats["wall_s"] * 1e6,
                     f"exact={stats['exact_phases']}/{stats['phases']}"
                     f";mape={stats['from_units_mape_pct']}"))
    for allocator, stats in result["policy"].items():
        rows.append((f"replay/policy/{allocator}", stats["wall_s"] * 1e6,
                     f"acc={stats['avg_accuracy']}"
                     f";boosted={stats['boosted_phases']}"))
    rows.append(("replay/policy_gain", 0.0,
                 f"gain={result['replay_policy_gain']}"))
    return rows


if __name__ == "__main__":
    main()
