"""Online spatial re-allocation benchmark: DC-ST vs DC-ST-Online.

Runs both policies over every paper scenario (S1-S6 regular, ES1/ES2
extreme, Table II) on identical pretrained weights, through a concurrent
session consuming an explicit :class:`~repro.data.pipeline.FramePipeline`
handle, and writes ``BENCH_reallocation.json`` with, per scenario and
policy:

* ``avg_accuracy`` / ``drift_events`` / ``phases`` — learning outcome;
* ``rows_over_time`` — ``[t, rows_tsa, rows_bsa]`` per phase: the online
  policy's drift-time row boosts and hysteresis returns, flat for DC-ST;
* ``speculation`` — the pipeline's reconcile counters (hit rate must be
  > 0: concurrent dispatch is actually issuing programs against prefetched
  windows);
* ``wall_s`` / ``mean_phase_dt_s`` — host wall time and mean virtual phase
  time.

A third variant (``dacapo-spatiotemporal+nohints``) re-runs DC-ST with
decision-aware speculation disabled — the labeling burst replayed from the
last layout instead of pre-sized with the next decision's budget — and the
sweep reports ``decision_aware_hit_rate_delta``: how much hit rate the
decision-aware predictor recovers (drift phases change the burst size, so
pure replay always misses them).

Scenario segments are compressed (60 s -> 30 s, 15 s in smoke) so drift —
and with it the re-allocation path — fires inside bench timescales. The
serving precision is pinned to MX9 so the offline split is the balanced
(8, 8) where row moves change both sides' throughput materially, and the
forced 4-row mesh makes each boost re-fission the T-SA/B-SA sub-meshes.

Run:  PYTHONPATH=src python benchmarks/bench_reallocation.py [--smoke]
          [--out F] [--scenarios S1,ES1]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import numpy as np

POLICIES = ("dacapo-spatiotemporal", "dacapo-spatiotemporal-online")
# (policy, decision_aware_spec) per measured variant.
VARIANTS = {
    "dacapo-spatiotemporal": ("dacapo-spatiotemporal", True),
    "dacapo-spatiotemporal-online": ("dacapo-spatiotemporal-online", True),
    "dacapo-spatiotemporal+nohints": ("dacapo-spatiotemporal", False),
}


def _stats(res, pipe, wall_s: float) -> dict:
    recs = res.records
    dts = [r.t - r.phase_start for r in recs]
    return {
        "avg_accuracy": round(res.avg_accuracy, 6),
        "drift_events": res.drift_events,
        "phases": len(recs),
        "wall_s": round(wall_s, 3),
        "mean_phase_dt_s": round(float(np.mean(dts)), 6) if dts else 0.0,
        "rows_over_time": [
            [round(r.t, 4), r.decision.rows_tsa, r.decision.rows_bsa]
            for r in recs],
        "rows_moved_phases": sum(
            1 for r in recs if r.decision.rows_tsa != recs[0].decision.rows_tsa),
        "speculation": {
            "hits": pipe.stats.hits,
            "misses": pipe.stats.misses,
            "hit_rate": round(pipe.stats.hit_rate, 4),
            "windows_speculated": pipe.stats.windows_speculated,
            "windows_wasted": pipe.stats.windows_wasted,
        },
    }


def bench_scenario(scen: str, smoke: bool) -> dict:
    from repro.configs.dacapo_pairs import RESNET18, WIDERESNET50
    from repro.core.allocation import CLHyperParams
    from repro.core.mx import PrecisionPolicy
    from repro.core.partition import forced_row_mesh
    from repro.core.session import CLSystemSpec, pretrain_model
    from repro.data.pipeline import FramePipeline
    from repro.data.stream import DriftStream, scenario
    from repro.models.registry import make_vision_model

    seg_s = 15.0 if smoke else 30.0
    n_seg = 4 if smoke else 5
    duration = 45.0 if smoke else 120.0
    segs = [dataclasses.replace(s, duration_s=seg_s)
            for s in scenario(scen, n_seg)]
    stream = DriftStream(segs, seed=17, img=24)
    hp = (CLHyperParams(n_t=32, n_l=16, c_b=128, epochs=1) if smoke
          else CLHyperParams(n_t=48, n_l=24, c_b=192, epochs=1))
    rng = np.random.default_rng(0)
    steps = (8, 6) if smoke else (25, 15)
    tp = pretrain_model(make_vision_model(WIDERESNET50.reduced()), stream,
                        steps[0], 32, rng)
    sp = pretrain_model(make_vision_model(RESNET18.reduced()), stream,
                        steps[1], 32, rng, segments=stream.segments[:1],
                        seed=8)

    # MX9 serving -> balanced (8, 8) offline split; 4-row mesh -> row
    # boosts re-fission the sub-meshes (8->6 B-SA rows: 2->1 mesh rows).
    mx9_serve = PrecisionPolicy(inference="mx9")
    base = CLSystemSpec(student=RESNET18, teacher=WIDERESNET50, hp=hp,
                        apply_mx=False, seed=0, eval_fps=0.5,
                        policy=mx9_serve, dispatch="concurrent",
                        mesh=forced_row_mesh(4))

    out = {}
    for variant, (policy, aware) in VARIANTS.items():
        session = dataclasses.replace(base, allocator=policy,
                                      decision_aware_spec=aware).build()
        session.set_pretrained(tp, sp)
        pipe = FramePipeline(stream, speculative=True)
        t0 = time.perf_counter()
        res = session.run(pipe, duration=duration)
        wall = time.perf_counter() - t0
        pipe.close()  # settles the wasted-window accounting
        out[variant] = _stats(res, pipe, wall)
    return out


def main(argv=None):
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    from repro.data.stream import SCENARIOS

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes + scenario subset for CI")
    ap.add_argument("--out", default="BENCH_reallocation.json")
    ap.add_argument("--scenarios", default=None,
                    help="comma-separated subset (default: all 8; "
                         "smoke default: S1,ES1)")
    args = ap.parse_args(argv)

    if args.scenarios:
        names = args.scenarios.split(",")
    else:
        names = ["S1", "ES1"] if args.smoke else sorted(SCENARIOS)
    unknown = [n for n in names if n not in SCENARIOS]
    if unknown:
        raise SystemExit(f"unknown scenarios: {unknown}")

    result = {
        "bench": "reallocation",
        "mode": "smoke" if args.smoke else "full",
        "backend": jax.default_backend(),
        "policies": list(POLICIES),
        "variants": list(VARIANTS),
        "scenarios": {},
    }
    for name in names:
        t0 = time.perf_counter()
        result["scenarios"][name] = bench_scenario(name, args.smoke)
        print(f"# {name} done in {time.perf_counter() - t0:.1f}s",
              flush=True)

    for variant in VARIANTS:
        hits = sum(s[variant]["speculation"]["hits"]
                   for s in result["scenarios"].values())
        misses = sum(s[variant]["speculation"]["misses"]
                     for s in result["scenarios"].values())
        rate = hits / max(1, hits + misses)
        result.setdefault("speculation_hit_rate", {})[variant] = round(rate,
                                                                       4)
    # Satellite: what the decision-aware predictor recovers over pure
    # layout replay (same policy, hints off).
    result["decision_aware_hit_rate_delta"] = round(
        result["speculation_hit_rate"]["dacapo-spatiotemporal"]
        - result["speculation_hit_rate"]["dacapo-spatiotemporal+nohints"],
        4)
    # Phases the online policy spent away from the offline split
    # (drift-dependent, hence sweep-level).
    result["online_rows_moved_phases"] = sum(
        s[POLICIES[1]]["rows_moved_phases"]
        for s in result["scenarios"].values())

    # Write BEFORE the acceptance asserts: a failing sweep must still leave
    # the per-scenario counters needed to diagnose it (CI uploads the file).
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps({k: v for k, v in result.items() if k != "scenarios"},
                     indent=2))
    print(f"wrote {args.out} ({len(result['scenarios'])} scenarios)")

    # Acceptance: concurrent sessions actually speculate, for every
    # variant, across the sweep — and the decision-aware predictor never
    # costs hits (it only rewrites bursts to the budget actually coming).
    for variant, rate in result["speculation_hit_rate"].items():
        assert rate > 0, f"{variant}: speculation never hit"
    assert result["decision_aware_hit_rate_delta"] >= 0, \
        "decision-aware speculation lost hits vs pure replay"
    return result


def run():
    """Registry entry (benchmarks/run.py): smoke sweep as CSV rows. Writes
    to a distinct file so a full-sweep BENCH_reallocation.json survives."""
    result = main(["--smoke", "--out", "BENCH_reallocation_smoke.json"])
    rows = []
    for scen, variants in result["scenarios"].items():
        for variant, stats in variants.items():
            rows.append((f"reallocation/{scen}/{variant}",
                         stats["wall_s"] * 1e6,
                         f"acc={stats['avg_accuracy']}"
                         f";hit_rate={stats['speculation']['hit_rate']}"))
    rows.append(("reallocation/decision_aware_delta", 0.0,
                 f"hit_rate_delta="
                 f"{result['decision_aware_hit_rate_delta']}"))
    return rows


if __name__ == "__main__":
    main()
