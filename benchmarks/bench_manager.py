"""Fleet-manager benchmark: fault recovery, migration, overlapped
stepping, and estimator-driven placement.

Runs the sharded fleet tier (:class:`~repro.core.manager.FleetManager`,
N shards = N independent FleetSessions on their own sub-accelerators)
through five experiments on identical pretrained weights and an identical
virtual-clock budget:

* **recovery** — the same fleet twice: a no-fault baseline vs a run where
  one shard's accelerator is lost mid-run
  (:class:`~repro.runtime.fault.FailureInjector`, probed per round with
  ``key=shard_index``). The dead shard's lanes restore from their last
  per-lane durable checkpoint and re-home onto the survivors; the bench
  reports the accuracy cost of the fault, the explicitly-charged recovery
  seconds, and the manager/shard **ledger conservation gap** (must be ~0:
  every phase's T-SA seconds are charged once per tier);
* **migration** — migration-off (``static`` placement, lanes pinned where
  admitted) vs migration-on (``headroom`` placement: a drifted lane on an
  oversubscribed shard re-homes to the shard with T-SA headroom) at equal
  budget, on the bench_fleet drifting-camera fleet packed asymmetrically
  so the drifting camera starts on the loaded shard;
* **parallel** — serial (``parallel_shards=0``) vs overlapped
  (``parallel_shards=n``) round stepping at 2 and 4 shards.
  **Methodology, honestly:** this container is a 1-core CPU host, so
  jitted jax compute cannot overlap — what DOES overlap in the modeled
  system is each shard *waiting on its own sub-accelerator*. The bench
  emulates that blocking with the manager's ``shard_pace`` knob
  (host-seconds slept per modeled phase-second, inside ``step()``,
  touching no state), pace-calibrated from a pace-free probe run so the
  emulated device time is a fixed fraction of real host compute. Serial
  stepping pays every shard's wait back-to-back; the worker pool hides
  all but the slowest — the exact win overlapping gives on real
  hardware. Bit-identity of the two arms (accuracy, ledgers, decisions,
  events) is ASSERTED before the JSON is written; the headline
  ``manager_parallel_speedup`` is the 4-shard wall ratio;
* **placement** — ``headroom`` (lane-count balance) vs ``estimator``
  (seconds-based :class:`~repro.core.estimator.PlacementCostModel`) on a
  skewed fleet: shard 0 = both drifting cameras + one stable, shard 1 =
  two stables. The lane-count gap (1) sits below headroom's ``min_gap``
  hysteresis so headroom never migrates; the estimator reasons in
  seconds — it finds the move that lowers the fleet's load max and fires
  when the horizon-amortized T-SA gain beats ``migration_cost_s`` (which
  is charged to the manager ledger). A late admission demonstrates
  admission control: the estimator rejects it when every warm shard is
  past ``oversub_limit`` (surfaced as a ``reject`` action/event),
  headroom admits unconditionally;
* **scenario_matrix** — ``drift-pack`` vs ``headroom`` crossed with
  *aligned* vs *scattered* two-camera drift: the same S1/S3 drifters
  flipping simultaneously (packing their retraining bursts onto one
  T-SA pays) or staggered by half a segment (the payoff dilutes). The
  per-layout ``drift_pack_gain`` headline is the accuracy delta.

Writes ``BENCH_manager.json`` with, per experiment arm: mean fleet
accuracy, per-lane accuracies, rounds, ledger (T-SA / recovery /
migration seconds), events (fail/recover/migrate/reject counts) and host
wall time, plus the top-level ``manager_parallel_speedup`` headline.

Acceptance (asserted after the JSON is written): both recovery arms keep
every camera; the ledger conservation gap is ~0 in every arm; the faulted
run recovers (>=1 recover event) and lands within an accuracy tolerance
of the no-fault baseline; serial and overlapped arms are bit-identical;
the estimator arm migrates where headroom does not.

Run:  PYTHONPATH=src python benchmarks/bench_manager.py [--smoke]
          [--out F] [--fail-shard K] [--shards N] [--parallel N]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax

# Importable both via benchmarks/run.py (repo root on sys.path) and as a
# standalone CLI (only benchmarks/ on sys.path).
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmarks.bench_fleet import _hp, _pretrain, build_streams  # noqa: E402

# The faulted arm must land within this of the no-fault baseline. The
# dominant cost is not checkpoint staleness but budget dilution: after
# the round-3 loss every camera shares the surviving shard's single
# T-SA for the rest of the run, so per-lane retrain budget roughly
# halves fleet-wide (~0.2 accuracy on the smoke fleet).
ACCURACY_TOLERANCE = 0.3

# parallel section: emulated per-shard device wait as a fraction of the
# probe run's host compute (see bench_parallel's methodology note).
PACE_FRACTION = 0.75

# placement section: estimator admission ceiling — T-SA seconds per phase
# over the phase wall a shard may reach with one more lane aboard.
# Calibrated between the skewed fleet's stable-shard (~low) and
# drift-shard (~high) utilizations so the late admission is rejected once
# both shards are busy retraining.
OVERSUB_LIMIT = 0.5


def _manager(hp, smoke, **kw):
    from repro.configs.dacapo_pairs import RESNET18, WIDERESNET50
    from repro.core.fleet import FleetSpec
    from repro.core.manager import FleetManager
    from repro.core.mx import PrecisionPolicy

    spec = FleetSpec(student=RESNET18, teacher=WIDERESNET50, hp=hp,
                     policy=PrecisionPolicy(inference="mx9"),
                     apply_mx=False, seed=0, eval_fps=1.0,
                     dispatch="concurrent", fleet_mode="drift-weighted",
                     fleet_kwargs={"label_floor": 1.0, "drift_bias": 3.0,
                                   "gap_eps": 0.01})
    return FleetManager(spec, **kw)


def _summary(res, wall):
    counts = {}
    for e in res.events:
        counts[e.kind] = counts.get(e.kind, 0) + 1
    return {
        "fleet_avg_accuracy": round(res.fleet_avg_accuracy, 6),
        "per_lane_accuracy": {str(k): round(v.avg_accuracy, 6)
                              for k, v in sorted(res.lane_results.items(),
                                                 key=lambda kv: str(kv[0]))},
        "lanes": len(res.lane_results),
        "rounds": res.rounds,
        "parallel_rounds": res.parallel_rounds,
        "dead_shards": sum(1 for r in res.shard_results if r is None),
        "t_tsa_s": round(res.ledger["t_tsa"], 6),
        "recovery_cost_s": round(res.ledger["recovery_cost"], 6),
        "migration_cost_s": round(res.ledger.get("migration_cost", 0.0), 6),
        "conservation_gap": res.conservation_gap(),
        "events": counts,
        "wall_s": round(wall, 3),
    }


def _run(mgr, streams, duration, admissions=()):
    t0 = time.perf_counter()
    res = mgr.run(streams, duration=duration, admissions=admissions)
    return res, _summary(res, time.perf_counter() - t0)


def _assert_bit_identical(serial, overlapped, label):
    """Serial vs overlapped stepping must be bit-identical — not close,
    EQUAL: the pool only changes host scheduling, never modeled state."""
    assert serial.fleet_avg_accuracy == overlapped.fleet_avg_accuracy, label
    assert serial.ledger == overlapped.ledger, label
    assert serial.shard_ledgers == overlapped.shard_ledgers, label
    assert serial.rounds == overlapped.rounds, label
    assert serial.decisions == overlapped.decisions, label
    assert serial.events == overlapped.events, label
    sa = {str(k): v.avg_accuracy for k, v in serial.lane_results.items()}
    oa = {str(k): v.avg_accuracy for k, v in overlapped.lane_results.items()}
    assert sa == oa, label


def bench_recovery(n_shards, fail_shard, smoke, ckpt_root,
                   parallel=0) -> dict:
    """No-fault baseline vs mid-run shard loss with checkpoint recovery."""
    from repro.runtime.fault import FailureInjector

    duration = 90.0 if smoke else 180.0
    hp = _hp(smoke)
    streams = build_streams(3, smoke)
    tp, sp = _pretrain(streams, smoke)

    base = _manager(hp, smoke, n_shards=n_shards, migration=False,
                    parallel_shards=parallel,
                    checkpoint_dir=os.path.join(ckpt_root, "no_fault"),
                    checkpoint_every=2)
    base.set_pretrained(tp, sp)
    _, no_fault = _run(base, build_streams(3, smoke), duration)

    injector = FailureInjector(fail_at_steps=[(3, fail_shard)])
    faulted = _manager(hp, smoke, n_shards=n_shards, migration=False,
                       parallel_shards=parallel,
                       checkpoint_dir=os.path.join(ckpt_root, "fault"),
                       checkpoint_every=2, failure_injector=injector,
                       recovery_cost_s=2.0)
    faulted.set_pretrained(tp, sp)
    _, fault = _run(faulted, build_streams(3, smoke), duration)

    return {
        "no_fault": no_fault,
        "fault": fault,
        "fail_shard": fail_shard,
        "accuracy_delta": round(no_fault["fleet_avg_accuracy"]
                                - fault["fleet_avg_accuracy"], 6),
        "recovery_overhead_s": fault["recovery_cost_s"],
    }


def bench_migration(n_shards, smoke, parallel=0) -> dict:
    """static (no migration) vs headroom (drifted lanes re-home) at equal
    budget. The drifting camera is admitted first so static round-robin
    and headroom both start it on shard 0 next to a stable camera — the
    loaded shard headroom migrates it away from."""
    duration = 90.0 if smoke else 180.0
    hp = _hp(smoke)
    streams = build_streams(3, smoke)
    tp, sp = _pretrain(streams, smoke)

    out = {}
    for arm, kw in (
            ("off", {"placement": "static", "migration": False}),
            ("on", {"placement": "headroom",
                    "placement_kwargs": {"min_gap": 1},
                    "migration": True, "migration_cooldown": 2})):
        mgr = _manager(hp, smoke, n_shards=n_shards,
                       parallel_shards=parallel, **kw)
        mgr.set_pretrained(tp, sp)
        _, out[arm] = _run(mgr, build_streams(3, smoke), duration)
    out["accuracy_delta"] = round(out["on"]["fleet_avg_accuracy"]
                                  - out["off"]["fleet_avg_accuracy"], 6)
    out["migrations"] = out["on"]["events"].get("migrate", 0)
    return out


def bench_parallel(smoke) -> dict:
    """Serial vs overlapped round stepping at 2 and 4 shards.

    A pace-free probe measures pure host compute for one serial sweep;
    ``shard_pace`` is then set so each shard's emulated sub-accelerator
    wait over the run is ``PACE_FRACTION`` of that compute. Serial
    stepping pays the waits back-to-back (wall ~ C + N*P); the worker
    pool overlaps them (wall ~ C + P). Bit-identity of every arm pair is
    asserted before anything is reported."""
    duration = 90.0 if smoke else 180.0
    hp = _hp(smoke)
    streams = build_streams(4, smoke)
    tp, sp = _pretrain(streams, smoke)

    def make(n_shards, workers, pace):
        mgr = _manager(hp, smoke, n_shards=n_shards, placement="static",
                       migration=False, parallel_shards=workers,
                       shard_pace=pace)
        mgr.set_pretrained(tp, sp)
        return mgr

    t0 = time.perf_counter()
    make(2, 0, 0.0).run(build_streams(4, smoke), duration=duration)
    compute_wall = time.perf_counter() - t0
    # Each shard's modeled busy time over the run is ~`duration` virtual
    # seconds, so this pace makes one shard's emulated device wait equal
    # PACE_FRACTION x the probe's host compute.
    pace = PACE_FRACTION * compute_wall / duration

    out = {
        "methodology": ("1-core host: shard_pace emulates per-shard "
                        "sub-accelerator blocking; overlap hides it. "
                        "Serial/overlapped arms asserted bit-identical."),
        "host_cores": os.cpu_count(),
        "compute_only_wall_s": round(compute_wall, 3),
        "pace_fraction": PACE_FRACTION,
        "shard_pace": round(pace, 6),
    }
    for n in (2, 4):
        res_s, serial = _run(make(n, 0, pace), build_streams(4, smoke),
                             duration)
        res_p, par = _run(make(n, n, pace), build_streams(4, smoke),
                          duration)
        _assert_bit_identical(res_s, res_p, f"parallel/{n}_shards")
        assert serial["parallel_rounds"] == 0
        assert par["parallel_rounds"] > 0, "pool never engaged"
        out[f"{n}_shards"] = {
            "serial": serial, "overlapped": par,
            "wall_speedup": round(serial["wall_s"] / par["wall_s"], 3),
        }
    out["manager_parallel_speedup"] = out["4_shards"]["wall_speedup"]
    return out


def bench_placement(n_shards, smoke) -> dict:
    """headroom (lane counts) vs estimator (seconds) on a skewed fleet.

    Shard 0 starts with BOTH drifting cameras plus one stable camera,
    shard 1 with two stables — a lane-count gap of 1, below headroom's
    min_gap=2 hysteresis, so headroom never moves anything; but shard 0's
    T-SA *seconds* dominate the fleet's round wall, and the cost model
    finds the move that lowers the load max (shipping a lane off the hot
    shard pays because its seconds are smaller than the inter-shard gap)
    and fires once the horizon-amortized gain beats ``migration_cost_s``.
    A late admission lands unconditionally under headroom and is rejected
    by the estimator when every warm shard is past ``oversub_limit``."""
    from benchmarks.bench_fleet import build_multi_drift_streams

    duration = 90.0 if smoke else 180.0
    hp = _hp(smoke)
    probe = build_multi_drift_streams(6, smoke)
    tp, sp = _pretrain(probe, smoke)

    def skewed():
        # build_multi_drift_streams order: [drift_S1, drift_S3, stable x4].
        # Interleave so the alternating initial placement lands shard 0 =
        # {drift, drift, stable} and shard 1 = {stable, stable}; the last
        # stable camera is the late admission.
        s = build_multi_drift_streams(6, smoke)
        return [s[0], s[3], s[1], s[4], s[2]], s[5]

    out = {}
    for arm, kw in (
            ("headroom", {"placement": "headroom",
                          "migration": True, "migration_cooldown": 2,
                          "migration_cost_s": 2.0}),
            ("estimator", {"placement": "estimator",
                           "placement_kwargs": {
                               "migration_cost_s": 2.0,
                               "horizon_rounds": 4,
                               "oversub_limit": OVERSUB_LIMIT},
                           "migration": True, "migration_cooldown": 2,
                           "migration_cost_s": 2.0})):
        cams, late = skewed()
        mgr = _manager(hp, smoke, n_shards=n_shards, **kw)
        mgr.set_pretrained(tp, sp)
        _, out[arm] = _run(mgr, cams, duration,
                           admissions=[(duration * 0.55, "late", late)])
    out["migration_divergence"] = (
        out["estimator"]["events"].get("migrate", 0)
        - out["headroom"]["events"].get("migrate", 0))
    out["estimator_rejects"] = out["estimator"]["events"].get("reject", 0)
    out["accuracy_delta"] = round(
        out["estimator"]["fleet_avg_accuracy"]
        - out["headroom"]["fleet_avg_accuracy"], 6)
    return out


def build_scattered_drift_streams(n_streams: int, smoke: bool):
    """The *scattered* twin of bench_fleet's aligned multi-drift fleet.

    Same cameras — S1 and S3 drifters plus stable fillers — but the S3
    camera's first segment is halved, so every subsequent label flip
    lands mid-way between the S1 camera's flips. Aligned drift
    concentrates the retraining load into shared instants (the regime
    drift-pack consolidates onto one T-SA); scattered drift spreads it
    across the round, where lane-count balancing has less to lose."""
    import dataclasses as _dc

    from repro.data.stream import DriftStream, Segment, scenario

    seg_s = 30.0 if smoke else 45.0
    n_seg = 3 if smoke else 4

    def compressed(name):
        return [_dc.replace(s, duration_s=seg_s)
                for s in scenario(name, n_seg)]

    staggered = compressed("S3")
    staggered[0] = _dc.replace(staggered[0], duration_s=seg_s / 2)
    streams = [DriftStream(compressed("S1"), seed=17, img=24),
               DriftStream(staggered, seed=17, img=24)]
    for _ in range(max(0, n_streams - 2)):
        streams.append(DriftStream([Segment(duration_s=seg_s)] * n_seg,
                                   seed=17, img=24))
    return streams[:n_streams]


def bench_scenario_matrix(n_shards, smoke) -> dict:
    """drift-pack vs headroom across aligned vs scattered two-camera
    drift, at equal budget on identical pretrained weights.

    Aligned (bench_fleet's ``build_multi_drift_streams``): both cameras
    flip at the same instants — packing both drifters onto one shard
    lets their N_ldd bursts share a T-SA while the other shard serves
    undisturbed. Scattered (``build_scattered_drift_streams``): the same
    flips staggered by half a segment, diluting the payoff of packing.
    The headline ``drift_pack_gain`` per layout is drift-pack's fleet
    accuracy minus headroom's."""
    from benchmarks.bench_fleet import build_multi_drift_streams

    duration = 90.0 if smoke else 180.0
    hp = _hp(smoke)
    tp, sp = _pretrain(build_multi_drift_streams(4, smoke), smoke)

    builders = {"aligned": build_multi_drift_streams,
                "scattered": build_scattered_drift_streams}
    out = {"layouts": {}}
    for layout, build in builders.items():
        arms = {}
        for arm, kw in (
                ("drift-pack", {"placement": "drift-pack"}),
                ("headroom", {"placement": "headroom",
                              "placement_kwargs": {"min_gap": 1}})):
            mgr = _manager(hp, smoke, n_shards=n_shards, migration=True,
                           migration_cooldown=2, **kw)
            mgr.set_pretrained(tp, sp)
            _, arms[arm] = _run(mgr, build(4, smoke), duration)
        out["layouts"][layout] = arms
    out["drift_pack_gain"] = {
        layout: round(arms["drift-pack"]["fleet_avg_accuracy"]
                      - arms["headroom"]["fleet_avg_accuracy"], 6)
        for layout, arms in out["layouts"].items()}
    return out


def main(argv=None):
    import tempfile

    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI")
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--fail-shard", type=int, default=1,
                    help="shard index the injector kills (CI matrix leg)")
    ap.add_argument("--parallel", type=int, default=0,
                    help="parallel_shards for the recovery/migration "
                         "sections (CI matrix leg; 0 = serial)")
    ap.add_argument("--out", default="BENCH_manager.json")
    args = ap.parse_args(argv)
    if not 0 <= args.fail_shard < args.shards:
        ap.error(f"--fail-shard must be in [0, {args.shards})")

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="bench_manager_ckpt_") as d:
        recovery = bench_recovery(args.shards, args.fail_shard,
                                  args.smoke, d, args.parallel)
    migration = bench_migration(args.shards, args.smoke, args.parallel)
    parallel = bench_parallel(args.smoke)
    placement = bench_placement(args.shards, args.smoke)
    scenario_matrix = bench_scenario_matrix(args.shards, args.smoke)
    result = {
        "bench": "manager",
        "mode": "smoke" if args.smoke else "full",
        "backend": jax.default_backend(),
        "n_shards": args.shards,
        "parallel_shards": args.parallel,
        "manager_parallel_speedup": parallel["manager_parallel_speedup"],
        "recovery": recovery,
        "migration": migration,
        "parallel": parallel,
        "placement": placement,
        "scenario_matrix": scenario_matrix,
    }

    # Write BEFORE the acceptance asserts so a failing comparison still
    # leaves the per-arm numbers to diagnose (CI uploads the file).
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result, indent=2))
    print(f"wrote {args.out} in {time.perf_counter() - t0:.1f}s")

    for arm in ("no_fault", "fault"):
        assert recovery[arm]["lanes"] == 3, \
            f"recovery/{arm}: a camera was lost"
        assert recovery[arm]["conservation_gap"] < 1e-6, \
            f"recovery/{arm}: manager/shard ledgers diverged"
    for arm in ("off", "on"):
        assert migration[arm]["conservation_gap"] < 1e-6, \
            f"migration/{arm}: manager/shard ledgers diverged"
        assert migration[arm]["lanes"] == 3
    assert recovery["fault"]["events"].get("fail", 0) == 1
    assert recovery["fault"]["events"].get("recover", 0) >= 1, \
        "the faulted run never recovered a lane"
    assert recovery["fault"]["dead_shards"] == 1
    assert recovery["accuracy_delta"] <= ACCURACY_TOLERANCE, \
        (f"fault cost {recovery['accuracy_delta']} fleet accuracy "
         f"(tolerance {ACCURACY_TOLERANCE})")
    # Overlapped stepping: bit-identity is asserted inside bench_parallel
    # (before any number is reported); here, the wall win must be real.
    floor = 1.3 if not args.smoke else 1.0
    assert parallel["manager_parallel_speedup"] > floor, \
        (f"4-shard overlap speedup "
         f"{parallel['manager_parallel_speedup']} <= {floor}")
    # Placement: seconds-based estimator must act where lane-count
    # headroom cannot (balanced counts, skewed seconds), pay the charged
    # migration cost, and reject the late oversubscribed admission.
    assert placement["migration_divergence"] >= 1, \
        "estimator never out-migrated headroom on the skewed fleet"
    assert placement["headroom"]["events"].get("migrate", 0) == 0, \
        "headroom migrated on balanced lane counts — scenario broken"
    est = placement["estimator"]
    assert est["migration_cost_s"] == pytest_approx(
        2.0 * est["events"].get("migrate", 0)), \
        "migration cost not charged per move"
    assert placement["estimator_rejects"] >= 1, \
        "estimator admitted the late camera on an oversubscribed fleet"
    assert placement["headroom"]["lanes"] == 6  # late camera admitted
    assert est["lanes"] == 5  # late camera rejected
    # Scenario matrix: every arm keeps all four cameras with conserved
    # ledgers in both drift layouts (which placement wins per layout is
    # the measured result, not an invariant).
    for layout, arms in scenario_matrix["layouts"].items():
        for arm in ("drift-pack", "headroom"):
            assert arms[arm]["lanes"] == 4, \
                f"scenario_matrix/{layout}/{arm}: a camera was lost"
            assert arms[arm]["conservation_gap"] < 1e-6, \
                f"scenario_matrix/{layout}/{arm}: ledgers diverged"
    return result


def pytest_approx(x, eps=1e-9):
    """Tiny float-compare helper (no pytest dependency in the bench)."""
    class _A:
        def __eq__(self, other):
            return abs(other - x) < eps
    return _A()


def run():
    """Registry entry (benchmarks/run.py): smoke manager sweep as CSV
    rows. Writes to a distinct file so a full BENCH_manager.json
    survives."""
    result = main(["--smoke", "--out", "BENCH_manager_smoke.json"])
    rows = []
    for arm in ("no_fault", "fault"):
        r = result["recovery"][arm]
        rows.append((f"manager/recovery/{arm}", r["wall_s"] * 1e6,
                     f"acc={r['fleet_avg_accuracy']}"))
    for arm in ("off", "on"):
        r = result["migration"][arm]
        rows.append((f"manager/migration/{arm}", r["wall_s"] * 1e6,
                     f"acc={r['fleet_avg_accuracy']}"))
    for n in (2, 4):
        for arm in ("serial", "overlapped"):
            r = result["parallel"][f"{n}_shards"][arm]
            rows.append((f"manager/parallel/{n}shard/{arm}",
                         r["wall_s"] * 1e6,
                         f"acc={r['fleet_avg_accuracy']}"))
    for arm in ("headroom", "estimator"):
        r = result["placement"][arm]
        rows.append((f"manager/placement/{arm}", r["wall_s"] * 1e6,
                     f"acc={r['fleet_avg_accuracy']}"))
    for layout, arms in result["scenario_matrix"]["layouts"].items():
        for arm, r in arms.items():
            rows.append((f"manager/scenario/{layout}/{arm}",
                         r["wall_s"] * 1e6,
                         f"acc={r['fleet_avg_accuracy']}"))
    return rows


if __name__ == "__main__":
    main()
