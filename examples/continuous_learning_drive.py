"""End-to-end driver: DaCapo continuous learning on a drifting drive.

Runs the full Algorithm 1 system against an extreme scenario (ES1 — all four
drift axes) and compares against the Ekya-like fixed-window baseline on
identical pretrained weights, printing the accuracy timeline.

``--dispatch concurrent`` executes through the async dispatch layer
(core/dispatch.py): a forced 2-row mesh is fissioned into T-SA/B-SA
sub-meshes, score windows are fused into batched inference, each phase
charges max(t_TSA, t_BSA) — the paper's Fig. 4 overlap — instead of the
serial chain, and frame windows flow through the speculative FramePipeline
(data/pipeline.py), whose reconcile hit rate is reported per system.

``--online`` swaps DaCapo-ST for DaCapo-ST-Online, the drift-reactive
spatial re-allocator: watch the tsa/bsa row split move in the phase log
when a drift fires, then return as validation accuracy recovers.

``--trace PATH`` turns on the trace spine (core/trace.py) for the DaCapo
system, dumps the full per-program execution trace as JSON to PATH for
offline analysis (:meth:`~repro.core.trace.SessionTrace.load` /
:class:`~repro.core.replay.TraceReplayer`), and prints the top-5 device
programs by measured host wall time and by virtual-clock cost.

Run:  PYTHONPATH=src python examples/continuous_learning_drive.py [--fast]
          [--dispatch sequential|concurrent] [--online] [--trace PATH]
"""
import argparse

import numpy as np


def main():
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--scenario", default="ES1")
    ap.add_argument("--dispatch", default="sequential",
                    choices=("sequential", "concurrent"))
    ap.add_argument("--online", action="store_true",
                    help="use the drift-reactive online spatial "
                         "re-allocator (DC-ST-Online) instead of DC-ST")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record the DaCapo run's execution trace and "
                         "dump it as JSON to PATH")
    args = ap.parse_args()

    from repro.configs.dacapo_pairs import RESNET18, WIDERESNET50
    from repro.core import CLHyperParams, CLSystemSpec, pretrain_model
    from repro.core.partition import forced_row_mesh
    from repro.data.stream import DriftStream, scenario
    from repro.models.registry import make_vision_model

    mesh = None
    if args.dispatch == "concurrent":
        # Force a 2-row mesh so T-SA and B-SA are disjoint sub-meshes.
        mesh = forced_row_mesh(2)

    n_seg = 3 if args.fast else 5
    duration = 90.0 if args.fast else 240.0
    stream = DriftStream(scenario(args.scenario, n_seg), seed=11, img=24)
    hp = CLHyperParams(n_t=64 if args.fast else 96,
                       n_l=32 if args.fast else 48,
                       c_b=256)

    # One shared pretraining for fairness.
    rng = np.random.default_rng(0)
    steps = (30, 20) if args.fast else (100, 40)
    tp = pretrain_model(make_vision_model(WIDERESNET50.reduced()), stream,
                        steps[0], 48, rng)
    sp = pretrain_model(make_vision_model(RESNET18.reduced()), stream,
                        steps[1], 48, rng, segments=stream.segments[:1],
                        seed=8)

    dacapo = ("dacapo-spatiotemporal-online" if args.online
              else "dacapo-spatiotemporal")
    results = {}
    trace_rec = None
    for allocator in (dacapo, "ekya"):
        session = CLSystemSpec(
            student=RESNET18, teacher=WIDERESNET50, hp=hp,
            allocator=allocator, apply_mx=False, eval_fps=0.5,
            mesh=mesh, dispatch=args.dispatch,
            trace=bool(args.trace) and allocator == dacapo).build()
        if allocator == dacapo:
            trace_rec = session.dispatcher.recorder
        session.set_pretrained(tp, sp)
        # Observer hook: structured per-phase metrics as they happen.
        session.add_observer(lambda rec, name=allocator: print(
            f"  [{name}] phase {rec.index:2d} t={rec.t:6.1f}s "
            f"acc_v={rec.acc_valid:.2f} acc_l={rec.acc_label:.2f}"
            f" tsa/bsa={rec.t_tsa:.2f}/{rec.t_bsa:.2f}s"
            f" rows={rec.decision.rows_tsa}/{rec.decision.rows_bsa}"
            f"{' DRIFT' if rec.drift else ''}"))
        results[allocator] = session.run(stream, duration=duration)

    print(f"\nscenario {args.scenario}, {duration:.0f} virtual seconds")
    print(f"{'time':>6} | {'DaCapo':>10} | {'Ekya':>10}")
    dc = dict(results[dacapo].accuracy_timeline)
    ek = dict(results["ekya"].accuracy_timeline)
    for t in sorted(set(list(dc) + list(ek))):
        a = f"{dc[t]*100:9.1f}%" if t in dc else "         -"
        b = f"{ek[t]*100:9.1f}%" if t in ek else "         -"
        print(f"{t:6.0f} | {a} | {b}")
    for name, res in results.items():
        hits = sum(r.spec_hits for r in res.records)
        misses = sum(r.spec_misses for r in res.records)
        spec = (f" spec-hit-rate={hits / (hits + misses):.0%}"
                if hits + misses else "")
        print(f"{name}: avg={res.avg_accuracy*100:.1f}% "
              f"drifts={res.drift_events} "
              f"label/retrain={res.label_time:.0f}/{res.retrain_time:.0f}s"
              f"{spec}")

    if args.trace and trace_rec is not None:
        trace = trace_rec.trace
        trace.save(args.trace)
        programs = [(ph.index, e) for ph in trace.phases
                    for e in ph.events if e.kind == "program"]
        n_events = sum(len(ph.events) for ph in trace.phases)
        print(f"\ntrace: {len(trace.phases)} phases, {n_events} events "
              f"({len(programs)} programs) -> {args.trace}")
        for title, key in (("host wall time", lambda pe: pe[1].wall_s),
                           ("virtual cost", lambda pe: pe[1].cost_s)):
            print(f"top-5 programs by {title}:")
            for idx, e in sorted(programs, key=key, reverse=True)[:5]:
                path = f" path={e.path}" if e.path else ""
                print(f"  phase {idx:2d} {e.label:>9} [{e.role}] "
                      f"cost={e.cost_s:8.4f}s wall={e.wall_s:8.4f}s "
                      f"units={e.units:g}{path}")


if __name__ == "__main__":
    main()
