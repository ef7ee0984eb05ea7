"""Dispatch-layer benchmark: sequential vs. concurrent execution + fused
score-window inference.

Two measurements, written machine-readable to ``BENCH_dispatch.json`` (the
first entry of the bench trajectory):

* **session** — one `CLSession` per dispatch mode on a forced 2-row mesh,
  identical pretrained weights and stream: host wall-clock, executed phases,
  mean per-phase virtual time (sequential charges the T-SA sum, concurrent
  charges ``max(t_TSA, t_BSA)`` — see core/dispatch.py), and the number of
  jitted apply dispatches issued by the inference+labeling kernels.
* **scoring_fusion** — the eval/labeling inference path: scoring W frame
  windows one-jitted-call-per-window (the seed pattern) vs. ONE fused
  ``predict_batched`` call, with frames produced through the prefetching
  window iterator (`DriftStream.windows`) so host-side frame synthesis
  overlaps device work. Acceptance: fused issues fewer jitted calls.
* **fused** (PR 7) — the MX hot path itself: ``ops.mx_matmul_fused`` (the
  whole quantize→matmul chain as ONE program) against the unfused
  ``ops.mx_quantize``→``ops.mx_matmul`` pipeline (three programs with MX
  tensors materialized between them), measured in the container's serving
  kernel mode at the repo's hot-path GEMM sizes, bit-identity asserted per
  shape; plus the version-keyed serving-copy cache on repeated teacher
  labeling bursts (cached vs ``maxsize=0``). Headlines:
  ``fused_wall_speedup`` (geomean), ``fused_op_reduction`` (jitted
  programs per GEMM: 3 → 1), ``label_cache_speedup``. Acceptance: the op
  reduction is >= 2x (deterministic) and fused is never slower.
* **bwd_pair** (PR 9) — the retraining backward: both gradient GEMMs of a
  dense layer as ONE program (``ops.mx_matmul_bwd_pair``) vs the two
  independent fused launches they replace, bit-identity asserted per
  shape. Headline ``bwd_pair_speedup`` is the PROGRAM reduction per
  backward (2 → 1, measured via kernel_stats and asserted >= 2x,
  deterministic) — the launch-count win the fusion exists for; the raw
  wall times of both arms are reported per shape, with the caveat that
  the CPU interpreter's per-step emulation cost scales with the number
  of kernel operands, so its wall ratio under-reports what a native
  single launch saves.
* **serve_prequant** (PR 9) — the weight-resident serving path: quantize
  the weight ONCE (``ops.mx_quantize_rhs``), serve every window through
  ``ops.mx_matmul_prequant``, vs the fused GEMM re-quantizing the weight
  in-program every window. Bit-identity asserted per window; kernel_stats
  proves per-window weight-quantization ops drop to ZERO after the fill
  (asserted). Headline ``serve_prequant_speedup``.

Both PR 9 sections also re-run the PR 7 no-silent-ref-fallback audit over
every hot-path op they dispatch.

Run:  PYTHONPATH=src python benchmarks/bench_dispatch.py [--smoke] [--out F]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np


def _session_stats(res, session, wall_s: float) -> dict:
    recs = res.records
    dts = [r.t - r.phase_start for r in recs]
    return {
        "wall_s": round(wall_s, 3),
        "phases": len(recs),
        "virtual_end_s": round(recs[-1].t, 4) if recs else 0.0,
        "mean_phase_dt_s": round(float(np.mean(dts)), 6) if dts else 0.0,
        "mean_t_tsa_s": round(float(np.mean([r.t_tsa for r in recs])), 6)
        if recs else 0.0,
        "mean_t_bsa_s": round(float(np.mean([r.t_bsa for r in recs])), 6)
        if recs else 0.0,
        "avg_accuracy": round(res.avg_accuracy, 6),
        "jit_calls": (session.inference.n_apply_calls
                      + session.labeling.n_apply_calls),
    }


def bench_session(smoke: bool) -> dict:
    from repro.configs.dacapo_pairs import RESNET18, WIDERESNET50
    from repro.core.allocation import CLHyperParams
    from repro.core.partition import forced_row_mesh
    from repro.core.session import CLSystemSpec, pretrain_model
    from repro.data.stream import DriftStream, scenario
    from repro.models.registry import make_vision_model

    duration = 20.0 if smoke else 60.0
    hp = CLHyperParams(n_t=32 if smoke else 48, n_l=16 if smoke else 24,
                       c_b=128 if smoke else 192, epochs=1)
    stream = DriftStream(scenario("S1", 2 if smoke else 3), seed=5, img=24)
    rng = np.random.default_rng(0)
    steps = (10, 8) if smoke else (25, 15)
    tp = pretrain_model(make_vision_model(WIDERESNET50.reduced()), stream,
                        steps[0], 32, rng)
    sp = pretrain_model(make_vision_model(RESNET18.reduced()), stream,
                        steps[1], 32, rng, segments=stream.segments[:1],
                        seed=8)

    # Forced 2-row mesh: T-SA and B-SA become disjoint sub-meshes so the
    # concurrent mode's overlap model matches the bound placement.
    mesh = forced_row_mesh(2)
    base = CLSystemSpec(student=RESNET18, teacher=WIDERESNET50, hp=hp,
                        allocator="dacapo-spatiotemporal", apply_mx=False,
                        seed=0, eval_fps=0.5, mesh=mesh)

    out = {"duration_s": duration}
    for mode in ("sequential", "concurrent"):
        session = dataclasses.replace(base, dispatch=mode).build()
        session.set_pretrained(tp, sp)
        t0 = time.perf_counter()
        res = session.run(stream, duration=duration)
        wall = time.perf_counter() - t0
        out[mode] = _session_stats(res, session, wall)
    seq_dt, con_dt = (out["sequential"]["mean_phase_dt_s"],
                      out["concurrent"]["mean_phase_dt_s"])
    out["virtual_phase_speedup"] = round(seq_dt / con_dt, 4) if con_dt else 0
    return out


def bench_scoring_fusion(smoke: bool) -> dict:
    from repro.configs.dacapo_pairs import RESNET18
    from repro.core.estimator import DaCapoEstimator
    from repro.core.kernel import InferenceKernel
    from repro.data.stream import DriftStream, scenario
    from repro.models.registry import make_vision_model

    n_windows = 6 if smoke else 16
    frames_per_window = 8 if smoke else 24
    stream = DriftStream(scenario("S1", 2), seed=5, img=24)
    model = make_vision_model(RESNET18.reduced())
    params = model.init(jax.random.PRNGKey(0))
    kernel = InferenceKernel(model, RESNET18, DaCapoEstimator(),
                             apply_mx=False)

    window_s = frames_per_window / stream.fps
    spans_end = n_windows * window_s

    def gather():
        it = stream.windows(0.0, spans_end, window_s,
                            max_frames=frames_per_window, prefetch=3)
        return [(x, y) for _, _, x, y in it]

    windows = gather()
    total = sum(len(x) for x, _ in windows)

    # Warm both jit paths (per-window shape and fused shape).
    np.asarray(kernel.predict_async(params, windows[0][0]))
    [np.asarray(p) for p in
     kernel.predict_batched(params, [x for x, _ in windows])]

    kernel.n_apply_calls = 0
    t0 = time.perf_counter()
    preds_pw = [kernel.predict_async(params, x) for x, _ in windows]
    preds_pw = [np.asarray(p) for p in preds_pw]
    wall_pw = time.perf_counter() - t0
    calls_pw = kernel.n_apply_calls

    kernel.n_apply_calls = 0
    t0 = time.perf_counter()
    preds_f = kernel.predict_batched(params, [x for x, _ in windows])
    preds_f = [np.asarray(p) for p in preds_f]
    wall_f = time.perf_counter() - t0
    calls_f = kernel.n_apply_calls

    assert all(np.array_equal(a, b) for a, b in zip(preds_pw, preds_f)), \
        "fused predictions diverge from per-window predictions"
    assert calls_f < calls_pw, \
        f"fusion must issue fewer jitted calls ({calls_f} !< {calls_pw})"

    return {
        "n_windows": n_windows,
        "frames_per_window": frames_per_window,
        "per_window": {"jit_calls": calls_pw, "wall_s": round(wall_pw, 4),
                       "frames_per_s": round(total / wall_pw, 1)},
        "fused": {"jit_calls": calls_f, "wall_s": round(wall_f, 4),
                  "frames_per_s": round(total / wall_f, 1)},
        "call_reduction": round(calls_pw / calls_f, 2),
    }


def _wall_us(fn, reps: int) -> float:
    fn()  # warm (jit compile / trace)
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps * 1e6


def bench_fused(smoke: bool) -> dict:
    from repro.kernels import ops

    # The repo's hot-path GEMM sizes (img=24 models: small M, modest K/N —
    # where per-program dispatch overhead is a real fraction of the GEMM).
    shapes = ([(16, 432, 64), (32, 128, 64)] if smoke
              else [(16, 432, 64), (32, 128, 64), (64, 256, 128)])
    reps = 5 if smoke else 30
    per_shape = {}
    speedups = []
    for m, k, n in shapes:
        a = jax.random.normal(jax.random.PRNGKey(0), (m, k))
        b = jax.random.normal(jax.random.PRNGKey(1), (k, n))
        # Bit-identity first: fused must equal the unfused chain exactly.
        fused0 = np.asarray(ops.mx_matmul_fused(a, b, "mx6", "mx6"))
        unfused0 = np.asarray(ops.mx_matmul(a, b, "mx6", "mx6"))
        assert np.array_equal(fused0, unfused0), \
            f"fused != unfused at {(m, k, n)}"
        ops.reset_kernel_stats()
        wall_u = _wall_us(lambda: jax.block_until_ready(
            ops.mx_matmul(a, b, "mx6", "mx6")), reps)
        stats = ops.kernel_stats()
        ops_unfused = sum(sum(p.values()) for op, p in stats.items()
                          if op != "mx_matmul_fused") / (reps + 1)
        ops.reset_kernel_stats()
        wall_f = _wall_us(lambda: jax.block_until_ready(
            ops.mx_matmul_fused(a, b, "mx6", "mx6")), reps)
        ops_fused = sum(
            ops.kernel_stats()["mx_matmul_fused"].values()) / (reps + 1)
        ops.reset_kernel_stats()
        speedup = wall_u / wall_f
        speedups.append(speedup)
        per_shape[f"{m}x{k}x{n}"] = {
            "unfused_us": round(wall_u, 1), "fused_us": round(wall_f, 1),
            "wall_speedup": round(speedup, 2),
            "ops_per_gemm_unfused": ops_unfused,
            "ops_per_gemm_fused": ops_fused,
        }
    op_reduction = (per_shape[next(iter(per_shape))]["ops_per_gemm_unfused"]
                    / per_shape[next(iter(per_shape))]["ops_per_gemm_fused"])
    assert op_reduction >= 2.0, \
        f"fused must at least halve the jitted-op count ({op_reduction})"
    return {
        "kernel_mode": ops.kernel_mode(),
        "shapes": per_shape,
        "fused_wall_speedup": round(
            float(np.exp(np.mean(np.log(speedups)))), 2),
        "fused_op_reduction": round(op_reduction, 2),
    }


def _assert_no_silent_ref(ops_mod, op_names) -> None:
    """The PR 7 audit, extended: in a non-ref serving mode every listed op
    must have been served by its kernel path — zero silent ref fallbacks."""
    stats = ops_mod.kernel_stats()
    mode = ops_mod.kernel_mode()
    for op in op_names:
        assert op in stats, (op, stats)
        if mode != "ref":
            assert "ref" not in stats[op], (op, stats)


def bench_bwd_pair(smoke: bool) -> dict:
    """The retraining backward: dX + dW as ONE program vs two fused GEMMs."""
    from repro.kernels import ops

    shapes = ([(16, 432, 64), (32, 128, 64)] if smoke
              else [(16, 432, 64), (32, 128, 64), (64, 256, 128)])
    reps = 5 if smoke else 30
    per_shape = {}
    speedups = []
    for m, k, n in shapes:
        g = jax.random.normal(jax.random.PRNGKey(2), (m, n))
        x = jax.random.normal(jax.random.PRNGKey(3), (m, k))
        w = jax.random.normal(jax.random.PRNGKey(4), (k, n))
        # Bit-identity first: the pair must equal the two-GEMM chain.
        dx, dw = ops.mx_matmul_bwd_pair(g, x, w, "mx9")
        assert np.array_equal(np.asarray(dx), np.asarray(
            ops.mx_matmul_fused(g, w.T, "mx9", "mx9"))), (m, k, n)
        assert np.array_equal(np.asarray(dw), np.asarray(
            ops.mx_matmul_fused(x.T, g, "mx9", "mx9"))), (m, k, n)

        def two_gemms():
            jax.block_until_ready(ops.mx_matmul_fused(g, w.T, "mx9", "mx9"))
            jax.block_until_ready(ops.mx_matmul_fused(x.T, g, "mx9", "mx9"))

        ops.reset_kernel_stats()
        wall_u = _wall_us(two_gemms, reps)
        progs_unfused = sum(
            ops.kernel_stats()["mx_matmul_fused"].values()) / (reps + 1)
        ops.reset_kernel_stats()
        wall_p = _wall_us(lambda: jax.block_until_ready(
            ops.mx_matmul_bwd_pair(g, x, w, "mx9")), reps)
        progs_pair = sum(
            ops.kernel_stats()["mx_matmul_bwd_pair"].values()) / (reps + 1)
        _assert_no_silent_ref(ops, ["mx_matmul_bwd_pair"])
        ops.reset_kernel_stats()
        speedup = wall_u / wall_p
        speedups.append(speedup)
        per_shape[f"{m}x{k}x{n}"] = {
            "two_gemms_us": round(wall_u, 1), "pair_us": round(wall_p, 1),
            "wall_speedup": round(speedup, 2),
            "programs_per_bwd_unfused": progs_unfused,
            "programs_per_bwd_pair": progs_pair,
        }
    first = per_shape[next(iter(per_shape))]
    program_reduction = (first["programs_per_bwd_unfused"]
                         / first["programs_per_bwd_pair"])
    assert program_reduction >= 2.0, \
        f"the pair must halve the backward program count ({program_reduction})"
    # The headline is the deterministic program reduction (2 GEMM launches
    # + duplicate g-quantization -> 1 launch); the wall geomean is reported
    # alongside but is an emulation artifact on CPU hosts (see module doc).
    return {
        "kernel_mode": ops.kernel_mode(),
        "shapes": per_shape,
        "bwd_pair_speedup": round(program_reduction, 2),
        "bwd_pair_program_reduction": round(program_reduction, 2),
        "bwd_pair_wall_speedup": round(
            float(np.exp(np.mean(np.log(speedups)))), 2),
    }


def bench_serve_prequant(smoke: bool) -> dict:
    """Weight-resident serving: quantize the weight once, then serve every
    window with zero weight-quantization work — vs the fused GEMM that
    re-quantizes the weight inside every program."""
    from repro.kernels import ops

    n_windows = 6 if smoke else 16
    reps = 5 if smoke else 20
    m, k, n = (32, 256, 64)
    xs = [jax.random.normal(jax.random.PRNGKey(100 + i), (m, k))
          for i in range(n_windows)]
    w = jax.random.normal(jax.random.PRNGKey(5), (k, n))
    qw = ops.mx_quantize_rhs(w, "mx6")  # the one-time fill

    # Bit-identity per window: resident serving == re-quantizing serving.
    for x in xs:
        assert np.array_equal(
            np.asarray(ops.mx_matmul_prequant(x, qw, "mx6")),
            np.asarray(ops.mx_matmul_fused(x, w, "mx6", "mx6")))

    # Op accounting over one serving sweep: after the fill (1 mx_quantize,
    # counted above at qw creation — redone here under reset for the
    # audit), the per-window weight-quantization op count is exactly zero.
    ops.reset_kernel_stats()
    qw2 = ops.mx_quantize_rhs(w, "mx6")
    for x in xs:
        jax.block_until_ready(ops.mx_matmul_prequant(x, qw2, "mx6"))
    stats = ops.kernel_stats()
    fill_quants = sum(stats.get("mx_quantize", {}).values())
    serve_calls = sum(stats["mx_matmul_prequant"].values())
    assert fill_quants == 1, stats
    assert serve_calls == n_windows, stats
    weight_quants_per_window = (fill_quants - 1) / n_windows
    assert weight_quants_per_window == 0.0, stats
    _assert_no_silent_ref(ops, ["mx_matmul_prequant"])
    ops.reset_kernel_stats()

    def serve_resident():
        for x in xs:
            jax.block_until_ready(ops.mx_matmul_prequant(x, qw, "mx6"))

    def serve_requant():
        for x in xs:
            jax.block_until_ready(ops.mx_matmul_fused(x, w, "mx6", "mx6"))

    wall_r = _wall_us(serve_resident, reps)
    wall_q = _wall_us(serve_requant, reps)
    ops.reset_kernel_stats()
    return {
        "kernel_mode": ops.kernel_mode(),
        "gemm": f"{m}x{k}x{n}",
        "n_windows": n_windows,
        "resident_us": round(wall_r, 1),
        "requant_us": round(wall_q, 1),
        "weight_quant_ops_per_window": weight_quants_per_window,
        "serve_prequant_speedup": round(wall_q / wall_r, 2),
    }


def bench_label_cache(smoke: bool) -> dict:
    """Repeated teacher labeling bursts, apply_mx=True: the version-keyed
    serving cache quantizes the teacher tree ONCE; the ``maxsize=0``
    baseline re-quantizes it every burst (the pre-PR behavior)."""
    from repro.configs.dacapo_pairs import WIDERESNET50
    from repro.core.estimator import DaCapoEstimator
    from repro.core.kernel import LabelingKernel, ServingParamsCache
    from repro.models.registry import make_vision_model

    burst, reps = 4, (5 if smoke else 20)
    model = make_vision_model(WIDERESNET50.reduced())
    params = model.init(jax.random.PRNGKey(0))
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1),
                                     (burst, 24, 24, 3)), np.float32)
    cached = LabelingKernel(model, WIDERESNET50, DaCapoEstimator(),
                            apply_mx=True)
    uncached = LabelingKernel(model, WIDERESNET50, DaCapoEstimator(),
                              apply_mx=True)
    uncached.serving_cache = ServingParamsCache(maxsize=0)
    y_c = cached.label(params, x, "mx6")  # warm both paths
    y_u = uncached.label(params, x, "mx6")
    assert np.array_equal(y_c, y_u), "cache changed the labels"
    wall_c = _wall_us(lambda: cached.label(params, x, "mx6"), reps)
    wall_u = _wall_us(lambda: uncached.label(params, x, "mx6"), reps)
    stats = cached.serving_cache.stats()
    assert stats["misses"] == 1 and stats["hits"] >= reps, stats
    return {
        "burst_frames": burst,
        "cached_us": round(wall_c, 1), "uncached_us": round(wall_u, 1),
        "label_cache_speedup": round(wall_u / wall_c, 2),
        "cache_stats": stats,
    }


def main():
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI")
    ap.add_argument("--out", default="BENCH_dispatch.json")
    args = ap.parse_args()

    fused = bench_fused(args.smoke)
    bwd_pair = bench_bwd_pair(args.smoke)
    serve_prequant = bench_serve_prequant(args.smoke)
    label_cache = bench_label_cache(args.smoke)
    result = {
        "bench": "dispatch",
        "mode": "smoke" if args.smoke else "full",
        "backend": jax.default_backend(),
        "scoring_fusion": bench_scoring_fusion(args.smoke),
        "fused": fused,
        "bwd_pair": bwd_pair,
        "serve_prequant": serve_prequant,
        "label_cache": label_cache,
        "fused_wall_speedup": fused["fused_wall_speedup"],
        "fused_op_reduction": fused["fused_op_reduction"],
        "bwd_pair_speedup": bwd_pair["bwd_pair_speedup"],
        "bwd_pair_program_reduction": bwd_pair["bwd_pair_program_reduction"],
        "serve_prequant_speedup": serve_prequant["serve_prequant_speedup"],
        "label_cache_speedup": label_cache["label_cache_speedup"],
        "session": bench_session(args.smoke),
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result, indent=2))
    print(f"\nwrote {args.out}")


def run():
    """Registry entry (benchmarks/run.py): smoke measurements as CSV rows."""
    fusion = bench_scoring_fusion(True)
    fused = bench_fused(True)
    bwd_pair = bench_bwd_pair(True)
    serve_prequant = bench_serve_prequant(True)
    cache = bench_label_cache(True)
    session = bench_session(True)
    return [
        ("dispatch/scoring_fused", fusion["fused"]["wall_s"] * 1e6,
         f"call_reduction={fusion['call_reduction']}"),
        ("dispatch/mx_fused",
         next(iter(fused["shapes"].values()))["fused_us"],
         f"wall_speedup={fused['fused_wall_speedup']}"
         f";op_reduction={fused['fused_op_reduction']}"),
        ("dispatch/mx_bwd_pair",
         next(iter(bwd_pair["shapes"].values()))["pair_us"],
         f"wall_speedup={bwd_pair['bwd_pair_speedup']}"
         f";program_reduction={bwd_pair['bwd_pair_program_reduction']}"),
        ("dispatch/serve_prequant", serve_prequant["resident_us"],
         f"speedup={serve_prequant['serve_prequant_speedup']}"
         f";weight_quants_per_window="
         f"{serve_prequant['weight_quant_ops_per_window']}"),
        ("dispatch/label_cache", cache["cached_us"],
         f"speedup={cache['label_cache_speedup']}"),
        ("dispatch/session_sequential",
         session["sequential"]["wall_s"] * 1e6,
         f"phase_dt={session['sequential']['mean_phase_dt_s']}"),
        ("dispatch/session_concurrent",
         session["concurrent"]["wall_s"] * 1e6,
         f"phase_dt={session['concurrent']['mean_phase_dt_s']}"
         f";virtual_speedup={session['virtual_phase_speedup']}"),
    ]


if __name__ == "__main__":
    main()
