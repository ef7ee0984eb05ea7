"""Smoke check: the continuous-learning session runs on a TPU.

Run from the root of a checkout, on a host with a TPU:

    python chip_smoke.py            # one chip, published widths
    python chip_smoke.py --chips 4  # four chips, T-SA/B-SA fission only

One chip, in order:
  (a) fail unless JAX's first device is a TPU;
  (b) student (ResNet18) and teacher (WideResNet50) forward at published
      widths on a seeded batch, on the chip and on the host CPU, both at
      ``highest`` matmul precision; the chip's logits must agree;
  (c) ``CLSystemSpec(student=RESNET18, teacher=WIDERESNET50, reduced=False,
      dispatch="concurrent").build().run(stream)`` on scenario S1 at 224 px
      for at least three phases, checking where the arrays live and that
      accuracies and the SGD loss are sane;
  (d) fail if any MX kernel op was served by the interpreter or the jnp
      reference instead of Pallas;
  (e) print the readings — a smoke reading, not a benchmark.

``--chips 4`` runs only the fission path: one seeded reduced-width session
with ``mesh=forced_row_mesh(4)`` (T-SA and B-SA on different chips) and the
same session with ``mesh=None`` on chip 0; per-phase accuracies must match
and each kernel's outputs must lie on its sub-accelerator's chip.

The last line of standard output is ``{"ok": true, "device": {...}}``; any
failure exits non-zero before it is printed. The persistent compile cache
is placed by ``repro.runtime.compile_cache`` (``JAX_COMPILATION_CACHE_DIR``
or ``<checkout>/.jax_cache``).
"""
import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import default_hp  # noqa: E402
from repro.configs.dacapo_pairs import RESNET18, WIDERESNET50  # noqa: E402
from repro.core import CLHyperParams, CLSystemSpec  # noqa: E402
from repro.core.partition import forced_row_mesh  # noqa: E402
from repro.data.stream import DriftStream, scenario  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models.registry import make_vision_model  # noqa: E402
from repro.runtime.compile_cache import use_compile_cache  # noqa: E402

SEED = 0
REF_BATCH = 8
REF_BOUND = 1e-3  # max |logit_tpu - logit_cpu| / max |logit_cpu|
PRETRAIN_STEPS = (20, 10)  # teacher, student
PRETRAIN_BATCH = 16
DURATION_S = 30.0  # virtual seconds of S1
MIN_PHASES = 3
MIN_PROGRAMS = 2  # per kernel
FISSION_ACC_TOL = 1e-6  # same programs on chips of one kind: exact


class CompileMeter:
    """XLA compile seconds and persistent-cache hits, from JAX's events."""

    def __init__(self):
        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def check(ok, what):
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def on_devices(tree, devices) -> bool:
    return all(leaf.devices() <= set(devices)
               for leaf in jax.tree_util.tree_leaves(tree))


def device_check():
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={jax.device_count()}", flush=True)
    check(dev.platform == "tpu",
          f"JAX's first device is {dev.platform!r}, not a TPU")
    return dev


def reference_check(tpu):
    """Student and teacher logits on the chip vs the host CPU."""
    cpu = jax.devices("cpu")[0]
    x, _ = DriftStream(scenario("S1", 1), seed=SEED,
                       img=RESNET18.img_size).frames(
                           0.0, 1.0, max_frames=REF_BATCH)
    worst = 0.0
    for cfg in (RESNET18, WIDERESNET50):
        model = make_vision_model(cfg)
        with jax.default_device(cpu):
            params = model.init(jax.random.PRNGKey(SEED))
        apply = jax.jit(model.apply)
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(apply(params, jax.device_put(x, cpu)))
            out = apply(jax.device_put(params, tpu), jax.device_put(x, tpu))
        check(out.devices() == {tpu}, f"{cfg.name} logits not on the chip")
        out = np.asarray(out)
        check(out.shape == (REF_BATCH, cfg.num_classes)
              and np.isfinite(out).all(),
              f"{cfg.name} logits: shape {out.shape} or not finite")
        err = float(np.abs(out - ref).max() / np.abs(ref).max())
        print(f"reference: {cfg.name} max|dlogit|/max|logit_cpu| = {err!r} "
              f"(bound {REF_BOUND})", flush=True)
        check(err <= REF_BOUND, f"{cfg.name} chip logits off by {err}")
        worst = max(worst, err)
    return worst


def build_session(*, reduced, hp, mesh=None):
    return CLSystemSpec(student=RESNET18, teacher=WIDERESNET50, hp=hp,
                        reduced=reduced, dispatch="concurrent", seed=SEED,
                        mesh=mesh).build()


def check_records(result, label):
    check(len(result.records) >= MIN_PHASES,
          f"{label}: {len(result.records)} phases, want >= {MIN_PHASES}")
    accs = ([a for _, a in result.accuracy_timeline]
            + [r.acc_valid for r in result.records]
            + [r.acc_label for r in result.records]
            + [result.avg_accuracy])
    check(all(math.isfinite(a) and 0.0 <= a <= 1.0 for a in accs),
          f"{label}: accuracy outside [0, 1] or not finite")


def programs(session):
    return {k.name: k.n_apply_calls for k in session.kernels}


def probe(session, x):
    """One more program each of the serving and the labeling kernel."""
    prec = session.policy
    served = session.inference.predict_async(
        session.inference.serving_params(session.student_params,
                                         prec.inference), x)
    labels = session.labeling.label_async(session.teacher_params, x,
                                          prec.labeling)
    return served, labels


def main_path(meter):
    """(c) The session at published widths, one chip, concurrent dispatch."""
    hp = default_hp()
    stream = DriftStream(scenario("S1", 2), seed=SEED,
                         img=RESNET18.img_size)
    compile_before = meter.compile_s
    t0 = time.perf_counter()
    session = build_session(reduced=False, hp=hp)
    check(session.student_cfg == RESNET18
          and session.teacher_cfg == WIDERESNET50,
          "the session does not execute the published configs")
    session.pretrain(stream, teacher_steps=PRETRAIN_STEPS[0],
                     student_steps=PRETRAIN_STEPS[1], batch=PRETRAIN_BATCH)
    jax.block_until_ready((session.teacher_params, session.student_params))
    setup_s = time.perf_counter() - t0
    compile_setup = meter.compile_s - compile_before

    compile_before = meter.compile_s
    t0 = time.perf_counter()
    result = session.run(stream, duration=DURATION_S)
    jax.block_until_ready(session.student_params)
    run_s = time.perf_counter() - t0
    compile_run = meter.compile_s - compile_before

    check_records(result, "main path")
    calls = programs(session)
    check(all(n >= MIN_PROGRAMS for n in calls.values()),
          f"programs per kernel {calls}, want >= {MIN_PROGRAMS} each")
    loss = session.retrain.last_loss
    check(loss is not None and math.isfinite(float(loss)),
          f"SGD loss {loss}")

    # Where the arrays live: model state, the last loss, and one more
    # program of each kernel.
    served, labels = probe(
        session, stream.frames(0.0, 1.0, max_frames=hp.sgd_batch)[0])
    tpus = [d for d in jax.devices() if d.platform == "tpu"]
    check(on_devices((session.student_params, session.teacher_params, loss,
                      served, labels), tpus),
          "a returned device array is not on the TPU")
    return dict(phases=len(result.records), programs=calls,
                setup_s=setup_s, run_s=run_s, loss=float(loss),
                avg_accuracy=result.avg_accuracy,
                compile_setup_s=compile_setup,
                compile_run_s=compile_run)


def kernel_path_check():
    """(d) No MX op served by the interpreter or the reference on a TPU."""
    stats = ops.kernel_stats()
    print(f"kernel paths: {stats or 'no MX kernel op on the session path'}",
          flush=True)
    slow = {op: paths for op, paths in stats.items()
            if paths.get("interpret") or paths.get("ref")}
    check(not slow, f"MX ops not served by Pallas on the TPU: {slow}")


def fission_path():
    """--chips 4: the same seeded reduced session, fissioned over four
    chips and on chip 0 alone."""
    check(jax.device_count() >= 4,
          f"--chips 4 needs four chips, found {jax.device_count()}")
    hp = CLHyperParams(n_t=48, n_l=24, c_b=192)
    stream = DriftStream(scenario("S1", 3), seed=SEED, img=24)
    base = build_session(reduced=True, hp=hp)
    base.pretrain(stream, teacher_steps=30, student_steps=15, batch=32)
    runs = {}
    for label, mesh in (("fission", forced_row_mesh(4)), ("one chip", None)):
        session = build_session(reduced=True, hp=hp, mesh=mesh)
        session.set_pretrained(base.teacher_params, base.student_params)
        t0 = time.perf_counter()
        result = session.run(stream, duration=45.0)
        jax.block_until_ready(session.student_params)
        check_records(result, label)
        runs[label] = (session, result, time.perf_counter() - t0)

    session, result, _ = runs["fission"]
    part = session.partition
    check(not part.time_shared, "the fission session was not fissioned")
    t_dev, b_dev = part.t_sa.devices.flat[0], part.b_sa.devices.flat[0]
    check(t_dev != b_dev, f"T-SA and B-SA share chip {t_dev}")
    served, labels = probe(
        session, stream.frames(0.0, 1.0, max_frames=hp.sgd_batch)[0])
    check(served.devices() == {b_dev},
          f"serving output on {served.devices()}, B-SA is {b_dev}")
    check(labels.devices() == {t_dev},
          f"labeling output on {labels.devices()}, T-SA is {t_dev}")
    check(on_devices((session.student_params, session.retrain.last_loss),
                     [t_dev]),
          f"retraining output not on the T-SA chip {t_dev}")
    print(f"fission: T-SA chips {[d.id for d in part.t_sa.devices.flat]} "
          f"(runs on {t_dev.id}), B-SA chips "
          f"{[d.id for d in part.b_sa.devices.flat]} (runs on {b_dev.id}); "
          f"serving on {b_dev.id}, labeling and retraining on {t_dev.id}",
          flush=True)

    other = runs["one chip"][1]
    check(len(result.records) == len(other.records),
          f"phase counts differ: {len(result.records)} vs "
          f"{len(other.records)}")
    diff = max(
        [abs(a.acc_valid - b.acc_valid) for a, b in zip(result.records,
                                                       other.records)]
        + [abs(a.acc_label - b.acc_label) for a, b in zip(result.records,
                                                         other.records)]
        + [abs(a[1] - b[1]) for a, b in zip(result.accuracy_timeline,
                                            other.accuracy_timeline)])
    for label, (s, r, wall) in runs.items():
        print(f"smoke reading (not a benchmark): {label}: "
              f"{len(r.records)} phases, programs {programs(s)}, "
              f"run {wall!r} s, avg accuracy {r.avg_accuracy!r}", flush=True)
    print(f"fission vs one chip: max per-phase accuracy difference "
          f"{diff!r} (tolerance {FISSION_ACC_TOL})", flush=True)
    check(diff <= FISSION_ACC_TOL,
          f"fission accuracies differ from one chip by {diff}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    meter = CompileMeter()
    cache_dir = use_compile_cache()
    dev = device_check()
    print(f"compile cache: {cache_dir}", flush=True)
    ops.reset_kernel_stats()
    if args.chips == 4:
        fission_path()
    else:
        ref_err = reference_check(dev)
        reading = main_path(meter)
        kernel_path_check()
        print(f"reference error (worst of student, teacher): {ref_err!r}")
        for key in ("phases", "programs", "setup_s", "compile_setup_s",
                    "run_s", "compile_run_s", "loss", "avg_accuracy"):
            print(f"smoke reading (not a benchmark): {key} = "
                  f"{reading[key]!r}")
    print(f"smoke reading (not a benchmark): XLA compile s = "
          f"{meter.compile_s!r} over {meter.compiles} programs, "
          f"persistent-cache hits = {meter.cache_hits}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}), flush=True)


if __name__ == "__main__":
    main()
