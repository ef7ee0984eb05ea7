"""Quickstart: the three public layers of the framework in one script.

1. MX precision — quantize tensors / run an MX matmul (the paper's DPE).
2. Continuous learning — Algorithm 1 on a drifting stream (60 virtual s).
3. LM zoo — one train step + one decode step of an assigned architecture.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np


def demo_mx():
    from repro.kernels import ops

    x = jax.random.normal(jax.random.PRNGKey(0), (64, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 64))
    exact = x @ w
    for prec in ("mx4", "mx6", "mx9"):
        out = ops.mx_matmul(x, w, prec, prec)
        rel = float(jnp.linalg.norm(out - exact) / jnp.linalg.norm(exact))
        print(f"  {prec}: matmul relative error {rel:.4f}")


def demo_continuous_learning():
    from repro.configs.dacapo_pairs import RESNET18, WIDERESNET50
    from repro.core import CLHyperParams, CLSystemSpec
    from repro.data.stream import DriftStream, scenario

    stream = DriftStream(scenario("S1", 3), seed=0, img=24)
    hp = CLHyperParams(n_t=48, n_l=24, c_b=192)
    # Declarative front door: describe the system, then build the session.
    session = CLSystemSpec(student=RESNET18, teacher=WIDERESNET50, hp=hp,
                           allocator="dacapo-spatiotemporal",
                           apply_mx=False, eval_fps=0.5).build()
    print(f"  spatial allocation: T-SA={session.r_tsa} rows, "
          f"B-SA={session.r_bsa} rows (30 FPS inference)")
    session.pretrain(stream, teacher_steps=30, student_steps=20, batch=32)
    result = session.run(stream, duration=60.0)
    print(f"  60s of S1: avg accuracy {result.avg_accuracy*100:.1f}%, "
          f"{result.drift_events} drift events, "
          f"retrain/label = {result.retrain_time:.1f}s/"
          f"{result.label_time:.1f}s")


def demo_lm():
    from repro import configs
    from repro.models.transformer import make_model

    cfg = configs.get_arch("gemma2-2b").reduced()
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0,
                              cfg.vocab_size)
    loss, metrics = model.loss(
        params, {"inputs": toks[:, :-1], "labels": toks[:, 1:]})
    print(f"  {cfg.name}: train loss {float(loss):.2f}")
    logits, caches = model.prefill(params, toks[:, :16], cache_capacity=33)
    logits, _ = model.decode_step(params, toks[:, 16:17], jnp.asarray(16),
                                  caches)
    print(f"  prefill(16) + decode(1): logits {logits.shape}")


if __name__ == "__main__":
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    print("== MX block-floating-point (paper §V-B) ==")
    demo_mx()
    print("== LM architecture zoo (assigned archs) ==")
    demo_lm()
    print("== Continuous learning (Algorithm 1) ==")
    demo_continuous_learning()
    print("done.")
