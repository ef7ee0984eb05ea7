"""Kernel micro-benchmarks: MX quantize / MX matmul / flash attention.

Wall-times measured on the jitted jnp reference path (CPU container; the
Pallas kernels target TPU and are validated in interpret mode by tests).
'derived' reports the kernel-level roofline on TPU v5e from the analytic
byte/FLOP counts (the number the DPE comparison in §Perf uses).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core.estimator import TPU_HBM_BW, TPU_PEAK_FLOPS
from repro.kernels import ref
from repro.kernels.ref import MANTISSA_BITS


def _time(fn, *args, reps=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps * 1e6


def run():
    rows = []
    m, k, n = 512, 2048, 512
    a = jax.random.normal(jax.random.PRNGKey(0), (m, k))
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n))

    for prec in ("mx4", "mx6", "mx9"):
        qfn = jax.jit(lambda x, p=prec: ref.mx_quant_dequant_ref(x, p))
        us = _time(qfn, a)
        mb = MANTISSA_BITS[prec]
        bits = mb + 1 + 16 / 16 + 8 / 16  # mantissa+sign+mx+shared/16
        rows.append((f"kernels/mx_quantize_{prec}", us,
                     f"bits_per_val={bits:.2f} compression={32/bits:.1f}x"))

    for prec in ("mx6", "mx9"):
        mfn = jax.jit(lambda a, b, p=prec: ref.mx_matmul_fp_ref(a, b, p, p))
        us = _time(mfn, a, b)
        flops = 2 * m * k * n
        # TPU-side: int8 mantissa traffic vs fp32
        bytes_mx = (m * k + k * n) * (MANTISSA_BITS[prec] + 1) / 8 + m * n * 4
        t_c = flops / TPU_PEAK_FLOPS
        t_m = bytes_mx / TPU_HBM_BW
        rows.append((f"kernels/mx_matmul_{prec}", us,
                     f"tpu_roofline_us={max(t_c, t_m)*1e6:.2f} "
                     f"bound={'compute' if t_c > t_m else 'memory'}"))

    # Fused quantize->matmul (PR 7) vs the 3-jit unfused chain, at the
    # repo's hot-path (small-M) GEMM sizes where the per-program dispatch
    # overhead the fusion removes is a real fraction of the GEMM.
    for fm, fk, fn in [(16, 432, 64), (32, 128, 64), (64, 256, 128)]:
        fa_ = jax.random.normal(jax.random.PRNGKey(4), (fm, fk))
        fb_ = jax.random.normal(jax.random.PRNGKey(5), (fk, fn))
        qfn = jax.jit(lambda x: ref.mx_quantize_ref(x, "mx6"))
        mmr = jax.jit(ref.mx_matmul_ref)
        ffn = jax.jit(
            lambda a, b: ref.mx_matmul_fused_ref(a, b, "mx6", "mx6"))

        def unfused_chain(a=fa_, b=fb_):
            return mmr(qfn(a), qfn(b.T))  # 3 programs, MX tensors between

        us_u = _time(unfused_chain, reps=20)
        us_f = _time(ffn, fa_, fb_, reps=20)
        rows.append((f"kernels/mx_fused_{fm}x{fk}x{fn}", us_f,
                     f"unfused_3jit_us={us_u:.1f} "
                     f"wall_speedup={us_u / us_f:.2f}x"))

    q = jax.random.normal(jax.random.PRNGKey(2), (1, 1024, 8, 64))
    kk = jax.random.normal(jax.random.PRNGKey(3), (1, 1024, 2, 64))
    from repro.models.attention import flash_attention as fa

    for window in (None, 256):
        ffn = jax.jit(lambda q, k, v, w=window: fa(q, k, v, causal=True,
                                                   window=w))
        us = _time(ffn, q, kk, kk)
        rows.append((f"kernels/flash_attn_w{window}", us,
                     "chunked-online-softmax (jnp ref path)"))
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    emit(run())
