"""Table III: parameter counts and GFLOPs of the six evaluated models.

Validates our implementations against the paper's reported numbers
(paper GFLOPs are MACs; ours count 2*MACs, so we compare flops/2).
"""
from __future__ import annotations

import time

import jax

from repro.configs.dacapo_pairs import TABLE_III, VISION_MODELS
from repro.models.registry import make_vision_model


def run():
    rows = []
    for name, cfg in VISION_MODELS.items():
        m = make_vision_model(cfg)
        t0 = time.time()
        params = m.init(jax.random.PRNGKey(0))
        us = (time.time() - t0) * 1e6
        n = m.param_count(params)
        gmacs = m.flops() / 2 / 1e9
        ref_n, ref_g = TABLE_III[name]
        derived = (f"params={n/1e6:.1f}M(paper {ref_n/1e6:.1f}M) "
                   f"gmacs={gmacs:.2f}(paper {ref_g:.2f}) "
                   f"param_err={abs(n-ref_n)/ref_n*100:.1f}%")
        rows.append((f"table3/{name}", us, derived))
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    emit(run())
