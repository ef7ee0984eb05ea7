"""Benchmark runner — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Set REPRO_BENCH_FAST=1 for a quick
pass; SKIP_SLOW=1 skips the end-to-end CL accuracy benches.
"""
from __future__ import annotations

import os
import sys
import time
import traceback


def main() -> None:
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    from benchmarks import (
        bench_dispatch,
        bench_fig3_flops,
        bench_fig9_accuracy,
        bench_fig11_temporal,
        bench_fig12_extreme,
        bench_fleet,
        bench_kernels,
        bench_manager,
        bench_reallocation,
        bench_replay,
        bench_table3_models,
    )
    from benchmarks.common import emit

    modules = [
        ("table3", bench_table3_models),
        ("fig3", bench_fig3_flops),
        ("kernels", bench_kernels),
    ]
    if not int(os.environ.get("SKIP_SLOW", "0")):
        modules += [
            ("fig9", bench_fig9_accuracy),
            ("fig11", bench_fig11_temporal),
            ("fig12", bench_fig12_extreme),
            # System benches (smoke sizes when run via the registry; the
            # standalone scripts expose the full sweeps + JSON artifacts).
            ("dispatch", bench_dispatch),
            ("reallocation", bench_reallocation),
            ("replay", bench_replay),
            ("fleet", bench_fleet),
            ("manager", bench_manager),
        ]
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in modules:
        t0 = time.time()
        try:
            emit(mod.run())
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
            print(f"# {name} FAILED", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
