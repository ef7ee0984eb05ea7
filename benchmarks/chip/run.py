"""Chip benchmark of the continuous-learning loop: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

from the root of a checkout, on a machine with the chips the cell asks for
(cells and their files: ``BENCHMARK.json`` and ``harness.py``). It fails,
printing no result, where JAX finds no TPU or too few chips. The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or its per-layer
metrics with ``--trace 1``), ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``: each number the check compared, beside its limit. The
same numbers end standard error.
"""
import time

T_START = time.perf_counter()  # set-up is timed from the process's start

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "..", "..", "src")]


def finite(x: float):
    """A reading as JSON can hold it: ``None`` where nothing was compared."""
    return x if math.isfinite(x) else None


def fail(msg: str) -> None:
    print(f"chip benchmark: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(2)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    import harness

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        fail(f"JAX's devices are {dev.platform!r}, not TPUs")
    if len(devices) < cell.chips:
        fail(f"{args.workload} needs {cell.chips} chips, found {len(devices)}")
    print(f"compile cache: {harness.env_setup()}", flush=True)

    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START, log=lambda s: print(s, flush=True),
                           device=dev)
    ctx = out["ctx"]
    metrics = harness.metrics_of(cell, ctx, bool(args.trace))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": out["memory_peak"]}
    result = {"correct": bool(out["correct"]), "attempted": out["attempted"],
              "failed": 0, "metrics": metrics, "device": device}
    if args.trace:
        summary = harness.trace_summary(ctx)
        if summary is None:
            fail("the trace holds no device operation in the window")
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    checks = {k: {"value": finite(out["readings"][k]), "limit": v}
              for k, v in out["limits"].items()}
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
