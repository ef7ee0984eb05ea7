"""Device time of the SGD step program (``_sgd_step``) in the trace,
against the least time its operations or its bytes need at the chip's
peaks (compute bounds it at these sizes), in percent."""

import flops
import peaks
import trace_reduce


def read(ctx):
    tr = ctx["trace"]
    win = trace_reduce.window(tr) if tr else None
    if win is None:
        return None
    runs = ns = 0
    for mods in tr.modules.values():
        r, t = trace_reduce.program_times(mods, *win).get("_sgd_step", (0, 0))
        runs, ns = runs + r, ns + t
    if not runs or ns <= 0:
        return None
    peak = peaks.peak(ctx["device_kind"])
    least = max(
        runs * flops.sgd_flops(ctx["student"], ctx["sgd_batch"])
        / peak["flops_per_s"],
        runs * flops.sgd_bytes(ctx["student"], ctx["sgd_batch"])
        / peak["bytes_per_s"])
    return 100.0 * least / (ns / 1e9)
