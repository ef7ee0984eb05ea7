"""Device time of all forward programs (``apply``: student serving and
teacher labeling share the name) in the trace, against the least time
their operations or bytes need at the chip's peaks, in percent. The rows
and calls are those of the traced part of the window."""

import flops
import peaks
import trace_reduce


def read(ctx):
    tr = ctx["trace"]
    win = trace_reduce.window(tr) if tr else None
    if win is None:
        return None
    ns = sum(trace_reduce.program_times(mods, *win).get("apply", (0, 0))[1]
             for mods in tr.modules.values())
    if ns <= 0:
        return None
    s, t, n = ctx["student"], ctx["teacher"], ctx["traced"]
    peak = peaks.peak(ctx["device_kind"])
    ops = (n["rows_served"] * flops.forward_flops(s)
           + n["rows_labeled"] * flops.forward_flops(t))
    moved = (flops.forward_bytes(s, n["rows_served"],
                                 n["calls"]["inference"])
             + flops.forward_bytes(t, n["rows_labeled"],
                                   n["calls"]["labeling"]))
    least = max(ops / peak["flops_per_s"], moved / peak["bytes_per_s"])
    return 100.0 * least / (ns / 1e9)
