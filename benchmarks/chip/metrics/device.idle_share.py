"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / window, averaged over
the chips in use. From the profiler trace."""

import trace_reduce


def read(ctx):
    tr = ctx["trace"]
    win = trace_reduce.window(tr) if tr else None
    chips = trace_reduce.chip_ops(tr, ctx["chips"]) if tr else []
    if win is None or not any(chips):
        return None
    lo, hi = win
    busy = sum(trace_reduce.busy_ns(iv, lo, hi) for iv in chips) / len(chips)
    return 1.0 - busy / (hi - lo)
