"""Model operations of the window's work over (window seconds x the chip's
peak), in percent: student forwards for every frame served, teacher
forwards for every frame labeled, three student forwards per SGD sample."""

import flops
import peaks


def read(ctx):
    work = (ctx["rows_served"] * flops.forward_flops(ctx["student"])
            + ctx["rows_labeled"] * flops.forward_flops(ctx["teacher"])
            + ctx["sgd_steps"] * flops.sgd_flops(ctx["student"],
                                                 ctx["sgd_batch"]))
    if work <= 0 or ctx["window_s"] <= 0:
        return None
    peak = peaks.peak(ctx["device_kind"])["flops_per_s"]
    return 100.0 * work / (ctx["window_s"] * ctx["chips"] * peak)
