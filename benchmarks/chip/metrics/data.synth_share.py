"""Seconds in ``dacapo.data.synthesize`` on every thread over lanes x the
traced window. Read by ``program_spans.METRICS``."""

import program_spans


def read(ctx):
    return program_spans.METRICS["data.synth_share"][0](ctx)
