"""Share of the device's idle time in the traced window in which the innermost
engine-line program span is ``dacapo.phase`` itself, or none. Read by
``program_spans.METRICS``."""

import program_spans


def read(ctx):
    return program_spans.METRICS["device.idle_unattributed_share"][0](ctx)
