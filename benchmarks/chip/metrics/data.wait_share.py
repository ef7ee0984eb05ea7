"""Engine-line seconds in ``dacapo.data.wait`` (waiting on a prefetched window)
over the traced window. Read by ``program_spans.METRICS``."""

import program_spans


def read(ctx):
    return program_spans.METRICS["data.wait_share"][0](ctx)
