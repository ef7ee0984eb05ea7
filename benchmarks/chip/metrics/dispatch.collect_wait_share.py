"""Engine-line seconds in ``dacapo.collect`` (materializing results on the
host) over the traced window. Read by ``program_spans.METRICS``."""

import program_spans


def read(ctx):
    return program_spans.METRICS["dispatch.collect_wait_share"][0](ctx)
