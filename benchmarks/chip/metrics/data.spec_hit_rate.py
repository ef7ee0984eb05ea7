"""Frame windows served from the speculative prefetch, over all frame
windows, in the window's phases (``PhaseRecord.spec_hits/misses``)."""


def read(ctx):
    total = ctx["spec_hits"] + ctx["spec_misses"]
    if not total:
        return None
    return ctx["spec_hits"] / total
