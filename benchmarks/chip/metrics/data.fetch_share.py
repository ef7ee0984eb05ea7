"""Share of the window the engine thread spent in the data plane's
``frames()`` (synthesis inline, or waiting for a prefetched window)."""


def read(ctx):
    if ctx["window_s"] <= 0:
        return None
    return ctx["fetch_s"] / ctx["window_s"]
