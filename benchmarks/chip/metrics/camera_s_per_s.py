"""Stream-seconds every lane completed in the window's phases, over the
wall seconds from the window's start to its last barrier."""


def read(ctx):
    if ctx["window_s"] <= 0 or ctx["camera_s"] <= 0:
        return None
    return ctx["camera_s"] / ctx["window_s"]
