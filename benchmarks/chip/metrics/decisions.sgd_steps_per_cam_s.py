"""SGD steps the decisions asked for in the window, per camera-second."""


def read(ctx):
    if ctx["camera_s"] <= 0:
        return None
    return ctx["sgd_steps"] / ctx["camera_s"]
