"""Kernel programs issued in the window (serving, labeling and SGD,
counted by each kernel's ``n_apply_calls``) per camera-second."""


def read(ctx):
    if ctx["camera_s"] <= 0:
        return None
    return sum(ctx["calls"].values()) / ctx["camera_s"]
