"""Frames fetched for labeling (tag ``label``) in the window, per
camera-second."""


def read(ctx):
    if ctx["camera_s"] <= 0:
        return None
    return ctx["label_frames"] / ctx["camera_s"]
