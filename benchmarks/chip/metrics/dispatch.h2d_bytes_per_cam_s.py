"""Bytes of host arrays the kernels handed to the device in the window (their
``h2d_bytes``), per camera-second. Read by ``program_spans.METRICS``."""

import program_spans


def read(ctx):
    return program_spans.METRICS["dispatch.h2d_bytes_per_cam_s"][0](ctx)
