"""Operations and bytes of the programs the window runs, from their shapes.

``gemms`` lists the (M, N, K) matrix products of one forward pass (a
convolution counted as its im2col product), after the program's own
estimator (``vision_gemms``), kept here so that no later change to the
program moves the yardstick. A product costs 2*M*N*K operations; the
counts land within a few percent of the paper's Table III for all six
models (checked in tests/test_chipbench_yardstick.py).
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import reference

F32 = 4  # bytes of a float32 value


def gemms(cfg: Dict[str, Any], batch: int = 1) -> List[Tuple[int, int, int]]:
    """(M, N, K) of every matrix product of one forward pass."""
    out: List[Tuple[int, int, int]] = []
    if cfg["kind"] == "vit":
        n = (cfg["img_size"] // cfg["patch"]) ** 2 + 1
        d, f = cfg["d_model"], cfg["d_ff"]
        out.append((batch * n, d, cfg["patch"] ** 2 * 3))
        for _ in range(cfg["num_layers"]):
            out += [(batch * n, 3 * d, d),  # qkv
                    (batch * n, n, d),      # q k^T, heads folded into K
                    (batch * n, d, n),      # attention @ v
                    (batch * n, d, d),      # output projection
                    (batch * n, f, d),      # fc1
                    (batch * n, d, f)]      # fc2
        out.append((batch, cfg["num_classes"], d))
        return out
    big = cfg["img_size"] > 64
    h = cfg["img_size"] // (2 if big else 1)
    k = 7 if big else 3
    out.append((batch * h * h, cfg["base"], k * k * 3))
    if big:
        h //= 2
    for kind, cin, mid, cout, stride in reference.block_plan(cfg):
        h2 = h // stride
        if kind == "basic":
            out += [(batch * h2 * h2, mid, 9 * cin),
                    (batch * h2 * h2, cout, 9 * mid)]
        else:
            out += [(batch * h * h, mid, cin),
                    (batch * h2 * h2, mid, 9 * mid),
                    (batch * h2 * h2, cout, mid)]
        if stride != 1 or cin != cout:
            out.append((batch * h2 * h2, cout, cin))
        h = h2
    out.append((batch, cfg["num_classes"], reference.block_plan(cfg)[-1][3]))
    return out


def forward_flops(cfg: Dict[str, Any]) -> float:
    """Operations of one forward pass of one frame."""
    return 2.0 * sum(m * n * k for m, n, k in gemms(cfg))


def sgd_flops(cfg: Dict[str, Any], batch: int) -> float:
    """One SGD step: the forward and the two gradient products (dX, dW)
    of every forward product."""
    return 3.0 * batch * forward_flops(cfg)


def frame_bytes(cfg: Dict[str, Any]) -> int:
    return cfg["img_size"] ** 2 * 3 * F32


def weight_bytes(cfg: Dict[str, Any]) -> int:
    return reference.param_count(cfg) * F32


def forward_bytes(cfg: Dict[str, Any], frames: int, calls: int) -> float:
    """Least traffic of ``calls`` forward programs over ``frames`` frames:
    each call reads the weights once, each frame is read once."""
    return float(calls * weight_bytes(cfg) + frames * frame_bytes(cfg))


def sgd_bytes(cfg: Dict[str, Any], batch: int) -> float:
    """Least traffic of one SGD step: read the weights and the momentum,
    write both back, read the batch."""
    return float(4 * weight_bytes(cfg) + batch * frame_bytes(cfg))
