"""ResNet / WideResNet (paper Table III students & teachers) in pure JAX.

GroupNorm replaces BatchNorm (no mutable running stats in the functional CL
loop) — the one departure from the published architectures, at every width.
Params are pure-array pytrees; the static block plan is derived from the
config.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.dacapo_pairs import VisionConfig

_STAGES = {
    18: ((2, 2, 2, 2), "basic"),
    34: ((3, 4, 6, 3), "basic"),
    50: ((3, 4, 6, 3), "bottleneck"),
    101: ((3, 4, 23, 3), "bottleneck"),
}


def block_plan(cfg: VisionConfig) -> List[Tuple[str, int, int, int, int]]:
    """[(kind, cin, mid, cout, stride), ...] — static, derived from config."""
    stages, kind = _STAGES[cfg.depth]
    plan = []
    cin = cfg.base
    for stage, n_blocks in enumerate(stages):
        base = cfg.base * (2 ** stage)
        if kind == "bottleneck":
            mid, cout = base * cfg.width_mult, base * 4
        else:
            mid, cout = base * cfg.width_mult, base * cfg.width_mult
        for b in range(n_blocks):
            stride = 2 if (b == 0 and stage > 0) else 1
            plan.append((kind, cin, mid, cout, stride))
            cin = cout
    return plan


def _conv_def(key, cin, cout, ksize):
    scale = (ksize * ksize * cin) ** -0.5
    return jax.random.normal(key, (ksize, ksize, cin, cout)) * scale


def _gn_def(c):
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}


def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _gn(x, p, groups=8):
    c = x.shape[-1]
    g = min(groups, c)
    while c % g:
        g -= 1
    xg = x.reshape(x.shape[:-1] + (g, c // g))
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = xg.var(axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) * jax.lax.rsqrt(var + 1e-5)
    return xg.reshape(x.shape) * p["scale"] + p["bias"]


def init_resnet(key, cfg: VisionConfig) -> Dict[str, Any]:
    plan = block_plan(cfg)
    keys = iter(jax.random.split(key, 8 + 4 * len(plan)))
    params: Dict[str, Any] = {
        "stem": _conv_def(next(keys), 3, cfg.base,
                          7 if cfg.img_size > 64 else 3),
        "stem_gn": _gn_def(cfg.base),
    }
    blocks: List[Dict[str, Any]] = []
    for kind, cin, mid, cout, stride in plan:
        bp: Dict[str, Any] = {}
        if kind == "basic":
            bp["conv1"] = _conv_def(next(keys), cin, mid, 3)
            bp["gn1"] = _gn_def(mid)
            bp["conv2"] = _conv_def(next(keys), mid, cout, 3)
            bp["gn2"] = _gn_def(cout)
        else:
            bp["conv1"] = _conv_def(next(keys), cin, mid, 1)
            bp["gn1"] = _gn_def(mid)
            bp["conv2"] = _conv_def(next(keys), mid, mid, 3)
            bp["gn2"] = _gn_def(mid)
            bp["conv3"] = _conv_def(next(keys), mid, cout, 1)
            bp["gn3"] = _gn_def(cout)
        if stride != 1 or cin != cout:
            bp["proj"] = _conv_def(next(keys), cin, cout, 1)
            bp["proj_gn"] = _gn_def(cout)
        blocks.append(bp)
    params["blocks"] = blocks
    cfinal = plan[-1][3]
    params["head_w"] = jax.random.normal(
        next(keys), (cfinal, cfg.num_classes)) * cfinal ** -0.5
    params["head_b"] = jnp.zeros((cfg.num_classes,))
    return params


def resnet_forward(params, images, cfg: VisionConfig):
    """images [B,H,W,3] -> logits [B,C]."""
    x = _conv(images, params["stem"], stride=2 if images.shape[1] > 64 else 1)
    x = jax.nn.relu(_gn(x, params["stem_gn"]))
    if images.shape[1] > 64:
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")
    for bp, (kind, cin, mid, cout, stride) in zip(params["blocks"],
                                                  block_plan(cfg)):
        resid = x
        if kind == "basic":
            y = jax.nn.relu(_gn(_conv(x, bp["conv1"], stride), bp["gn1"]))
            y = _gn(_conv(y, bp["conv2"]), bp["gn2"])
        else:
            y = jax.nn.relu(_gn(_conv(x, bp["conv1"]), bp["gn1"]))
            y = jax.nn.relu(_gn(_conv(y, bp["conv2"], stride), bp["gn2"]))
            y = _gn(_conv(y, bp["conv3"]), bp["gn3"])
        if "proj" in bp:
            resid = _gn(_conv(x, bp["proj"], stride), bp["proj_gn"])
        x = jax.nn.relu(resid + y)
    x = x.mean(axis=(1, 2))
    return x @ params["head_w"] + params["head_b"]


def resnet_flops(cfg: VisionConfig) -> float:
    """Forward-pass MACs*2 at cfg.img_size (conv + fc terms)."""
    h = w = cfg.img_size
    total = 0.0
    stem_k = 7 if cfg.img_size > 64 else 3
    stride0 = 2 if cfg.img_size > 64 else 1
    h, w = h // stride0, w // stride0
    total += 2 * stem_k * stem_k * 3 * cfg.base * h * w
    if cfg.img_size > 64:
        h, w = h // 2, w // 2
    for kind, cin, mid, cout, stride in block_plan(cfg):
        h2, w2 = h // stride, w // stride
        if kind == "basic":
            total += 2 * 9 * cin * mid * h2 * w2
            total += 2 * 9 * mid * cout * h2 * w2
        else:
            total += 2 * cin * mid * h * w
            total += 2 * 9 * mid * mid * h2 * w2
            total += 2 * mid * cout * h2 * w2
        if stride != 1 or cin != cout:
            total += 2 * cin * cout * h2 * w2
        h, w = h2, w2
    total += 2 * block_plan(cfg)[-1][3] * cfg.num_classes
    return total


def resnet_param_count(params) -> int:
    return sum(p.size for p in jax.tree_util.tree_leaves(params))
