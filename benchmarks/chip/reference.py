"""Plain reference of what the timed path computes: ResNet / WideResNet and
ViT forward passes, MX fake quantization of serving weights, and the SGD
step with momentum, in straightforward ``jax.numpy``.

It imports nothing of the program under test. A model is described by the
plain dict of sizes kept in ``configs/<config>.json`` (the fields of a
student or teacher entry there). Parameter trees use the program's layout
(nested dicts and lists with the same keys and shapes) so that the weights
the benchmark makes from the seed can be handed to both sides.

Departures from the published architectures are the program's own, and the
reference follows them, since it checks the program's arithmetic: GroupNorm
(8 groups) in place of BatchNorm in the ResNets, and the tanh form of GELU
in the ViTs.

``precision`` selects the arithmetic: ``"highest"`` is float32 with every
matrix product at full float32 precision (the reference); ``"bfloat16"``
runs weights, activations and the optimizer state in bfloat16 (the control,
the nearest precision below the float32 the configuration states).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# ResNet stage layout: depth -> (blocks per stage, block kind).
RESNET_STAGES = {
    18: ((2, 2, 2, 2), "basic"),
    34: ((3, 4, 6, 3), "basic"),
    50: ((3, 4, 6, 3), "bottleneck"),
    101: ((3, 4, 23, 3), "bottleneck"),
}

# MX block floating point: blocks of 16 along the last axis share the
# largest exponent; pairs whose exponents are both below it shift their
# scale down by one (micro-exponent); sign-magnitude mantissas.
MX_BLOCK = 16
MX_PAIR = 2
MX_MANTISSA_BITS = {"mx4": 2, "mx6": 4, "mx9": 7}
MX_MIN_SIZE = 1024  # leaves smaller than this (and 1-D leaves) stay fp32
EXP_ZERO = -126

CONTROL_DTYPE = jnp.bfloat16


# --------------------------------------------------------------- layout
def block_plan(cfg: Dict[str, Any]) -> List[Tuple[str, int, int, int, int]]:
    """[(kind, cin, mid, cout, stride), ...] of a ResNet config."""
    stages, kind = RESNET_STAGES[cfg["depth"]]
    plan, cin = [], cfg["base"]
    for stage, n_blocks in enumerate(stages):
        width = cfg["base"] * 2 ** stage
        mid = width * cfg["width_mult"]
        cout = width * 4 if kind == "bottleneck" else mid
        for b in range(n_blocks):
            plan.append((kind, cin, mid, cout,
                         2 if (b == 0 and stage > 0) else 1))
            cin = cout
    return plan


def _stem_size(cfg) -> int:
    return 7 if cfg["img_size"] > 64 else 3


def param_shapes(cfg: Dict[str, Any]):
    """The parameter tree of ``cfg`` with shapes (tuples) as leaves."""
    if cfg["kind"] == "resnet":
        k = _stem_size(cfg)
        gn = lambda c: {"scale": (c,), "bias": (c,)}  # noqa: E731
        tree = {"stem": (k, k, 3, cfg["base"]), "stem_gn": gn(cfg["base"])}
        blocks = []
        for kind, cin, mid, cout, stride in block_plan(cfg):
            if kind == "basic":
                b = {"conv1": (3, 3, cin, mid), "gn1": gn(mid),
                     "conv2": (3, 3, mid, cout), "gn2": gn(cout)}
            else:
                b = {"conv1": (1, 1, cin, mid), "gn1": gn(mid),
                     "conv2": (3, 3, mid, mid), "gn2": gn(mid),
                     "conv3": (1, 1, mid, cout), "gn3": gn(cout)}
            if stride != 1 or cin != cout:
                b["proj"] = (1, 1, cin, cout)
                b["proj_gn"] = gn(cout)
            blocks.append(b)
        tree["blocks"] = blocks
        cfinal = block_plan(cfg)[-1][3]
        tree["head_w"] = (cfinal, cfg["num_classes"])
        tree["head_b"] = (cfg["num_classes"],)
        return tree
    d, f = cfg["d_model"], cfg["d_ff"]
    n_tok = (cfg["img_size"] // cfg["patch"]) ** 2 + 1
    dense = lambda i, o: {"w": (i, o), "b": (o,)}  # noqa: E731
    ln = {"scale": (d,), "bias": (d,)}
    return {
        "patch": dense(cfg["patch"] ** 2 * 3, d),
        "cls": (1, 1, d),
        "pos": (1, n_tok, d),
        "final_ln": dict(ln),
        "head": dense(d, cfg["num_classes"]),
        "blocks": [{"ln1": dict(ln), "qkv": dense(d, 3 * d),
                    "proj": dense(d, d), "ln2": dict(ln),
                    "fc1": dense(d, f), "fc2": dense(f, d)}
                   for _ in range(cfg["num_layers"])],
    }


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def param_count(cfg) -> int:
    leaves = jax.tree_util.tree_leaves(param_shapes(cfg), is_leaf=_is_shape)
    return int(sum(np.prod(s) for s in leaves))


def init_params(cfg: Dict[str, Any], key: jax.Array):
    """Seeded weights in the program's layout, float32: fan-in scaled
    normals for weights, ones for norm scales, zeros for biases, small
    normals for the ViT's class token and position table. Jit it."""
    shapes = param_shapes(cfg)
    paths = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_shape)[0]
    keys = jax.random.split(key, len(paths))
    leaves = []
    for k, (path, shape) in zip(keys, paths):
        name = str(getattr(path[-1], "key", ""))
        if name in ("scale",):
            leaves.append(jnp.ones(shape, jnp.float32))
        elif name in ("bias", "b", "head_b"):
            leaves.append(jnp.zeros(shape, jnp.float32))
        elif name in ("cls", "pos"):
            leaves.append(0.02 * jax.random.normal(k, shape, jnp.float32))
        else:
            fan_in = int(np.prod(shape[:-1]))
            leaves.append(jax.random.normal(k, shape, jnp.float32)
                          * fan_in ** -0.5)
    treedef = jax.tree_util.tree_structure(shapes, is_leaf=_is_shape)
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ------------------------------------------------------------- forwards
def _conv(x, w, stride=1):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _groupnorm(x, p, groups=8):
    c = x.shape[-1]
    g = min(groups, c)
    while c % g:
        g -= 1
    xg = x.reshape(x.shape[:-1] + (g, c // g))
    mean = xg.mean(axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) * jax.lax.rsqrt(var + 1e-5)
    return (xg.reshape(x.shape) * p["scale"].astype(x.dtype)
            + p["bias"].astype(x.dtype))


def resnet_logits(cfg, params, images):
    big = images.shape[1] > 64
    x = _conv(images, params["stem"], stride=2 if big else 1)
    x = jax.nn.relu(_groupnorm(x, params["stem_gn"]))
    if big:  # 3x3 max pool, stride 2
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                  (1, 2, 2, 1), "SAME")
    for bp, (kind, cin, mid, cout, stride) in zip(params["blocks"],
                                                  block_plan(cfg)):
        if kind == "basic":
            y = jax.nn.relu(_groupnorm(_conv(x, bp["conv1"], stride),
                                       bp["gn1"]))
            y = _groupnorm(_conv(y, bp["conv2"]), bp["gn2"])
        else:
            y = jax.nn.relu(_groupnorm(_conv(x, bp["conv1"]), bp["gn1"]))
            y = jax.nn.relu(_groupnorm(_conv(y, bp["conv2"], stride),
                                       bp["gn2"]))
            y = _groupnorm(_conv(y, bp["conv3"]), bp["gn3"])
        short = (_groupnorm(_conv(x, bp["proj"], stride), bp["proj_gn"])
                 if "proj" in bp else x)
        x = jax.nn.relu(short + y)
    x = x.mean(axis=(1, 2))
    return x @ params["head_w"].astype(x.dtype) + params["head_b"].astype(
        x.dtype)


def _layernorm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + 1e-6) * p["scale"].astype(
        x.dtype) + p["bias"].astype(x.dtype))


def _linear(x, p):
    return x @ p["w"].astype(x.dtype) + p["b"].astype(x.dtype)


def _gelu_tanh(x):
    return 0.5 * x * (1 + jnp.tanh(np.sqrt(2 / np.pi).astype(np.float32)
                                   * (x + 0.044715 * x ** 3)))


def vit_logits(cfg, params, images):
    b, h, w, _ = images.shape
    p, d, nh = cfg["patch"], cfg["d_model"], cfg["num_heads"]
    dh = d // nh
    x = images.reshape(b, h // p, p, w // p, p, 3).transpose(0, 1, 3, 2, 4, 5)
    x = _linear(x.reshape(b, (h // p) * (w // p), p * p * 3), params["patch"])
    cls = jnp.broadcast_to(params["cls"].astype(x.dtype), (b, 1, d))
    x = jnp.concatenate([cls, x], axis=1)
    x = x + params["pos"][:, : x.shape[1]].astype(x.dtype)
    for bp in params["blocks"]:
        y = _layernorm(x, bp["ln1"])
        qkv = _linear(y, bp["qkv"]).reshape(b, -1, 3, nh, dh)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        att = jax.nn.softmax(
            jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.float32(dh) ** 0.5,
            axis=-1)
        y = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, -1, d)
        x = x + _linear(y, bp["proj"])
        y = _layernorm(x, bp["ln2"])
        x = x + _linear(_gelu_tanh(_linear(y, bp["fc1"])), bp["fc2"])
    x = _layernorm(x, params["final_ln"])
    return _linear(x[:, 0], params["head"])


def logits(cfg, params, images):
    fwd = resnet_logits if cfg["kind"] == "resnet" else vit_logits
    return fwd(cfg, params, images)


# ------------------------------------------------------------------- MX
def _mx_fake_quant_2d(x, precision: str):
    """Round each row of x [R, K] (K a multiple of 16) to MX and back."""
    bits = MX_MANTISSA_BITS[precision]
    r, k = x.shape
    blocks = x.reshape(r, k // MX_BLOCK, MX_BLOCK // MX_PAIR, MX_PAIR)
    raw = jax.lax.bitcast_convert_type(blocks, jnp.uint32)
    exp = ((raw >> 23) & 0xFF).astype(jnp.int32) - 127
    exp = jnp.where(blocks == 0.0, EXP_ZERO, exp)
    shared = exp.max(axis=(2, 3), keepdims=True)
    pair = exp.max(axis=3, keepdims=True)
    eff = (shared - (pair < shared).astype(jnp.int32)).astype(jnp.float32)
    mant = jnp.clip(jnp.round(jnp.abs(blocks) * jnp.exp2((bits - 1) - eff)),
                    0, 2 ** bits - 1)
    mant = jnp.where(blocks == 0.0, 0.0, mant)  # 0 * inf scale is no NaN
    return (jnp.sign(blocks) * mant
            * jnp.exp2(eff - (bits - 1))).reshape(r, k)


@functools.partial(jax.jit, static_argnames=("precision",))
def _mx_leaf(w, precision: str):
    flat = w.astype(jnp.float32).reshape(-1, w.shape[-1])
    pad = (-flat.shape[-1]) % MX_BLOCK
    q = _mx_fake_quant_2d(jnp.pad(flat, ((0, 0), (0, pad))), precision)
    return q[:, : w.shape[-1]].reshape(w.shape).astype(w.dtype)


def mx_fake_quant(params, precision: str):
    """The serving copy: every >=2-D weight of at least MX_MIN_SIZE values
    rounded to MX along its last axis; the rest unchanged."""
    def q(w):
        if w.ndim < 2 or w.size < MX_MIN_SIZE:
            return w
        return _mx_leaf(w, precision)

    return jax.tree_util.tree_map(q, params)


# ------------------------------------------------------------------ SGD
def _cross_entropy(z, y):
    logp = jax.nn.log_softmax(z.astype(jnp.float32))
    return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()


def sgd_step(cfg, lr, params, mom, x, y):
    """One step of SGD with momentum 0.9 on the mean cross-entropy:
    m <- 0.9 m + g ; p <- p - lr m. Returns (params, mom, loss, grads)."""
    loss, grads = jax.value_and_grad(
        lambda p: _cross_entropy(logits(cfg, p, x), y))(params)
    mom = jax.tree_util.tree_map(lambda m, g: 0.9 * m + g, mom, grads)
    params = jax.tree_util.tree_map(lambda p, m: (p - lr * m).astype(p.dtype),
                                    params, mom)
    return params, mom, loss, grads


def precision_scope(precision: str):
    """Matrix-product precision for a reference or control computation."""
    if precision == "highest":
        return jax.default_matmul_precision("highest")
    return contextlib.nullcontext()


def cast(tree, precision: str):
    dtype = CONTROL_DTYPE if precision == "bfloat16" else jnp.float32
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a).astype(dtype),
                                  tree)


class Reference:
    """Jitted forward and SGD step of one model at one precision."""

    def __init__(self, cfg: Dict[str, Any], precision: str = "highest"):
        self.cfg = dict(cfg)
        self.precision = precision
        frozen = tuple(sorted(self.cfg.items()))
        self._logits = jax.jit(functools.partial(_logits_frozen, frozen))
        self._step = jax.jit(functools.partial(_step_frozen, frozen))

    def forward(self, params, x, block: int = 32) -> np.ndarray:
        """Logits as float32 host arrays, in blocks of ``block`` rows (the
        last one padded, so that one program serves every call)."""
        params = cast(params, self.precision)
        out = []
        with precision_scope(self.precision):
            for i in range(0, len(x), block):
                xb = np.asarray(x[i: i + block])
                n = len(xb)
                if n < block:
                    xb = np.concatenate(
                        [xb, np.zeros((block - n,) + xb.shape[1:], xb.dtype)])
                z = self._logits(params, cast(jnp.asarray(xb), self.precision))
                out.append(np.asarray(z, np.float32)[:n])
        return np.concatenate(out) if out else np.zeros((0, 0), np.float32)

    def step(self, lr, params, mom, x, y):
        with precision_scope(self.precision):
            return self._step(jnp.float32(lr), cast(params, self.precision),
                              cast(mom, self.precision),
                              cast(jnp.asarray(x), self.precision),
                              jnp.asarray(y, jnp.int32))


def _logits_frozen(frozen, params, x):
    return logits(dict(frozen), params, x)


def _step_frozen(frozen, lr, params, mom, x, y):
    return sgd_step(dict(frozen), lr, params, mom, x, y)
