"""Production meshes.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state. Single pod: 16x16 = 256
chips ("data", "model"); multi-pod: 2x16x16 = 512 chips ("pod", "data",
"model") — the pod axis is pure DP and only gradient all-reduce (optionally
int8-compressed, training/grad.py) crosses the slow inter-pod links.

Mesh axes are ``AxisType.Auto``: the sharding rules place arrays with
``with_sharding_constraint`` (distributed.py), which refuses the
``Explicit`` axes ``jax.make_mesh`` gives by default.
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_parallel: int = 1) -> Mesh:
    """Mesh over whatever devices exist (tests / quickstart)."""
    n = jax.device_count()
    assert n % model_parallel == 0
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


def describe(mesh: Mesh) -> str:
    return f"mesh{dict(zip(mesh.axis_names, mesh.devices.shape))}"
