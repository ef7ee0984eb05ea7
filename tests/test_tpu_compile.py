"""Compile rehearsals for one TPU v5e chip, with no chip attached.

The TPU compiler is installed next to the CPU backend, so the session's own
programs can be compiled at published widths for a chip that is described
(``v5e:2x2``, one chip of it) and not attached. What the chip's compiler
refuses — a tiling, a shape cast, a program that does not fit HBM — fails
here instead of on the chip. Nothing runs: these tests say nothing about
results or times.

The topology is described only inside a module-scoped fixture. Describing it
loads the TPU library, which one process at a time may hold; at import time
every pytest worker would try, and all but one would fail.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.dacapo_pairs import RESNET18, WIDERESNET50
from repro.core.allocation import CLHyperParams
from repro.core.estimator import DaCapoEstimator
from repro.core.kernel import InferenceKernel, LabelingKernel, RetrainKernel
from repro.kernels import mx_fused, mx_quantize
from repro.models.registry import make_vision_model

HBM_BYTES = 16e9  # TPU v5e: 16 GB of HBM per chip
LABEL_MICROBATCH = 64  # CLSession's concurrent-dispatch label microbatch


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _params(model, sharding):
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return jax.tree_util.tree_map(
        lambda s: _spec(s.shape, s.dtype, sharding), shapes)


def _frames(cfg, batch, sharding):
    return _spec((batch, cfg.img_size, cfg.img_size, 3), jnp.float32,
                 sharding)


def _assert_fits(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used <= HBM_BYTES, used


# ------------------------------------------------- the session's programs --
def test_resnet18_serving_forward_compiles(one_chip):
    model = make_vision_model(RESNET18)
    kernel = InferenceKernel(model, RESNET18, DaCapoEstimator(), True)
    compiled = kernel._apply.lower(
        _params(model, one_chip), _frames(RESNET18, 16, one_chip)).compile()
    _assert_fits(compiled)


def test_wideresnet50_labeling_forward_compiles(one_chip):
    model = make_vision_model(WIDERESNET50)
    kernel = LabelingKernel(model, WIDERESNET50, DaCapoEstimator(), True)
    compiled = kernel._apply.lower(
        _params(model, one_chip),
        _frames(WIDERESNET50, LABEL_MICROBATCH, one_chip)).compile()
    _assert_fits(compiled)


def test_resnet18_sgd_step_compiles(one_chip):
    hp = CLHyperParams()
    model = make_vision_model(RESNET18)
    kernel = RetrainKernel(model, RESNET18, DaCapoEstimator(), hp)
    params = _params(model, one_chip)
    compiled = kernel._step.lower(
        params, params, _frames(RESNET18, hp.sgd_batch, one_chip),
        _spec((hp.sgd_batch,), jnp.int32, one_chip)).compile()
    _assert_fits(compiled)


# ------------------------------------- MX Pallas kernels (not yet on path) --
# At a ViT-B width (768 in, 3072 out), with the tiles kernels/ops.py picks.
# Each test expects one named refusal: any other error fails it, and a
# compile that passes fails it too (strict), so the PR that repairs a
# kernel turns its test green.
M, K, N = 128, 768, 3072
SHAPE_CAST = "unsupported shape cast"
SHAPE_CAST_REASON = ("Mosaic refuses the per-16-block reshape "
                     "(kernels/mx_fused.py::_quant_dequant_lhs): "
                     + SHAPE_CAST)


class Refused(Exception):
    """The chip's compiler refused the kernel for the expected reason."""


def _compile_expecting(lower, refusal):
    try:
        lower().compile()
    except Exception as e:
        if refusal in str(e):
            raise Refused(refusal) from e
        raise


@pytest.mark.xfail(strict=True, raises=Refused, reason=(
    "the Pallas TPU lowering refuses mx_quantize's exponent and "
    "micro-exponent output blocks (bm, bk // 16) "
    "(kernels/mx_quantize.py:76-77): the last block dimension is not "
    "divisible by 128"))
def test_mx_quantize_compiles(one_chip):
    x = _spec((M, K), jnp.float32, one_chip)
    _compile_expecting(
        lambda: mx_quantize.mx_quantize.lower(x, "mx6", bm=128, bk=256,
                                              interpret=False),
        "divisible by 8 and 128")


@pytest.mark.xfail(strict=True, raises=Refused, reason=SHAPE_CAST_REASON)
def test_mx_matmul_fused_compiles(one_chip):
    a = _spec((M, K), jnp.float32, one_chip)
    b = _spec((K, N), jnp.float32, one_chip)
    _compile_expecting(
        lambda: mx_fused.mx_matmul_fused.lower(
            a, b, "mx6", "mx6", bm=128, bn=128, bk=256, interpret=False),
        SHAPE_CAST)


@pytest.mark.xfail(strict=True, raises=Refused, reason=SHAPE_CAST_REASON)
def test_mx_matmul_bwd_pair_compiles(one_chip):
    g = _spec((M, N), jnp.float32, one_chip)
    wt = _spec((N, K), jnp.float32, one_chip)
    xt = _spec((K, M), jnp.float32, one_chip)
    _compile_expecting(
        lambda: mx_fused.mx_matmul_bwd_pair.lower(
            g, wt, xt, g, "mx9", bm1=128, bn1=128, bk1=512, bm2=128,
            bn2=128, bk2=128, interpret=False),
        SHAPE_CAST)


@pytest.mark.xfail(strict=True, raises=Refused, reason=SHAPE_CAST_REASON)
def test_mx_matmul_prequant_compiles(one_chip):
    a = _spec((M, K), jnp.float32, one_chip)
    rm = _spec((K, N), jnp.int8, one_chip)
    re = _spec((K // 16, N), jnp.int8, one_chip)
    rx = _spec((K // 16, N), jnp.uint8, one_chip)
    _compile_expecting(
        lambda: mx_fused.mx_matmul_prequant.lower(
            a, rm, re, rx, "mx6", 4, bm=128, bn=128, bk=256,
            interpret=False),
        SHAPE_CAST)
