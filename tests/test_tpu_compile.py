"""The fused FNO block compiled for a described TPU v5e, no chip attached.

Interpret mode (every other kernel test) runs the Pallas kernels as plain
XLA ops on the CPU, so it never sees Mosaic's tiling rule, its matmul
forms, or its VMEM limit. These tests hand the TPU compiler the
full-width block forward and its gradient with ``interpret=False`` and
assert that a Mosaic kernel (``tpu_custom_call``) comes out for every
launch: one for the forward, four under ``jax.value_and_grad``
(block_fwd, gz_recompute, dx_adjoint, wgrad).

fno3d is not here: Mosaic still refuses it (ROADMAP S2).

The topology is described inside a module-scoped fixture, never at import
time: only one process may hold the TPU library, and pytest-xdist workers
all import every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.configs.base import PrecisionPolicy
from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-chip executable is written to the persistent cache but
    # can never be read back without a chip: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _block_shapes(cfg, pol, sharding, batch=8):
    """Shapes of one full-width block's operands, placed on the chip."""
    h = cfg.hidden
    w = (h, h) + (tuple(cfg.modes) if cfg.weight_mode == "per_mode" else ())
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                                sharding=sharding)
    return (sd((batch, h) + tuple(cfg.spatial), pol.compute_dtype),
            sd(w, pol.param_dtype), sd(w, pol.param_dtype),
            sd((h, h), pol.param_dtype), sd((h,), pol.param_dtype))


def _compiled_text(fn, args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("arch", ["fno1d", "fno2d", "fno2d-large"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_fused_block_compiles_for_v5e(one_chip, arch, dtype, grad):
    cfg = get_config(arch)
    pol = PrecisionPolicy.from_name(dtype)
    args = _block_shapes(cfg, pol, one_chip)

    def block(x, wr, wi, wb, bias):
        return ops.fno_block_nd(x, wr, wi, wb, bias, cfg.modes,
                                path="pallas", variant="full",
                                policy=pol, interpret=False)

    if grad:
        def fn(x, *params):
            def loss(x, *params):
                return jnp.sum(block(x, *params).astype(jnp.float32))
            return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(
                x, *params)
    else:
        fn = block
    text = _compiled_text(fn, args)
    assert text.count("tpu_custom_call") == (4 if grad else 1), arch


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("arch", ["fno2d", "fno2d-large"])
def test_partial_block_compiles_for_v5e(one_chip, arch, dtype):
    """The paper-faithful partial fusion: the outer rDFT, the fused core
    (cDFT → CGEMM → icDFT) and the outer irDFT — three kernels."""
    cfg = get_config(arch)
    pol = PrecisionPolicy.from_name(dtype)

    def block(x, wr, wi, wb, bias):
        return ops.fno_block_nd(x, wr, wi, wb, bias, cfg.modes,
                                path="pallas", variant="partial",
                                policy=pol, interpret=False)

    text = _compiled_text(block, _block_shapes(cfg, pol, one_chip))
    assert text.count("tpu_custom_call") == 3, arch
