"""Pallas TPU kernels for the fused FFT→CGEMM→iFFT pipeline.

Every ``pl.pallas_call`` in this package takes its compiler parameters
from :func:`_compiler_params`, so the VMEM limit the kernels compile under
is set in exactly one place, and its ``name`` from :data:`LAUNCH_NAMES`,
so that a device trace tells the launches apart.
"""
from __future__ import annotations

from jax.experimental.pallas import tpu as pltpu

# Physical VMEM of one TPU v5e TensorCore is 128 MiB; Mosaic's default
# scoped limit is only 16 MiB. The kernels compile under 96 MiB: the whole
# per-sample spatial slab a fused FNO block holds (x/y windows,
# double-buffered, plus the f32 accumulators) fits at full fno2d width,
# with a quarter of the core left to the compiler's own internal scratch.
# analysis.vmem budgets block plans against this same number.
VMEM_LIMIT_BYTES = 96 * 1024 * 1024


# The name each launch kind gives its pallas_call. The compiled kernel
# instruction, and so the device trace's op, reads "<name>.N", and the XLA
# ops the compiler places around a launch carry the name in their
# op_name. The tuned-plan launch kinds (tuning.plans.LAUNCH_KINDS) are
# among them.
LAUNCH_NAMES = ("block_fwd", "block_ends", "spectral_fwd", "gz_recompute",
                "dx_adjoint", "wgrad", "core", "rdft", "cdft", "irdft",
                "cgemm", "end_mlp")


def _compiler_params(**kwargs):
    """pltpu compiler params with the package-wide VMEM limit."""
    kwargs.setdefault("vmem_limit_bytes", VMEM_LIMIT_BYTES)
    return pltpu.CompilerParams(**kwargs)
