"""Pallas TPU kernels: truncated rDFT / padded irDFT as MXU matmuls.

These are the standalone "FFT with built-in truncation / zero-padding"
kernels (paper §3.3): truncation = the DFT operand simply has `modes`
columns; zero-padding = the iDFT operand has `modes` rows. No separate copy
kernels exist anywhere. Pruning = the rows of the full DFT matrix that are
never materialized (docs/DESIGN.md §3.2).

Grid: 1-D over row-tiles of the flattened batch. The DFT matrices are
broadcast operands resident in VMEM for every program.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import _compiler_params

_F32 = jnp.float32

# 1-D grid over independent row-tiles — no cross-program accumulation.
_SEMANTICS = ("parallel",)


def _rdft_kernel(x_ref, cr_ref, ci_ref, xr_ref, xi_ref):
    x = x_ref[...]
    xr_ref[...] = jax.lax.dot(x, cr_ref[...], preferred_element_type=_F32
                              ).astype(xr_ref.dtype)
    xi_ref[...] = jax.lax.dot(x, ci_ref[...], preferred_element_type=_F32
                              ).astype(xi_ref.dtype)


def _irdft_kernel(xr_ref, xi_ref, er_ref, ei_ref, y_ref):
    yr = jax.lax.dot(xr_ref[...], er_ref[...], preferred_element_type=_F32)
    yi = jax.lax.dot(xi_ref[...], ei_ref[...], preferred_element_type=_F32)
    y_ref[...] = (yr - yi).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _rdft_call(x2d: jax.Array, cr: jax.Array, ci: jax.Array,
               block_rows: int, interpret: bool) -> Tuple[jax.Array, jax.Array]:
    m, n = x2d.shape
    k = cr.shape[1]
    grid = (m // block_rows,)
    spec_x = pl.BlockSpec((block_rows, n), lambda i: (i, 0))
    spec_m = pl.BlockSpec((n, k), lambda i: (0, 0))
    spec_o = pl.BlockSpec((block_rows, k), lambda i: (i, 0))
    out_sd = jax.ShapeDtypeStruct((m, k), x2d.dtype)
    return pl.pallas_call(
        _rdft_kernel,
        grid=grid,
        name="rdft",
        in_specs=[spec_x, spec_m, spec_m],
        out_specs=[spec_o, spec_o],
        out_shape=[out_sd, out_sd],
        compiler_params=_compiler_params(dimension_semantics=_SEMANTICS),
        interpret=interpret,
    )(x2d, cr, ci)


def _cdft_kernel(xr_ref, xi_ref, fr_ref, fi_ref, or_ref, oi_ref):
    """Complex-to-complex truncated DFT / padded iDFT (the operand decides
    which): 4 real MXU matmuls."""
    xr, xi = xr_ref[...], xi_ref[...]
    fr, fi = fr_ref[...], fi_ref[...]
    dot = lambda a, b: jax.lax.dot(a, b, preferred_element_type=_F32)
    or_ref[...] = (dot(xr, fr) - dot(xi, fi)).astype(or_ref.dtype)
    oi_ref[...] = (dot(xr, fi) + dot(xi, fr)).astype(oi_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _cdft_call(xr2d: jax.Array, xi2d: jax.Array, fr: jax.Array,
               fi: jax.Array, block_rows: int,
               interpret: bool) -> Tuple[jax.Array, jax.Array]:
    m, n = xr2d.shape
    k = fr.shape[1]
    grid = (m // block_rows,)
    spec_x = pl.BlockSpec((block_rows, n), lambda i: (i, 0))
    spec_m = pl.BlockSpec((n, k), lambda i: (0, 0))
    spec_o = pl.BlockSpec((block_rows, k), lambda i: (i, 0))
    out_sd = jax.ShapeDtypeStruct((m, k), xr2d.dtype)
    return pl.pallas_call(
        _cdft_kernel,
        grid=grid,
        name="cdft",
        in_specs=[spec_x, spec_x, spec_m, spec_m],
        out_specs=[spec_o, spec_o],
        out_shape=[out_sd, out_sd],
        compiler_params=_compiler_params(dimension_semantics=_SEMANTICS),
        interpret=interpret,
    )(xr2d, xi2d, fr, fi)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _irdft_call(xr2d: jax.Array, xi2d: jax.Array, er: jax.Array, ei: jax.Array,
                block_rows: int, interpret: bool) -> jax.Array:
    m, k = xr2d.shape
    n = er.shape[1]
    grid = (m // block_rows,)
    spec_x = pl.BlockSpec((block_rows, k), lambda i: (i, 0))
    spec_m = pl.BlockSpec((k, n), lambda i: (0, 0))
    spec_o = pl.BlockSpec((block_rows, n), lambda i: (i, 0))
    return pl.pallas_call(
        _irdft_kernel,
        grid=grid,
        name="irdft",
        in_specs=[spec_x, spec_x, spec_m, spec_m],
        out_specs=spec_o,
        out_shape=jax.ShapeDtypeStruct((m, n), xr2d.dtype),
        compiler_params=_compiler_params(dimension_semantics=_SEMANTICS),
        interpret=interpret,
    )(xr2d, xi2d, er, ei)
