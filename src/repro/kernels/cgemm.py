"""Pallas TPU kernel: blocked complex GEMM (CGEMM).

The paper builds a CUDA-core CGEMM with m_tb=32, n_tb=32, k_tb=8 and double
smem buffering (Table 1). The TPU analogue uses MXU-aligned 128-tiles; the
k-loop is the innermost grid dimension with an f32 VMEM accumulator, and
Pallas's automatic pipelining plays the role of double buffering
(docs/DESIGN.md §2). Complex product = 4 real matmuls.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import _compiler_params

_F32 = jnp.float32


def _cgemm_kernel(ar_ref, ai_ref, br_ref, bi_ref, cr_ref, ci_ref,
                  accr, acci):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        accr[...] = jnp.zeros_like(accr)
        acci[...] = jnp.zeros_like(acci)

    ar, ai = ar_ref[...], ai_ref[...]
    br, bi = br_ref[...], bi_ref[...]
    dot = functools.partial(jax.lax.dot, preferred_element_type=_F32)
    accr[...] += dot(ar, br) - dot(ai, bi)
    acci[...] += dot(ar, bi) + dot(ai, br)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _epilogue():
        cr_ref[...] = accr[...].astype(cr_ref.dtype)
        ci_ref[...] = acci[...].astype(ci_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def cgemm_call(ar: jax.Array, ai: jax.Array, br: jax.Array, bi: jax.Array,
               bm: int = 128, bn: int = 128, bk: int = 128,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """(M,K)·(K,N) complex matmul. All dims must be multiples of the blocks
    (ops.py pads)."""
    m, k = ar.shape
    _, n = br.shape
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _cgemm_kernel,
        grid=grid,
        name="cgemm",
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        ],
        out_shape=[jax.ShapeDtypeStruct((m, n), ar.dtype)] * 2,
        scratch_shapes=[pltpu.VMEM((bm, bn), _F32),
                        pltpu.VMEM((bm, bn), _F32)],
        compiler_params=_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(ar, ai, br, bi)
