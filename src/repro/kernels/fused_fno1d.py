"""Compatibility wrappers for the 1D fused FNO kernels.

The kernel bodies moved to the rank-generic engine
(``repro.kernels.engine``), which emits the same grid/accumulator layout
for every spatial rank — see engine.py's module docstring for the layout
notes that used to live here. These wrappers pin rank 1 and preserve the
original positional-operand signatures.

For the WHOLE FNO block — gelu(spectral(x) + 1×1 bypass + bias) in one
pallas_call, end-to-end differentiable — use the block API instead:
``engine.fused_fno_block_call`` (raw kernel) or ``ops.fno_block_nd``
(padded, custom_vjp, rank-generic).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import spectral
from repro.kernels import engine


def fused_fno1d_call(x: jax.Array, wr: jax.Array, wi: jax.Array,
                     cr: jax.Array, ci: jax.Array, er: jax.Array,
                     ei: jax.Array, bb: int, bo: int, bh: int,
                     interpret: bool = False) -> jax.Array:
    """x: [B,H,N] real; w: [O,H] or [O,H,K]; c: [N,K]; e: [K,N] -> y [B,O,N].

    All of B,O,H must divide by (bb,bo,bh); K,N are whole blocks (ops.py
    pads everything to (8,128)-aligned shapes).
    """
    mats = spectral.pack_realified([(cr, ci), (er, ei)], 1, jnp)
    return engine.fused_fnond_call(x, wr, wi, *mats,
                                   bb=bb, bo=bo, bh=bh, name="spectral_fwd",
                                   interpret=interpret)


def fused_fno1d_wgrad_call(x: jax.Array, g: jax.Array, cr: jax.Array,
                           ci: jax.Array, etr: jax.Array, eti: jax.Array,
                           bb: int, bo: int, bh: int, per_mode: bool,
                           interpret: bool = False
                           ) -> Tuple[jax.Array, jax.Array]:
    """x: [B,H,N] primal; g: [B,O,N] cotangent; c,et: [N,K].

    Returns (dwr, dwi): [O,H] shared, or [K,O,H] per-mode (caller
    transposes back to [O,H,K]).
    """
    return engine.fused_fnond_wgrad_call(x, g, cr, ci, etr, eti,
                                         bb=bb, bo=bo, bh=bh,
                                         per_mode=per_mode,
                                         interpret=interpret)
