"""Compatibility wrappers for the 2D fused FNO kernels.

The kernel bodies moved to the rank-generic engine
(``repro.kernels.engine``). These wrappers pin rank 2 and preserve the
original positional-operand signatures:

* ``fused_fno2d_call`` — paper-faithful partial fusion middle (§4.3,
  Fig. 6): [truncated cDFT along X → CGEMM → padded icDFT along X] on the
  complex stage-1 output (engine ``fused_fnond_core_call``).
* ``fused_fno2d_full_call`` — beyond-paper full fusion: the entire layer
  [rDFT_Y → cDFT_X → CGEMM → icDFT_X → irDFT_Y] in one kernel.
* ``fused_fno2d_wgrad_call`` — fused rank-reduction weight gradient.

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


def fused_fno2d_call(zr: jax.Array, zi: jax.Array, wr: jax.Array,
                     wi: jax.Array, fr: jax.Array, fi: jax.Array,
                     gr: jax.Array, gi: jax.Array, bb: int, bo: int, bh: int,
                     interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """z: [B,H,X,KY] complex pair (stage-1 output); w: [O,H] or
    [O,H,KX,KY]; f: [X,KX]; g: [KX,X]. Returns y pair — [B,KY,O,X] shared
    or [KY,B,O,X] per-mode (caller transposes)."""
    return engine.fused_fnond_core_call(zr, zi, wr, wi, fr, fi, gr, gi,
                                        bb=bb, bo=bo, bh=bh,
                                        interpret=interpret)


def fused_fno2d_full_call(x: jax.Array, wr: jax.Array, wi: jax.Array,
                          cr: jax.Array, ci: jax.Array, fr: jax.Array,
                          fi: jax.Array, gr: jax.Array, gi: jax.Array,
                          er: jax.Array, ei: jax.Array, bb: int, bo: int,
                          bh: int, interpret: bool = False) -> jax.Array:
    """Whole 2D FNO spectral layer in one kernel.

    x: [B,H,X,Y] real; w: [O,H] or [O,H,KX,KY]; c: [Y,KY]; f: [X,KX];
    g: [KX,X]; e: [KY,Y]. Returns y [B,O,X,Y] real.
    """
    mats = spectral.pack_realified([(cr, ci), (fr, fi), (gr, gi), (er, ei)],
                                   2, jnp)
    return engine.fused_fnond_call(x, wr, wi, *mats, bb=bb, bo=bo, bh=bh,
                                   name="spectral_fwd", interpret=interpret)


def fused_fno2d_wgrad_call(x: jax.Array, g: jax.Array, cr: jax.Array,
                           ci: jax.Array, fr: jax.Array, fi: jax.Array,
                           etr: jax.Array, eti: jax.Array, gtr: jax.Array,
                           gti: jax.Array, bb: int, bo: int, bh: int,
                           per_mode: bool, interpret: bool = False
                           ) -> Tuple[jax.Array, jax.Array]:
    """x: [B,H,X,Y] primal; g: [B,O,X,Y] cotangent; c,et: [Y,KY];
    f,gt: [X,KX]. Returns (dwr, dwi): [O,H] shared or [KY·KX,O,H]
    per-mode (caller reshapes and transposes back to [O,H,KX,KY])."""
    return engine.fused_fnond_wgrad_call(x, g, cr, ci, fr, fi, etr, eti,
                                         gtr, gti, bb=bb, bo=bo, bh=bh,
                                         per_mode=per_mode,
                                         interpret=interpret)
