"""SpectralConv modules — the FNO Fourier layer with selectable execution
path (ref | xla | pallas) and weight mode (shared | per_mode), any rank.

Functional style: ``init(key) -> params``, ``apply(params, x) -> y``.
Channel-first layout [B, C, *spatial], matching the paper. ``apply_*``
accept an optional ``policy`` (PrecisionPolicy) forwarded to the kernels;
init takes the *param* dtype (master weights — f32 under the bf16 preset).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import PrecisionPolicy
from repro.distributed import sharding as shd
from repro.kernels import ops


def init_spectral_nd(key: jax.Array, in_ch: int, out_ch: int,
                     modes: Sequence[int], weight_mode: str = "shared",
                     dtype=jnp.float32) -> Dict[str, jax.Array]:
    """Rank-generic spectral-weight init: W [O,I] shared (the paper's
    CGEMM) or [O,I,k_1..k_R] per-mode (classic FNO)."""
    scale = 1.0 / (in_ch * out_ch) ** 0.5
    shape = ((out_ch, in_ch) if weight_mode == "shared"
             else (out_ch, in_ch) + tuple(modes))
    kr, ki = jax.random.split(key)
    return {"wr": scale * jax.random.normal(kr, shape, dtype),
            "wi": scale * jax.random.normal(ki, shape, dtype)}


def apply_spectral_nd(params: Dict[str, jax.Array], x: jax.Array,
                      modes: Sequence[int], *, path: str = "xla",
                      variant: str = "full",
                      policy: Optional[PrecisionPolicy] = None,
                      **kw) -> jax.Array:
    """x: [B, C_in, *spatial] -> [B, C_out, *spatial], any rank
    (ops.spectral_layer_nd; ``truncation`` and block sizes in kw)."""
    return ops.spectral_layer_nd(x, params["wr"], params["wi"], modes,
                                 path=path, variant=variant, policy=policy,
                                 **kw)


def apply_fno_block_nd(spec_params: Dict[str, jax.Array],
                       byp_params: Dict[str, jax.Array], x: jax.Array,
                       modes: Sequence[int], *, path: str = "pallas",
                       variant: str = "full",
                       policy: Optional[PrecisionPolicy] = None,
                       tp_layout: str = "psum", tp_overlap: bool = False,
                       ends: Optional[Tuple] = None, act: str = "gelu",
                       **kw) -> jax.Array:
    """One whole FNO block — act(spectral(x) + 1×1 bypass + bias) — as a
    single fused kernel on the pallas path (ops.fno_block_nd), any rank;
    act is "gelu", "relu" or "linear", and ``truncation`` (in kw) picks
    the kept bins (ops.fno_block_nd).

    spec_params: {"wr","wi"} from init_spectral_nd; byp_params: {"w","b"}
    from core.fno._dense_init, where w is [C_in, C_out] (einsum
    ``bc...,cd->bd...``) — transposed here to the engine's [O,H] layout.

    Inside a multi-device ``sharding_context`` the block dispatches through
    ``ops.fno_block_nd_sharded``: DP over the context's batch axes, TP over
    its model axis — the engine's k-loop hidden contraction — with the TP
    partials completed per tp_layout ("scatter": psum_scatter emitting the
    next layer's hidden shard; "psum": all-reduce to a replicated output —
    docs/DESIGN.md §6). tp_layout/tp_overlap only apply to the sharded
    dispatch; the single-device path ignores them.

    ends: optional (lift, proj) param tuples (``ops.fno_block_ends_nd``)
    folding the model's end MLPs into this block's kernel — single-device
    and pure-DP dispatch only (core.fno guards TP off).
    """
    wb = jnp.swapaxes(byp_params["w"], 0, 1)
    ctx = shd.current_context()
    has_ends = ends is not None and any(e is not None for e in ends)
    if path == "pallas" and ctx is not None and ctx.mesh.devices.size > 1:
        return ops.fno_block_nd_sharded(
            x, spec_params["wr"], spec_params["wi"], wb, byp_params["b"],
            tuple(modes), mesh=ctx.mesh, batch_axes=ctx.batch_axes,
            model_axis=ctx.model_axis, variant=variant, policy=policy,
            act=act, tp_layout=tp_layout, tp_overlap=tp_overlap,
            ends=ends if has_ends else None, **kw)
    if has_ends:
        assert kw.pop("truncation", "low") == "low", "fused ends: low corner"
        return ops.fno_block_ends_nd(
            x, spec_params["wr"], spec_params["wi"], wb, byp_params["b"],
            tuple(modes), lift=ends[0], proj=ends[1], path=path,
            variant=variant, policy=policy, **kw)
    return ops.fno_block_nd(x, spec_params["wr"], spec_params["wi"], wb,
                            byp_params["b"], tuple(modes), path=path,
                            variant=variant, policy=policy, act=act, **kw)
