"""Fourier Neural Operator models (1D / 2D / 3D), built on SpectralConv.

Architecture (paper Fig. 1 / Li et al. 2020):
  lifting pointwise MLP  →  L × [spectral conv + 1x1 bypass conv + act]
  →  projection pointwise MLP.

The configuration picks the activation (GELU, or ReLU as in Li et al.),
whether the last block is activated, a two-layer or one-layer lift, and
the spectral truncation (TurboFNO's low corner or Li et al.'s ± corners);
the defaults are TurboFNO's FNO. Li et al.'s FNO-3D (``fno3d-ns``) is
fc0 → 3 × [K + W, ReLU] → [K + W] → fc1 → ReLU → fc2.

With ``cfg.fuse_block`` each whole block — spectral + bypass + bias +
GELU — runs as ONE pallas_call per layer on the pallas path, forward and
backward (kernels/ops.fno_block_nd); the staged composition below remains
the parity oracle and the only path for ref/xla.

Rank is taken from ``cfg.ndim`` — the 3D variant (Navier–Stokes-class
workloads, Li et al. §5.3) runs on the same rank-generic fused engine as
1D/2D. Functional params-as-pytree; channel-first [B, C, *spatial].

Mixed precision (cfg.precision — a PrecisionPolicy): parameters are
initialized and updated at the *param* dtype (f32 master weights under the
bf16 preset); ``apply_fno`` casts the input once to the compute dtype and
the dense/bypass layers follow the activation dtype, so the whole forward
runs at compute precision while the gradients flowing back to the master
params are upcast by the cast-VJPs. The loss is always reduced in f32.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import FNOConfig
from repro.core import spectral, spectral_conv as sc
from repro.distributed.sharding import (activation_spec, compat_shard_map,
                                        current_context, guard_spec,
                                        shard_activation)
from repro.kernels import ops


def _dense_init(key, din, dout, dtype=jnp.float32):
    k1, _ = jax.random.split(key)
    scale = (2.0 / (din + dout)) ** 0.5
    return {"w": scale * jax.random.normal(k1, (din, dout), dtype),
            "b": jnp.zeros((dout,), dtype)}


def _dense(p, x):  # x: [B, C, *sp] pointwise over channels; follows x dtype
    y = jnp.einsum("bc...,cd->bd...", x, p["w"].astype(x.dtype))
    # The bias is broadcast BEFORE the compute-dtype cast: the cast's VJP
    # then upcasts the cotangent to f32 ahead of the broadcast's sum-VJP,
    # so the bias-grad reduction accumulates in f32. (A bf16 reduce over a
    # coherent cotangent field swamps — the accumulator sticks at its
    # first power of two; the weight grads are immune because dot-general
    # VJPs already accumulate in f32.)
    b = p["b"].reshape((1, -1) + (1,) * (y.ndim - 2))
    return y + jnp.broadcast_to(b, y.shape).astype(x.dtype)


def init_fno(key: jax.Array, cfg: FNOConfig) -> Dict[str, Any]:
    cfg.validate()
    dtype = jnp.dtype(cfg.precision.param_dtype)
    lift = cfg.lifting_dim or 2 * cfg.hidden
    keys = jax.random.split(key, 4 + 2 * cfg.num_layers)
    modes = cfg.kept_modes
    params: Dict[str, Any] = {
        "proj1": _dense_init(keys[2], cfg.hidden, lift, dtype),
        "proj2": _dense_init(keys[3], lift, cfg.out_channels, dtype),
        "blocks": [],
    }
    if cfg.lift_layers == 1:  # one layer in -> hidden; keys[1] unused
        params["lift1"] = _dense_init(keys[0], cfg.in_channels, cfg.hidden,
                                      dtype)
    else:
        params["lift1"] = _dense_init(keys[0], cfg.in_channels, lift, dtype)
        params["lift2"] = _dense_init(keys[1], lift, cfg.hidden, dtype)
    for i in range(cfg.num_layers):
        params["blocks"].append({
            "spectral": sc.init_spectral_nd(keys[4 + 2 * i], cfg.hidden,
                                            cfg.hidden, modes,
                                            cfg.weight_mode, dtype),
            "bypass": _dense_init(keys[5 + 2 * i], cfg.hidden, cfg.hidden,
                                  dtype),
        })
    return params


def input_layout(cfg: FNOConfig, path: str = None) -> Tuple[int, ...]:
    """The trailing shape ``apply_fno`` keeps its activations in between
    the ends: on the fused pallas path with staged ends the forward
    kernel's layout (``spectral.kernel_layout``: a rank-3 grid's trailing
    axes merged), else the grid. An input given in it needs no relayout
    on the device, and a host input reshaped to it crosses to the device
    as lane-dense rows (``FNOServer``)."""
    if (path or cfg.path) == "pallas" and cfg.fuse_block and not cfg.fuse_ends:
        return spectral.kernel_layout(cfg.spatial, cfg.kept_modes)[0]
    return tuple(cfg.spatial)


def end_mlp_on(cfg: FNOConfig, path: str = None, ctx=None) -> bool:
    """Whether ``apply_fno`` runs each staged end as one ``end_mlp``
    launch: on the fused pallas path without fused ends, on one device
    or data-parallel under the sharding context `ctx` (under TP the ends
    shard the hidden axis and stay staged XLA einsums, DESIGN.md §6)."""
    return ((path or cfg.path) == "pallas" and cfg.fuse_block
            and not cfg.fuse_ends
            and (ctx is None or ctx.model_axis is None))


def pallas_launches(cfg: FNOConfig, path: str = None, ctx=None) -> int:
    """The pallas_calls one fused forward of ``apply_fno`` traces under
    the sharding context `ctx` (the fusion contract, DESIGN.md §3.4): one
    per block, plus the lift and projection ``end_mlp`` launches where
    they run (``end_mlp_on``)."""
    return cfg.num_layers + 2 * end_mlp_on(cfg, path, ctx)


def _end_mlp(x, ps, act):
    """``ops.end_mlp`` over x with the Linears ``ps`` (``_dense``
    params); inside a multi-device sharding context it runs per batch
    shard under shard_map, the weights replicated."""
    layers = tuple((p["w"], p["b"]) for p in ps)
    ctx = current_context()
    if ctx is None or ctx.mesh.devices.size == 1:
        return ops.end_mlp(x, layers, act)
    spec = guard_spec(activation_spec("fno", ctx), x.shape, ctx.mesh)
    return compat_shard_map(
        lambda xl, ll: ops.end_mlp(xl, ll, act), ctx.mesh,
        in_specs=(spec, jax.tree_util.tree_map(lambda _: P(), layers)),
        out_specs=spec)(x, layers)


def apply_fno(params: Dict[str, Any], cfg: FNOConfig, x: jax.Array,
              *, path: str = None, variant: str = "full") -> jax.Array:
    """x: [B, in_channels, *spatial] -> [B, out_channels, *spatial]; x may
    also come with its trailing axes in ``input_layout``.

    Runs at cfg.precision.compute_dtype (the single activation cast lives
    here; the spectral kernels receive the policy and keep their f32
    accumulators).

    Inside a ``sharding_context`` the ``shard_activation`` calls pin the
    layer boundaries to the DP/TP layout (batch over the data axes, hidden
    over the model axis — docs/DESIGN.md §6); the fused blocks themselves
    dispatch through the shard_map wrapper in ``spectral_conv``."""
    path = path or cfg.path
    pol = cfg.precision
    x = shard_activation(x.astype(jnp.dtype(pol.compute_dtype)), "fno")
    # Whole-block fusion (cfg.fuse_block, pallas path only): spectral +
    # bypass + bias + GELU collapse into ONE pallas_call per layer — the
    # bypass GEMM rides the engine's hidden k-loop and the activation is
    # applied in the iDFT epilogue, so the per-layer intermediates never
    # round-trip HBM. The staged composition below stays the oracle.
    fuse = cfg.fuse_block and path == "pallas"
    # Fused MODEL ENDS (cfg.fuse_ends): fold the lifting MLP into the
    # FIRST fused block kernel and the projection MLP into the LAST one
    # (ops.fno_block_ends_nd) — the boundary activations never round-trip
    # HBM and an L-layer forward still traces exactly L pallas_calls.
    # Single-device / pure-DP only: under TP the projection needs the full
    # post-psum hidden vector and the lift would replicate per shard, so
    # the ends stay staged XLA ops there (DESIGN.md §6).
    ctx = current_context()
    ends_on = fuse and cfg.fuse_ends and (ctx is None
                                          or ctx.model_axis is None)
    # The ends run under the named scopes "lift" and "projection", which
    # the compiled ops carry in their op_name (the device trace's labels
    # for the staged einsums and the end_mlp launches); the fused ends
    # are the block_ends launches.
    act = lambda z: ops.activate(z, cfg.activation)  # noqa: E731
    # Between its ends a fused model keeps the activations in the forward
    # kernel's layout (spectral.kernel_layout: a rank-3 grid's trailing
    # axes merged), so the pointwise ends and the block kernels meet
    # without a relayout; each block sees its natural shape, and the
    # reshapes around it cancel.
    spatial = (tuple(x.shape[2:]) if x.ndim == 2 + cfg.ndim
               else tuple(cfg.spatial))
    kspatial = (spectral.kernel_layout(spatial, cfg.kept_modes)[0]
                if fuse and not ends_on else spatial)
    x = x.reshape(x.shape[:2] + kspatial)
    as_grid = lambda a: a.reshape(a.shape[:2] + spatial)  # noqa: E731
    # Otherwise, where the blocks are fused and the ends are not sharded,
    # each staged end runs as ONE end_mlp launch (ops.end_mlp) on the
    # kernel layout: the MLP's inner activation stays in VMEM.
    mlp_on = end_mlp_on(cfg, path, ctx)
    lift_ps = [params["lift1"]] + ([params["lift2"]]
                                   if cfg.lift_layers == 2 else [])
    if ends_on:
        h = x
    elif mlp_on:
        with jax.named_scope("lift"):
            h = shard_activation(_end_mlp(x, lift_ps, cfg.activation),
                                 "fno_hidden")
    elif cfg.lift_layers == 1:
        with jax.named_scope("lift"):
            h = shard_activation(_dense(params["lift1"], x), "fno_hidden")
    else:
        with jax.named_scope("lift"):
            h = act(_dense(params["lift1"], x))
            h = _dense(params["lift2"], shard_activation(h, "fno_lift"))
            h = shard_activation(h, "fno_hidden")
    # An explicit cfg.block_plan pins the kernel launch plans; otherwise
    # the ops layer resolves them from the tuned cache (repro.tuning).
    bkw = {"block_plan": cfg.block_plan} if cfg.block_plan else {}
    last = cfg.num_layers - 1
    mlp = lambda p: (p["w"], p["b"])
    modes = tuple(cfg.modes)
    spec_kw = dict(policy=pol, truncation=cfg.truncation, **bkw)
    for i, blk in enumerate(params["blocks"]):
        block_act = (cfg.activation if i < last or cfg.act_last
                     else "linear")
        if fuse:
            # TP collective layout per layer position (DESIGN.md §6):
            # interior layers complete their sharded k-loop with a
            # psum_scatter that emits the NEXT layer's hidden shard
            # (cfg.tp_layout="scatter", half the wire bytes of a psum);
            # the FINAL layer always psums — the projection consumes the
            # full hidden vector, so there is no next shard to scatter
            # into. No-op when TP is off.
            layout = cfg.tp_layout if i < last else "psum"
            lift = (mlp(params["lift1"]) + mlp(params["lift2"])
                    if ends_on and i == 0 else None)
            proj = (mlp(params["proj1"]) + mlp(params["proj2"])
                    if ends_on and i == last else None)
            h = sc.apply_fno_block_nd(blk["spectral"], blk["bypass"],
                                      as_grid(h), modes, path=path,
                                      variant=variant, act=block_act,
                                      tp_layout=layout,
                                      tp_overlap=cfg.tp_overlap,
                                      ends=((lift, proj)
                                            if lift or proj else None),
                                      **spec_kw)
            h = h.reshape(h.shape[:2] + kspatial)
            h = shard_activation(h, "fno" if (ends_on and i == last)
                                 else "fno_hidden")
            continue
        s = sc.apply_spectral_nd(blk["spectral"], h, modes, path=path,
                                 variant=variant, **spec_kw)
        h = ops.activate(s.astype(h.dtype) + _dense(blk["bypass"], h),
                         block_act)
        h = shard_activation(h, "fno_hidden")
    if ends_on:
        return shard_activation(h, "fno")
    with jax.named_scope("projection"):
        if mlp_on:
            out = _end_mlp(h, [params["proj1"], params["proj2"]],
                           cfg.activation)
        else:
            out = _dense(params["proj2"], act(_dense(params["proj1"], h)))
        return shard_activation(as_grid(out), "fno")


def relative_l2(pred: jax.Array, target: jax.Array) -> jax.Array:
    """Mean relative L2 loss over the batch (standard FNO objective).

    Always reduced in f32 — the loss is the one place a bf16 sum would
    visibly bias training."""
    pred = pred.astype(jnp.float32)
    target = target.astype(jnp.float32)
    b = pred.shape[0]
    diff = jnp.sqrt(jnp.sum((pred - target).reshape(b, -1) ** 2, axis=-1))
    norm = jnp.sqrt(jnp.sum(target.reshape(b, -1) ** 2, axis=-1))
    return jnp.mean(diff / jnp.maximum(norm, 1e-8))


def fno_loss(params, cfg: FNOConfig, batch: Dict[str, jax.Array],
             *, path: str = None, variant: str = "full") -> jax.Array:
    pred = apply_fno(params, cfg, batch["x"], path=path, variant=variant)
    return relative_l2(pred, batch["y"])
