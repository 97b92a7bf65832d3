"""Logical-axis sharding: DP / TP / EP / SP rules for the whole framework.

Model code calls ``shard_activation(x, kind)`` at layer boundaries; outside a
``sharding_context`` these are no-ops (CPU unit tests), inside one they become
``with_sharding_constraint`` with specs derived from the mesh and the
architecture (docs/DESIGN.md §6).

FNO strategy: DP shards the batch axis; TP shards the HIDDEN/channel axis —
the fused engine's k-loop contraction axis — whenever the model axis divides
``cfg.hidden``. The TP partial pre-activations are completed by a ``psum``
inside the shard_map dispatch (``kernels.ops.fno_block_nd_sharded``); when
TP is off the model axis folds into the batch axes and the (tiny) FNO
weights replicate (docs/DESIGN.md §6).

TP strategy per architecture (``attn_tp``): attention shards over the "model"
axis when query heads divide it; KV heads are REPLICATED up to one copy per
shard (``kv_repeat``) when ``num_kv_heads < tp`` — this multiplies KV-cache
memory by the repeat factor and is recorded per-arch in EXPERIMENTS.md.
Archs whose head counts don't divide the axis (qwen2 12H, arctic 56H, hymba
25H) replicate attention and use TP for MLP/SSM/vocab only.

Parameter specs are PATH-BASED: ``param_specs(cfg, mesh, params)`` walks the
actual params pytree and assigns a PartitionSpec per leaf from its key path,
so the spec tree always matches the params structure exactly.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import FNOConfig, ModelConfig


@dataclasses.dataclass
class ShardingContext:
    mesh: Mesh
    batch_axes: Tuple[str, ...]  # ("data",) or ("pod", "data") or ()
    model_axis: Optional[str] = "model"
    attn_sharded: bool = True  # heads dim sharded over model axis
    kv_repeat_factor: int = 1  # KV-head replication for TP
    seq_axis: Any = None  # SP: shard sequence/KV-cache over this axis(es)
    resid_seq_axis: Any = None  # Megatron-SP: residual stream seq sharding


_TLS = threading.local()


def compat_shard_map(f, mesh, in_specs, out_specs):
    """The repo's one ``jax.shard_map`` call site (the AST lint keeps it
    so). Varying-manual-axes checking is off: the FNO dispatch closes
    over custom_vjp pallas wrappers that carry no replication rules."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# Scattered-layout collectives (docs/DESIGN.md §6).
#
# The TP data path completes each interior layer's sharded hidden k-loop
# with a reduce-scatter that emits the NEXT layer's hidden shard directly:
# (tp-1)/tp of the tensor crosses the wire instead of the psum layout's
# 2(tp-1)/tp (reduce + broadcast halves), and the output lands already
# sharded P(batch, model) — no implicit re-shard. ``scatter_sum`` is the
# collective wrapped in a custom_vjp so the backward pass gets the MIRRORED
# collective (an all_gather along the scatter axis — the reduce-scatter's
# exact transpose): jax.grad stays end-to-end differentiable through the
# scattered layout without relying on the primitive's own AD rules.
# ``ring_scatter_sum`` is the same reduction as tp-1 ppermute chunk hops —
# XLA lowers each hop to an async collective-permute it can overlap with
# neighboring k-loop compute (the opt-in ``tp_overlap`` mode; native AD
# transposes the ring into the mirrored all-gather ring).
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def scatter_sum(z: jax.Array, axis_name: str, axis: int = 1) -> jax.Array:
    """Reduce-scatter ``z`` over ``axis_name`` along ``axis`` (tiled): the
    cross-shard sum of z arrives with ``axis`` cut to 1/tp per shard —
    shard i holds chunk i. Must be called inside shard_map."""
    return jax.lax.psum_scatter(z, axis_name, scatter_dimension=axis,
                                tiled=True)


def _scatter_sum_fwd(z, axis_name, axis):
    return scatter_sum(z, axis_name, axis), None


def _scatter_sum_bwd(axis_name, axis, _, g):
    # The mirrored collective: scatter_sum is linear with matrix S·Σ (chunk
    # select ∘ cross-shard sum), whose transpose replicates the per-shard
    # cotangent chunk back to every shard along the scatter axis — exactly
    # a tiled all_gather.
    return (jax.lax.all_gather(g, axis_name, axis=axis, tiled=True),)


scatter_sum.defvjp(_scatter_sum_fwd, _scatter_sum_bwd)


def ring_scatter_sum(z: jax.Array, axis_name: str, axis_size: int,
                     axis: int = 1) -> jax.Array:
    """``scatter_sum`` as a ppermute ring (bidirectionally differentiable
    through ppermute's native transpose).

    Standard ring reduce-scatter: each shard starts from the chunk that is
    furthest (ring-wise) from its own, and over ``axis_size - 1`` steps
    forwards its partial sum to the next shard while adding the local
    chunk the arriving partial corresponds to; after the last hop shard i
    holds Σ_j z_j[chunk_i]. Each hop is an independent async
    collective-permute of 1/tp of the tensor, which XLA's latency-hiding
    scheduler can overlap with unrelated compute — the comm/compute
    overlap lever for the scattered TP layout (``FNOConfig.tp_overlap``).
    """
    n = axis_size
    if n == 1:
        return z
    idx = jax.lax.axis_index(axis_name)
    csize = z.shape[axis] // n

    def chunk(c):
        return jax.lax.dynamic_slice_in_dim(z, c * csize, csize, axis)

    perm = [(j, (j + 1) % n) for j in range(n)]
    acc = chunk((idx + n - 1) % n)
    for s in range(2, n + 1):
        acc = jax.lax.ppermute(acc, axis_name, perm)
        acc = acc + chunk((idx + n - s) % n)
    return acc


def current_context() -> Optional[ShardingContext]:
    return getattr(_TLS, "ctx", None)


@contextlib.contextmanager
def sharding_context(ctx: Optional[ShardingContext]):
    prev = current_context()
    _TLS.ctx = ctx
    try:
        yield ctx
    finally:
        _TLS.ctx = prev


def attn_tp(cfg: ModelConfig, tp: int) -> int:
    """Degree of head-sharding usable for this architecture (tp or 1)."""
    if not cfg.has_attention or tp <= 1:
        return 1
    if cfg.num_heads % tp:
        return 1
    kv = cfg.num_kv_heads
    if kv >= tp and kv % tp == 0:
        return tp
    if kv < tp and tp % kv == 0 and cfg.num_heads % tp == 0:
        # after repeating KV to tp heads, each shard needs whole q-groups
        return tp if (cfg.num_heads // tp) >= 1 and cfg.num_heads % tp == 0 \
            else 1
    return 1


def kv_repeat(cfg: ModelConfig, tp: int) -> int:
    """KV-head replication factor so every TP shard owns whole KV heads."""
    if attn_tp(cfg, tp) == 1 or cfg.num_kv_heads >= tp:
        return 1
    return tp // cfg.num_kv_heads


def make_context(cfg, mesh, *, kind: str = "train",
                 fno_strategy: Optional[str] = None) -> ShardingContext:
    """Standard context for an (arch × step-kind) cell.

    FNO (docs/DESIGN.md §6): DP shards the batch axis and TP shards the
    hidden/channel axis — the fused engine's k-loop contraction axis —
    whenever the model axis divides ``cfg.hidden`` (``fno_strategy`` None
    or "auto"). ``fno_strategy="dp"`` folds the model axis into the batch
    axes instead (weights replicated, no per-layer collective — the right
    call when batch ≫ hidden); indivisible hidden degrades to the same.
    ``kind`` is "train" or "serve" for FNO — the placement is identical,
    FNO serving being a pure batch-throughput forward.
    """
    tp = mesh.shape.get("model", 1)
    pod = "pod" in mesh.shape
    batch: Tuple[str, ...] = ("pod", "data") if pod else ("data",)
    seq_axis = None
    if isinstance(cfg, FNOConfig):
        tp_on = (fno_strategy or "auto") != "dp" and tp > 1 \
            and cfg.hidden % tp == 0
        if not tp_on and "model" in mesh.shape:
            batch = batch + ("model",)
        return ShardingContext(mesh=mesh, batch_axes=batch,
                               model_axis="model" if tp_on else None,
                               attn_sharded=False)
    if isinstance(cfg, ModelConfig):
        a_tp = attn_tp(cfg, tp)
        r = kv_repeat(cfg, tp)
    else:
        a_tp, r = 1, 1
    # Megatron sequence parallelism for training: the residual stream is
    # sequence-sharded over the model axis between layers, so the per-layer
    # saved-for-backward carries scale 1/tp (without it, a 96-layer 18k-wide
    # arch saves 14+ GB/chip of activations at 4k context).
    resid = "model" if (kind == "train" and isinstance(cfg, ModelConfig)) \
        else None
    return ShardingContext(mesh=mesh, batch_axes=batch,
                           attn_sharded=a_tp > 1, kv_repeat_factor=r,
                           resid_seq_axis=resid)


def _batch_entry(ctx: ShardingContext):
    if not ctx.batch_axes:
        return None
    return tuple(ctx.batch_axes) if len(ctx.batch_axes) > 1 \
        else ctx.batch_axes[0]


def activation_spec(kind: str, ctx: ShardingContext) -> Optional[P]:
    b = _batch_entry(ctx)
    m = ctx.model_axis
    s = ctx.seq_axis
    table = {
        "embed": P(b, ctx.resid_seq_axis, None),  # [B, S, D] residual
        "ffn": P(b, s, m),  # [B, S, F]
        "heads": P(b, s, m if ctx.attn_sharded else None, None),
        "logits": P(b, s, m),  # [B, S, V]
        "kv": P(b, s, m if ctx.attn_sharded else None, None),
        "experts": P(b, m, None, None),  # [B, E, C, D] per-row dispatch
        "ssm_inner": P(b, s, m),  # [B, S, d_inner]
        "fno": P(b, None, None, None),  # [B, C_io, *spatial] boundaries
        "fno_hidden": P(b, m, None, None),  # [B, H, *spatial]: H = TP k-loop
        "fno_lift": P(b, m, None, None),  # [B, lift, *spatial] MLP inner
    }
    return table.get(kind)


def shard_activation(x: jax.Array, kind: str) -> jax.Array:
    ctx = current_context()
    if ctx is None:
        return x
    spec = activation_spec(kind, ctx)
    if spec is None:
        return x
    entries = list(spec) + [None] * (x.ndim - len(spec))
    spec = P(*entries[: x.ndim])
    # drop specs that don't divide the dim evenly
    mesh_shape = ctx.mesh.shape
    def ok(dim, entry):
        if entry is None:
            return None
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = 1
        for a in axes:
            size *= mesh_shape.get(a, 1)
        return entry if dim % size == 0 else None
    spec = P(*(ok(d, e) for d, e in zip(x.shape, spec)))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


# ---------------------------------------------------------------------------
# Parameter partition specs (path-based)
# ---------------------------------------------------------------------------
def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def _div(n: int, tp: int) -> bool:
    return tp > 0 and n % tp == 0


def _add_fsdp(spec: P, shape, dp: int, start: int = 0, entry="data") -> P:
    """FSDP/ZeRO-3: shard the largest still-replicated weight dim over the
    data axis. Params+optimizer then scale 1/(dp·tp) per chip — without
    this, a 341B arch on a 16x16 mesh replicates 128 GB/chip of state.
    XLA inserts the per-layer weight all-gathers / gradient
    reduce-scatters (they appear in the collective roofline term).
    `start` skips the stacked-layer leading dim."""
    if len(shape) < start + 2:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    cands = [i for i, (d, e) in enumerate(zip(shape, entries))
             if i >= start and e is None and d % dp == 0 and d >= dp]
    if not cands:
        return spec
    best = max(cands, key=lambda i: shape[i])
    entries[best] = entry
    return P(*entries)


def _lm_leaf_spec(pstr: str, shape, cfg: ModelConfig, tp: int) -> P:
    m = "model"
    a_tp = attn_tp(cfg, tp)
    head_m = m if a_tp > 1 else None
    ff_m = m if _div(cfg.d_ff, tp) else None
    ssm_m = m if _div(cfg.d_inner, tp) else None
    ssmh_m = m if _div(cfg.ssm_heads, tp) else None
    emb_m = m if _div(cfg.vocab_size, tp) else None
    in_layers = pstr.startswith("layers/")
    lead = (None,) if in_layers else ()

    def sp(*tail):
        full = lead + tail
        assert len(full) == len(shape), (pstr, shape, full)
        return P(*full)

    if pstr == "embed":
        return P(emb_m, None)
    if pstr.startswith("lm_head"):
        return P(None, emb_m) if pstr.endswith("w") else P(emb_m)
    if pstr.startswith("final_norm"):
        return P(None)
    # ---- per-layer params (leading stacked dim) ----
    if "/attn/" in pstr:
        if "/wo/" in pstr:
            return sp(head_m, None)
        return sp(None, head_m) if pstr.endswith("/w") else sp(head_m)
    if "/ssm/" in pstr:
        if "/out/" in pstr:
            return sp(ssm_m, None)
        if "/in_x/" in pstr or "/in_z/" in pstr:
            return sp(None, ssm_m) if pstr.endswith("/w") else sp(ssm_m)
        if "/conv_w" in pstr:
            return sp(None, ssm_m)
        if "/a_log" in pstr or pstr.endswith("/ssm/d"):
            return sp(ssmh_m)
        if "/norm/" in pstr:
            return sp(ssm_m)
        if "/in_dt/" in pstr and pstr.endswith("/b"):
            return sp(None)
        return sp(None, None) if len(shape) == 3 else sp(None)
    if "/moe/experts/" in pstr:
        ep = _div(cfg.num_experts, tp)
        e_m = m if ep else None
        f_m = None if ep else ff_m
        if pstr.endswith("wo"):
            return sp(e_m, f_m, None)
        return sp(e_m, None, f_m)
    if "/moe/router/" in pstr:
        return sp(None, None)
    if "/mlp/" in pstr:
        if "/wo/" in pstr:
            return sp(ff_m, None)
        return sp(None, ff_m) if pstr.endswith("/w") else sp(ff_m)
    if "/ln1/" in pstr or "/ln2/" in pstr:
        return sp(None)
    # fallback: replicate
    return P(*([None] * len(shape)))


def _fno_leaf_spec(pstr: str, shape, cfg: FNOConfig, tp: int) -> P:
    """FNO tensor parallelism shards the CONTRACTION (hidden) axis — the
    fused engine's k-loop — so every TP shard computes a partial FNO block
    that ``kernels.ops.fno_block_nd_sharded`` completes with a psum over
    the model axis (docs/DESIGN.md §6). The lifting/projection MLPs follow
    the Megatron column→row pattern around the lifting dim."""
    m = "model"
    h_m = m if _div(cfg.hidden, tp) else None
    lift = cfg.lifting_dim or 2 * cfg.hidden
    l_m = m if _div(lift, tp) else None
    pad = (None,) * max(len(shape) - 2, 0)
    if "spectral" in pstr:  # wr/wi [O, H(, modes...)]: shard H (k-loop)
        return P(None, h_m, *pad)
    if "bypass" in pstr:  # dense [H_in, H_out]: shard the contraction dim
        return P(h_m, None) if pstr.endswith("/w") else P(None)
    if "lift1" in pstr:  # column-parallel into the lifting dim
        return P(None, l_m) if pstr.endswith("/w") else P(l_m)
    if "lift2" in pstr:  # row-parallel back down to hidden
        return P(l_m, None) if pstr.endswith("/w") else P(None)
    if "proj1" in pstr:  # row-parallel over the (sharded) hidden
        return P(h_m, None) if pstr.endswith("/w") else P(None)
    return P(*([None] * len(shape)))  # proj2 + biases: replicate (tiny)


def param_specs(cfg, mesh: Mesh, params, fsdp: bool = True,
                fno_tp: bool = True) -> Any:
    """Spec pytree with the same structure as ``params`` (arrays or SDS).

    fsdp=True additionally shards every weight matrix over the data axis
    (ZeRO-3 for training; 2D weight-stationary sharding for decode of the
    biggest archs — nothing else fits 341B+ on 256 chips).

    fno_tp=False replicates the FNO weights (the pure-DP strategy: the
    model axis is folded into the batch axes by ``make_context``, so the
    hidden axis must not also be sharded over it). Pass
    ``ctx.model_axis is not None`` from a context-driven caller."""
    tp = mesh.shape.get("model", 1)
    dp = mesh.shape.get("data", 1)
    is_lm = isinstance(cfg, ModelConfig)
    leaf_fn = _lm_leaf_spec if is_lm else _fno_leaf_spec
    if not is_lm and not fno_tp:
        tp = 0  # pure-DP FNO: _div() never holds, every leaf replicates
    # >=100B archs extend FSDP across the pod axis too (state /512) —
    # cross-pod weight gathers are the price of fitting at all.
    entry: Any = "data"
    if is_lm and "pod" in mesh.shape and cfg.param_count() > 1e11:
        entry = ("pod", "data")
        dp *= mesh.shape["pod"]

    def assign(path, leaf):
        pstr = _path_str(path)
        spec = leaf_fn(pstr, leaf.shape, cfg, tp)
        if fsdp and is_lm:
            start = 1 if pstr.startswith("layers/") else 0
            spec = _add_fsdp(spec, leaf.shape, dp, start, entry)
        return guard_spec(spec, leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(assign, params)


def opt_state_specs(cfg, mesh: Mesh, params, opt_state,
                    fno_tp: bool = True) -> Any:
    """AdamW state mirrors param sharding; step is replicated."""
    pspecs = param_specs(cfg, mesh, params, fno_tp=fno_tp)
    return {"m": pspecs, "v": pspecs, "step": P()}


def shardings_from_specs(specs, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P))


# ---------------------------------------------------------------------------
# Batch / cache specs
# ---------------------------------------------------------------------------
def guard_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop spec entries whose mesh-axis product doesn't divide the dim."""
    entries = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * len(shape)):
        if entry is None:
            entries.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = 1
        for a in axes:
            size *= mesh.shape.get(a, 1)
        entries.append(entry if dim % size == 0 else None)
    return P(*entries)


def batch_specs(cfg, ctx: ShardingContext, batch_tree) -> Any:
    b = _batch_entry(ctx)

    def assign(path, leaf):
        return guard_spec(P(b, *([None] * (len(leaf.shape) - 1))),
                          leaf.shape, ctx.mesh)

    return jax.tree_util.tree_map_with_path(assign, batch_tree)


def cache_specs(cfg: ModelConfig, ctx: ShardingContext, cache_tree,
                shard_seq: bool = False, seq_axes=None) -> Any:
    """Specs for the decode cache pytree.

    shard_seq=True (SP, long-context batch=1): KV-cache sequence dim over
    the data axis(es). seq_axes overrides the axes used for the sequence
    dim (e.g. ("model",) for big-cache decode where the per-chip KV cache
    would not fit with head sharding alone). SSM states shard heads over
    model when divisible.
    """
    b = _batch_entry(ctx)
    m = ctx.model_axis
    tp = ctx.mesh.shape.get(m, 1)
    kv_m = m if ctx.attn_sharded else None
    if seq_axes is None:
        data_ax = tuple(a for a in ("pod", "data") if a in ctx.mesh.shape)
    else:
        data_ax = tuple(seq_axes)
        shard_seq = True
        if m in data_ax:
            kv_m = None  # model axis now shards the sequence dim
    seq_entry = (data_ax if len(data_ax) > 1 else data_ax[0]) \
        if shard_seq else None
    dp = 1
    for a in (data_ax if shard_seq else ()):
        dp *= ctx.mesh.shape[a]

    def assign(path, leaf):
        pstr = _path_str(path)
        if pstr.endswith("len"):
            return P()
        if pstr.endswith("/k") or pstr.endswith("/v"):
            # [nl, B, Sc, Hkv_eff, D]
            se = seq_entry if (shard_seq and leaf.shape[2] % max(dp, 1) == 0) \
                else None
            kvh = kv_m if leaf.shape[3] % tp == 0 else None
            batch_e = b if (seq_axes is not None and m in (seq_axes or ())
                            ) or not shard_seq else None
            sp = P(None, batch_e, se, kvh, None)
        elif pstr.endswith("/ssm"):
            hm = m if leaf.shape[2] % tp == 0 else None
            sp = P(None, None if shard_seq else b, hm, None, None)
        elif pstr.endswith("/conv"):
            im = m if leaf.shape[3] % tp == 0 else None
            sp = P(None, None if shard_seq else b, None, im)
        else:
            sp = P(*([None] * len(leaf.shape)))
        return guard_spec(sp, leaf.shape, ctx.mesh)

    return jax.tree_util.tree_map_with_path(assign, cache_tree)
