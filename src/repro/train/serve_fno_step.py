"""Batched FNO serving: the forward step, the device-resident rollout
step, request bucketing, and a jit-cached server for the fused pallas
path (docs/DESIGN.md §6, §10).

FNO inference has no KV cache, so single-step serving reduces to (1)
batching requests, (2) padding each batch to a BUCKET size so the jit
cache stays finite and the fused kernel's grid never re-specializes, and
(3) running the bucketed forward on a DP×TP mesh. Buckets are multiples
of the fused engine's tuned batch block (``repro.tuning.serve_quantum``,
which validates the ladder against the autotuned cache) times the DP
shard count, so neither the kernel nor the mesh ever sees a ragged batch.

The production workload IS autoregressive, though: a PDE rollout feeds
step t's prediction back as step t+1's state. ``make_fno_rollout_step``
keeps the whole K-step trajectory device-resident inside one jitted
``lax.scan`` — the scan body traces once, so the trace stays exactly
forward's pallas_calls regardless of rollout depth (docs/DESIGN.md
§10; pinned by ``analysis.jaxpr_lint.lint_rollout``).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FNOConfig
from repro.core import fno as fno_mod
from repro.distributed import sharding as shd
from repro.tuning import resolve_block_plan, serve_quantum

# Bytes of host input per jitted step once a host request is large enough
# to pipeline: chunk k+1 crosses the host link while chunk k computes.
# About 3 ms on a TPU v5e's ~10 GB/s host link; a request at or under it
# goes over in one step.
_PIPELINE_CHUNK_BYTES = 32 << 20


def make_fno_serve_step(cfg: FNOConfig, *, path: Optional[str] = None,
                        variant: str = "full"):
    """serve_step(params, batch{"x": [B,C_in,*spatial]}) -> y.

    One batched forward at ``cfg.precision``; ``path`` defaults to
    ``cfg.path`` (the production cells set "pallas" + ``cfg.fuse_block``).
    Run it inside a ``sharding_context`` for the DP×TP placement.
    """
    def fno_serve_step(params, batch: Dict[str, jax.Array]) -> jax.Array:
        return fno_mod.apply_fno(params, cfg, batch["x"],
                                 path=path or cfg.path, variant=variant)
    return fno_serve_step


def make_fno_rollout_step(cfg: FNOConfig, *, path: Optional[str] = None,
                          variant: str = "full"):
    """rollout(params, batch{"x": [B,C_in,*spatial]}, steps=K) -> y_K.

    Device-resident autoregressive rollout: step t+1 consumes step t's
    output inside ONE jitted ``lax.scan`` — the carry never leaves HBM
    between steps, so the fused kernels' traffic win compounds over the
    whole trajectory instead of being paid back to HBM every step.

    Channel feedback: the model maps ``in_channels -> out_channels``.
    When they match the carry is simply the output; when the input has
    extra conditioning channels (fno2d serves ``(a, x, y) -> u``) the
    first ``out_channels`` carry channels are replaced by the prediction
    and the trailing ``in_channels - out_channels`` channels (coordinate
    grids / static conditioning) persist across steps. Requires
    ``out_channels <= in_channels``.

    Trace contract: the scan body traces ONCE, so a K-step rollout on the
    fused pallas path contains exactly one forward's pallas_calls
    (``fno.pallas_launches``) for ANY K (pinned by ``analysis.jaxpr_lint.lint_rollout``). ``steps``
    must be static under jit (``static_argnames=("steps",)``).
    """
    if cfg.out_channels > cfg.in_channels:
        raise ValueError(
            f"rollout needs out_channels <= in_channels to feed step t's "
            f"output back as step t+1's state, got {cfg.out_channels} > "
            f"{cfg.in_channels} for {cfg.name}")
    keep = cfg.in_channels - cfg.out_channels

    def fno_rollout_step(params, batch: Dict[str, jax.Array], *,
                         steps: int) -> jax.Array:
        # Cast ONCE so the scan carry dtype is invariant (apply_fno's own
        # input cast becomes the identity on every step).
        x0 = batch["x"].astype(jnp.dtype(cfg.precision.compute_dtype))

        def body(x, _):
            y = fno_mod.apply_fno(params, cfg, x, path=path or cfg.path,
                                  variant=variant)
            nxt = (jnp.concatenate([y, x[:, cfg.out_channels:]], axis=1)
                   if keep else y)
            return nxt, None

        xk, _ = jax.lax.scan(body, x0, None, length=steps)
        return xk[:, :cfg.out_channels]
    return fno_rollout_step


def batch_block(cfg: FNOConfig) -> int:
    """The fused engine's batch block (bb) for this workload — the
    serving quantum, so the kernel grid never pads the batch internally.
    Resolved through the tuned-plan cache (override → cache → static
    defaults), same as the kernel launch itself will."""
    return resolve_block_plan(cfg, "block_fwd").bb


def bucket_sizes(max_batch: int, *, quantum: int = 1) -> Tuple[int, ...]:
    """Geometric bucket ladder (quantum, 2q, 4q, … ≥ max_batch): one jit
    cache entry per bucket, log2(max/quantum)+1 compiles total."""
    q = max(quantum, 1)
    sizes = [q]
    while sizes[-1] < max_batch:
        sizes.append(sizes[-1] * 2)
    return tuple(sizes)


def pick_bucket(n: int, buckets: Sequence[int]) -> int:
    """Smallest bucket ≥ n (the largest bucket for oversize batches — the
    caller chunks those)."""
    for b in buckets:
        if b >= n:
            return b
    return buckets[-1]


def pad_to_bucket(x: jax.Array, bucket: int) -> Tuple[jax.Array, int]:
    """Zero-pad the batch axis to `bucket`; returns (padded, n_valid)."""
    n = x.shape[0]
    if n == bucket:
        return x, n
    pad = [(0, bucket - n)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad), n


class FNOServer:
    """Request-batched FNO inference on the fused pallas path.

    Pads every request batch to a bucket (``bucket_sizes``), keeps one jit
    cache entry per bucket, and — given a ``ShardingContext`` — traces the
    step inside it so the forward runs DP over the batch axes and TP over
    the hidden axis (the shard_map dispatch in ``kernels.ops``). The
    un-jitted ``step_fn`` is exposed for trace-level guards
    (``roofline.hlo_counter.count_pallas_calls``).
    """

    def __init__(self, cfg: FNOConfig, params, *,
                 ctx: Optional[shd.ShardingContext] = None,
                 path: Optional[str] = None, variant: str = "full",
                 max_batch: int = 64, quantum: Optional[int] = None):
        self.cfg, self.params, self.ctx = cfg, params, ctx
        # The quantum is validated against the TUNED plan's batch block
        # (serve_quantum): an explicit quantum that is not a multiple of
        # the tuned bb would misalign the whole ladder with the kernel
        # grid — a retune can therefore never silently break it.
        q = serve_quantum(cfg, quantum)
        if ctx is not None:
            for a in ctx.batch_axes:  # buckets must split across DP shards
                q *= ctx.mesh.shape.get(a, 1)
        self.buckets = bucket_sizes(max_batch, quantum=q)
        base = make_fno_serve_step(cfg, path=path, variant=variant)
        roll = make_fno_rollout_step(cfg, path=path, variant=variant)
        if ctx is not None:
            def step_fn(params, batch):
                with shd.sharding_context(ctx):
                    return base(params, batch)

            def rollout_step_fn(params, batch, *, steps):
                with shd.sharding_context(ctx):
                    return roll(params, batch, steps=steps)
        else:
            step_fn, rollout_step_fn = base, roll
        self.step_fn = step_fn
        # Un-jitted, exposed for trace guards: a K-step rollout must trace
        # exactly one forward's pallas_calls regardless of K
        # (lint_rollout).
        self.rollout_step_fn = rollout_step_fn
        # A host request crosses to the device already in the layout the
        # model runs in (a free reshape of a numpy array): a rank-3 grid's
        # short minor axis would otherwise be tiled to 128 lanes on the
        # way, and relaid on the device.
        self.host_layout = fno_mod.input_layout(cfg, path)
        self._step = jax.jit(step_fn)
        self._rollout = jax.jit(rollout_step_fn, static_argnames=("steps",))
        self.stats = {"requests": 0, "samples": 0, "padded": 0,
                      "chunks": 0}

    def collective_plan(self) -> Dict[str, object]:
        """The serving step's TP collective plan as metadata (ISSUE 8) —
        what the serve driver prints and ops dashboards scrape: the
        layout, whether the interior reduce-scatter runs as the ppermute
        ring (cfg.tp_overlap), the per-layer collective kinds, and the
        modeled per-device ICI wire bytes per forward at the SMALLEST
        bucket (``roofline.analysis.fno_collective_bytes`` — the
        scattered layout moves exactly half the psum layout's interior
        bytes). Pure metadata; never traces the step."""
        from repro.roofline.analysis import fno_collective_bytes

        ctx, cfg = self.ctx, self.cfg
        tp_on = ctx is not None and ctx.model_axis is not None
        dp = 1
        if ctx is not None:
            for a in ctx.batch_axes:
                dp *= ctx.mesh.shape.get(a, 1)
        tp = ctx.mesh.shape.get(ctx.model_axis, 1) if tp_on else 1
        layout = cfg.tp_layout if tp_on else None
        scattered = layout == "scatter"
        wire = fno_collective_bytes(cfg, dp, tp, scattered=scattered,
                                    batch=self.buckets[0])
        interior = ("none" if not tp_on else
                    ("ppermute-ring" if scattered and cfg.tp_overlap
                     else "psum_scatter" if scattered else "psum"))
        return {
            "tp_layout": layout, "tp_overlap": tp_on and cfg.tp_overlap,
            "dp": dp, "tp": tp,
            "interior_collective": interior,
            "final_collective": "psum" if tp_on else "none",
            "wire_bytes_per_fwd": wire["total"],
            "wire_bytes_interior_layer": wire["interior_per_layer"],
        }

    def step_with(self, params, x: jax.Array) -> jax.Array:
        """One bucketed step with EXPLICIT params (instead of
        ``self.params``): the canary-validation hook — the resilient
        runtime (``train/serve_runtime.py``) probes candidate reload
        params through the same jit cache before swapping them in."""
        b = pick_bucket(x.shape[0], self.buckets)
        xp, m = pad_to_bucket(x, b)
        return self._step(params, {"x": xp})[:m]

    def _bucketed(self, xp: jax.Array, rollout_steps: int) -> jax.Array:
        if rollout_steps == 1:
            return self._step(self.params, {"x": xp})
        return self._rollout(self.params, {"x": xp}, steps=rollout_steps)

    def _pipeline_chunk(self, x, rollout_steps: int) -> Optional[int]:
        """Samples per chunk of a host request pipelined through the host
        link: the smallest bucket that carries ``_PIPELINE_CHUNK_BYTES``
        (the top bucket if none does). None for a device array, a rollout
        or a request at or under that size: nothing to pipeline."""
        if (rollout_steps != 1 or not isinstance(x, np.ndarray)
                or x.nbytes <= _PIPELINE_CHUNK_BYTES):
            return None
        per_sample = x[0].nbytes
        return next((b for b in self.buckets
                     if b * per_sample >= _PIPELINE_CHUNK_BYTES),
                    self.buckets[-1])

    def __call__(self, x: jax.Array, rollout_steps: int = 1) -> jax.Array:
        """Serve one request batch x [n, C_in, *spatial] -> [n, C_out, …].

        ``rollout_steps > 1`` runs the device-resident autoregressive
        rollout (one lax.scan — the carry never leaves HBM) and returns
        the FINAL step's prediction; the jit cache keys on (bucket,
        steps). An empty batch returns an empty output without touching
        the step.

        The request runs as chunks, each padded to its own bucket and
        dispatched without waiting on any answer; the answers are joined
        on the device. Oversize batches are chunked at the largest
        bucket. A host (numpy) request of more than
        ``_PIPELINE_CHUNK_BYTES`` served in one step is instead chunked at
        the smallest bucket that carries that many bytes, and each chunk
        is sent with ``jax.device_put`` after the chunk before last has
        landed: the device computes chunk k while chunk k+1 crosses the
        host link, and the link is never shared by more than two chunks.
        ``stats["chunks"]`` counts the jitted steps dispatched.

        Each phase is a host span on the profiler's clock (a no-op unless
        a profiler session is open): ``fno_server.pad`` (bucket choice and
        padding), ``fno_server.step`` (the jitted call: with a host input,
        its transfer to the device and the dispatch), one pair per chunk,
        and ``fno_server.slice`` (dropping the padding, joining the
        chunks)."""
        if rollout_steps < 1:
            raise ValueError(f"rollout_steps must be >= 1, "
                             f"got {rollout_steps}")
        n = x.shape[0]
        if n == 0:
            return jnp.zeros(
                (0, self.cfg.out_channels) + tuple(x.shape[2:]),
                jnp.dtype(self.cfg.precision.compute_dtype))
        piped = self._pipeline_chunk(x, rollout_steps)
        size = piped or self.buckets[-1]
        outs, sent = [], []
        for s in range(0, n, size):
            with jax.profiler.TraceAnnotation("fno_server.pad"):
                chunk = x[s:s + size]
                b = pick_bucket(chunk.shape[0], self.buckets)
                xp, m = pad_to_bucket(chunk, b)
                if rollout_steps == 1 and isinstance(xp, np.ndarray):
                    xp = xp.reshape(xp.shape[:2] + self.host_layout)
            with jax.profiler.TraceAnnotation("fno_server.step"):
                if piped:
                    # Every chunk sent at once would share the link, and
                    # the first would land late.
                    if len(sent) == 2:
                        sent.pop(0).block_until_ready()
                    xp = jax.device_put(xp)
                    sent.append(xp)
                outs.append((self._bucketed(xp, rollout_steps), m))
            self.stats["padded"] += b - m
            self.stats["chunks"] += 1
        self.stats["requests"] += 1
        self.stats["samples"] += n
        with jax.profiler.TraceAnnotation("fno_server.slice"):
            ys = [y[:m] for y, m in outs]
            return jnp.concatenate(ys, 0) if len(ys) > 1 else ys[0]
