"""Pipeline parallelism: GPipe schedule over a mesh axis via shard_map +
collective_permute (lax.ppermute).

Stage s owns a contiguous slice of layers; microbatches stream through the
S stages in M + S - 1 ticks. Used for the biggest assigned archs when the
layer-parallel dimension is preferred over pure DP on the "pod" axis; the
schedule and its bubble fraction (S-1)/(M+S-1) are validated against a
sequential reference in tests/test_distributed.py.
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def gpipe_forward(stage_fn: Callable[[Any, jax.Array], jax.Array],
                  stage_params: Any, x_mb: jax.Array, *, axis: str,
                  num_stages: int) -> jax.Array:
    """Run inside shard_map over `axis`. stage_params: this stage's params
    (already sharded per-stage); x_mb: [M, mb, ...] microbatches (replicated
    content; stage 0 consumes them). Returns [M, mb, ...] outputs (valid on
    the LAST stage)."""
    s = jax.lax.axis_index(axis)
    m = x_mb.shape[0]
    ticks = m + num_stages - 1
    buf = jnp.zeros_like(x_mb[0])
    out = jnp.zeros_like(x_mb)

    def tick(carry, t):
        buf, out = carry
        # stage 0 ingests microbatch t; others use what arrived last tick
        inp = jnp.where(s == 0,
                        x_mb[jnp.clip(t, 0, m - 1)], buf)
        y = stage_fn(stage_params, inp)
        active = (t - s >= 0) & (t - s < m)
        y = jnp.where(active, y, jnp.zeros_like(y))
        # pass activations downstream s -> s+1 (ring; last wraps to 0, unused)
        perm = [(i, (i + 1) % num_stages) for i in range(num_stages)]
        buf_next = jax.lax.ppermute(y, axis, perm)
        # last stage records its finished microbatch
        out = jnp.where((s == num_stages - 1) & active,
                        out.at[jnp.clip(t - s, 0, m - 1)].set(y), out)
        return (buf_next, out), None

    (buf, out), _ = jax.lax.scan(tick, (buf, out), jnp.arange(ticks))
    # only the last stage holds real outputs (others are zeros); replicate
    return jax.lax.psum(out, axis)


def make_gpipe_fn(stage_fn, *, mesh: Mesh, axis: str, num_stages: int,
                  stage_param_spec, x_spec):
    """shard_map wrapper: returns f(stacked_stage_params, x_mb) -> out.

    Goes through compat_shard_map, which turns off the replication check
    this schedule needs off — only the last stage's outputs are real."""
    from repro.distributed.sharding import compat_shard_map

    def inner(params, x_mb):
        y = gpipe_forward(stage_fn, params, x_mb, axis=axis,
                          num_stages=num_stages)
        return y

    return compat_shard_map(
        inner, mesh,
        in_specs=(stage_param_spec, x_spec),
        out_specs=x_spec)
