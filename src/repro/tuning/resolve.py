"""Public block-plan resolution: override → tuned cache → static defaults.

``resolve_launch_plans`` is THE entry point the ops layer (and everything
above it) uses to turn a workload description into the five per-launch
(bb, bo, bh) preferences of a fused FNO block; ``resolve_block_plan``
answers for one launch kind (the serve bucket ladder asks it for the
``block_fwd`` batch block). Resolution order per launch:

1. explicit override — an ``FNOConfig.block_plan`` triple or nonzero
   bb/bo/bh in a public kernel signature (component-wise: 0 keeps the
   resolved value);
2. tuned cache hit (``store.lookup`` under the ``plans.plan_key``
   schema — regenerate with ``scripts/autotune.py``);
3. the documented static fallback ``plans._BLOCK_DEFAULTS``.

Returned plans are preferences: ``plans.legal_blocks`` still clamps them to
the actual dims at call time, so tiny trace shapes and ragged batches
never need their own cache entries.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

from repro.tuning import store
from repro.tuning.plans import (_BLOCK_DEFAULTS, BlockPlan, LAUNCH_KINDS,
                                LaunchPlans, dtype_tag, normalize_override,
                                plan_key, shape_class)


def _norm_workload(cfg_or_shapes, policy):
    """(hidden, out, spatial, modes, per_mode, policy, cfg_override) from
    an FNOConfig or a (hidden, spatial, modes, per_mode) tuple (the same
    tuple form ``analysis.vmem.block_launch_estimates`` accepts)."""
    from repro.configs.base import FNOConfig
    if isinstance(cfg_or_shapes, FNOConfig):
        cfg = cfg_or_shapes
        return (cfg.hidden, cfg.hidden, tuple(cfg.spatial),
                cfg.kept_modes, cfg.weight_mode == "per_mode",
                policy or cfg.precision, cfg.block_plan)
    h, spatial, modes, per_mode = cfg_or_shapes
    return (int(h), int(h), tuple(spatial), tuple(modes), bool(per_mode),
            policy, None)


def _resolve_one(rank: int, klass: str, layout: str, dtype: str,
                 launch: str, override: Tuple[int, int, int],
                 cache_path: Optional[str]) -> BlockPlan:
    key = plan_key(rank, klass, layout, dtype, launch)
    cached = store.lookup(key, cache_path)
    base = cached if cached is not None else _BLOCK_DEFAULTS[rank]
    source = "cache" if cached is not None else "default"
    bb, bo, bh = (override[0] or base[0], override[1] or base[1],
                  override[2] or base[2])
    if any(override):
        source = "override"
    return BlockPlan(bb, bo, bh, source=source, key=key)


def resolve_launch_plans(rank: int, *, hidden: int, out: Optional[int] = None,
                         spatial: Sequence[int], modes: Sequence[int],
                         per_mode: bool = False, policy=None,
                         override: Optional[Sequence[int]] = None,
                         cache_path: Optional[str] = None) -> LaunchPlans:
    """The five per-launch plans for one fused-block workload (see module
    doc for the resolution order). ``policy`` picks the dtype segment of
    the keys (None → f32). Rank 1 aliases ``core`` to ``fwd`` — partial
    fusion degenerates to full there."""
    out = hidden if out is None else out
    klass = shape_class(hidden, out, spatial, modes)
    layout = "per_mode" if per_mode else "shared"
    dtype = dtype_tag(policy.compute_dtype) if policy is not None else "f32"
    ov = normalize_override(override)
    one = lambda launch: _resolve_one(rank, klass, layout, dtype, launch,
                                      ov, cache_path).triple
    fwd = one("block_fwd")
    return LaunchPlans(fwd=fwd, core=fwd if rank == 1 else one("core"),
                       gz=one("gz_recompute"), dx=one("dx_adjoint"),
                       wgrad=one("wgrad"))


def serve_quantum(cfg_or_shapes, quantum: Optional[int] = None, *,
                  policy=None, cache_path: Optional[str] = None) -> int:
    """The serving bucket quantum, validated against the TUNED plan.

    The bucket ladder (``train/serve_fno_step.bucket_sizes``) must stay a
    multiple of the fused engine's batch block or every bucketed launch
    pads internally — and the batch block is whatever the tuned cache says
    for ``block_fwd``, not the static default. ``quantum=None`` returns
    the tuned ``bb`` itself; an explicit quantum (e.g. already multiplied
    by the DP shard count) is accepted only when it is a positive multiple
    of the tuned ``bb``, so a retune that changes the batch block can
    never silently misalign an explicitly-quantized ladder.
    """
    bb = resolve_block_plan(cfg_or_shapes, "block_fwd", policy=policy,
                            cache_path=cache_path).bb
    if quantum is None:
        return bb
    if quantum < 1 or quantum % bb != 0:
        raise ValueError(
            f"serve quantum {quantum} is not a positive multiple of the "
            f"tuned batch block bb={bb} (block_fwd plan) — the bucket "
            f"ladder would misalign with the kernel grid; use a multiple "
            f"of {bb} or pass quantum=None to take the tuned block")
    return quantum


def resolve_block_plan(cfg_or_shapes, launch: str = "block_fwd", *,
                       policy=None, override: Optional[Sequence[int]] = None,
                       cache_path: Optional[str] = None) -> BlockPlan:
    """Resolve ONE launch kind's plan for a config (or a ``(hidden,
    spatial, modes, per_mode)`` tuple). An ``FNOConfig.block_plan``
    participates as the override unless an explicit ``override`` is
    given. This is the public face of the ``plans._BLOCK_DEFAULTS``
    lookup — ``train/serve_fno_step.batch_block`` reads ``.bb`` off it.
    """
    if launch not in LAUNCH_KINDS:
        raise ValueError(f"unknown launch {launch!r}; want one of "
                         f"{LAUNCH_KINDS}")
    h, out, spatial, modes, per_mode, pol, cfg_ov = _norm_workload(
        cfg_or_shapes, policy)
    ov = normalize_override(override if override is not None else cfg_ov)
    klass = shape_class(h, out, spatial, modes)
    layout = "per_mode" if per_mode else "shared"
    dtype = dtype_tag(pol.compute_dtype) if pol is not None else "f32"
    rank = len(modes)
    if rank == 1 and launch == "core":
        launch = "block_fwd"
    return _resolve_one(rank, klass, layout, dtype, launch, ov, cache_path)
