"""Static VMEM-footprint estimator for the fused engine's launches.

Computes each engine launch's operand + scratch bytes from the resolved
block plans (``repro.tuning.resolve_launch_plans`` — tuned cache with the
static ``tuning.plans._BLOCK_DEFAULTS`` as fallback), the config's shapes,
and the ``PrecisionPolicy`` dtypes — BEFORE lowering, so an over-budget
config is a lint finding instead of a Mosaic allocation failure mid-run.
The estimator is also the autotuner's pruning oracle
(``launch_estimate`` scores one candidate triple for one launch kind).

Shape model (mirrors ``kernels/engine.py`` exactly):

  * grid-blocked operands and outputs (the x/w/y/wb/bias/gy windows) are
    double-buffered by Mosaic → ×2 bytes;
  * the DFT operand mats use constant index maps (same block every
    program) and the VMEM accumulators are scratch → ×1;
  * accumulators live at ``accum_dtype`` with the shapes the kernels
    declare (two ``(∏K,bb,bo)`` per-mode, or ``(bb,bo,K_1,K_2)`` where the
    per-mode CGEMM runs on the VPU, one realified ``(bb·∏K, 2bo)``
    shared, plus the bypass scratch ``(bo,bb)+spatial`` for the block
    epilogue);
  * the forward kernel family runs in ``spectral.kernel_layout``: a
    rank-3 layer's trailing axes merged, its DFT operands Kronecker
    products;
  * every buffer is padded to Mosaic's tiles: the minor dim to 128
    lanes, the second-minor to 8 (32-bit) or 16 (16-bit) sublanes.

The estimate still misses Mosaic's internal scratch (relayouts, matmul
temporaries); where it and a compile for the described chip disagree,
the compile decides (the tuner compiles its full-size winners,
tests/test_tpu_compile.py the main path's). Severity policy
(since the autotuner landed): EVERY config — reduced and full-size — must
resolve plans that fit, at error severity. A full-size config erroring
here means the committed tuned cache (``tuning/cache/blocks.json``) lost
coverage for its shape class; regenerate with ``scripts/autotune.py``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp

from repro.analysis import Finding
from repro.configs.base import FNOConfig, PrecisionPolicy
from repro.kernels import VMEM_LIMIT_BYTES

# The budget is the scoped-VMEM limit every engine kernel compiles under
# (``kernels._compiler_params``) — one number. The compile for a described
# chip (tests/test_tpu_compile.py) is the authority; this estimate only
# prunes candidates before anything is lowered.
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES


@dataclasses.dataclass(frozen=True)
class LaunchEstimate:
    """Bytes resident in VMEM for one engine launch (one grid program)."""

    launch: str        # block_fwd | gz_recompute | dx_adjoint | wgrad | core
    operand_bytes: int  # double-buffered windows + single-buffered mats
    scratch_bytes: int  # declared VMEM accumulators

    @property
    def total_bytes(self) -> int:
        return self.operand_bytes + self.scratch_bytes


def _isz(dtype: str) -> int:
    return jnp.dtype(dtype).itemsize


def _prod(xs: Sequence[int]) -> int:
    return int(math.prod(xs))


def _tiled(shape: Sequence[int], dtype: str) -> int:
    """Bytes of one VMEM buffer as Mosaic lays it out: the minor dim
    padded to 128 lanes, the second-minor to the dtype's sublane tile
    (8 rows of 32-bit, 16 of 16-bit)."""
    isz = _isz(dtype)
    shape = (1,) * max(0, 2 - len(shape)) + tuple(int(d) for d in shape)
    sub = 8 * max(1, 4 // isz)
    lead = _prod(shape[:-2])
    return (lead * (-(-shape[-2] // sub) * sub)
            * (-(-shape[-1] // 128) * 128) * isz)


def _mats_bytes(mats) -> int:
    return sum(_tiled(m.shape, m.dtype.name) for m in mats)


def resolve_blocks(rank: int, b: int, h: int, o: int,
                   bb: int = 0, bo: int = 0, bh: int = 0
                   ) -> Tuple[int, int, int]:
    """The (bb, bo, bh) the ops layer would pick: per-rank defaults from
    the block-size table, shrunk to the (8-aligned) actual dims."""
    from repro.tuning.plans import _resolve_blocks, legal_blocks
    return legal_blocks(rank, b, o, h,
                        *_resolve_blocks(rank, bb, bo, bh))


def _rev_modes(modes: Sequence[int]) -> Tuple[int, ...]:
    """Accumulator-order spectral extents, with the rank-1 lane-alignment
    pad (``spectral._mode_pad``) applied."""
    from repro.core import spectral
    kp = spectral._mode_pad(modes)
    return (kp,) if len(modes) == 1 else tuple(reversed(modes))


def _weight_windows(rev, bo, bh, per_mode, dtype: str) -> int:
    """Bytes of the forward kernel's weight windows, double-buffered: the
    packed [2bo, 2bh] shared window, or the per-mode pair — [bh, bo,
    K_1, K_2] where the CGEMM runs on the VPU, else [∏K, bo, bh]."""
    if per_mode and _vpu(rev):
        return 2 * 2 * _tiled((bh, bo) + tuple(reversed(rev)), dtype)
    if per_mode:
        return 2 * 2 * _tiled((_prod(rev), bo, bh), dtype)
    return 2 * _tiled((2 * bo, 2 * bh), dtype)


def _fwd_acc_bytes(rev, bb, bo, per_mode, dtype: str) -> int:
    """The forward kernel's CGEMM accumulators: the per-mode pair
    [bb, bo, K_1, K_2] (VPU) or [∏K, bb, bo], or the one realified shared
    [bb·∏K, 2bo]."""
    if per_mode and _vpu(rev):
        return 2 * _tiled((bb, bo) + tuple(reversed(rev)), dtype)
    if per_mode:
        return 2 * _tiled((_prod(rev), bb, bo), dtype)
    return _tiled((bb * _prod(rev), 2 * bo), dtype)


def _vpu(rev) -> bool:
    from repro.kernels.engine import vpu_cgemm
    return vpu_cgemm(len(rev), tuple(reversed(rev)))


def _kernel_shapes(spatial, modes):
    """(spatial, accumulator-order modes) of the forward kernel family:
    ``spectral.kernel_layout``, with the rank-1 lane pad."""
    from repro.core import spectral

    sp, km = spectral.kernel_layout(spatial, modes)
    return sp, (_rev_modes(modes) if len(modes) == 1
                else tuple(reversed(km)))


def _fused_call_estimate(launch: str, spatial, modes, bb, bo, bh, per_mode,
                         pol: PrecisionPolicy, *, with_epilogue: bool,
                         with_gy: bool, out_dtype: Optional[str] = None,
                         adjoint: bool = False) -> LaunchEstimate:
    """One ``engine.fused_fnond_call`` program, block epilogue included."""
    from repro.core import spectral

    cd, ad = pol.compute_dtype, pol.accum_dtype
    kp = spectral._mode_pad(modes)
    sp, rev = _kernel_shapes(spatial, modes)
    mats = spectral.realified_operand_mats(tuple(spatial), tuple(modes),
                                           pol.spectral_dtype, adjoint, kp)

    operands = 2 * _tiled((bb, bh) + sp, cd)               # x window
    operands += _weight_windows(rev, bo, bh, per_mode, cd)  # spectral W
    operands += _mats_bytes(mats)                          # constant mats
    operands += 2 * _tiled((bb, bo) + sp, out_dtype or cd)  # y window
    if with_epilogue:
        operands += 2 * _tiled((bo, bh), cd)               # wb window
        if not adjoint:
            operands += 2 * _tiled((bo, 1), cd)            # bias window
    if with_gy:
        operands += 2 * _tiled((bb, bo) + sp, cd)          # gy window

    scratch = _fwd_acc_bytes(rev, bb, bo, per_mode, ad)    # CGEMM acc
    if with_epilogue:
        scratch += _tiled((bo, bb) + sp, ad)               # bypass acc
    return LaunchEstimate(launch, operands, scratch)


def _core_call_estimate(spatial, modes, bb, bo, bh, per_mode,
                        pol: PrecisionPolicy) -> LaunchEstimate:
    """One partial-fusion middle program (``fused_fnond_core_call``)."""
    from repro.core import spectral

    r = len(modes)
    sd, ad = pol.spectral_dtype, pol.accum_dtype
    nx = spatial[0]
    spec = tuple(reversed(modes[1:]))  # K_R .. K_2
    mats = spectral.fused_operand_mats(tuple(spatial), tuple(modes),
                                       pol.spectral_dtype)
    kx = int(mats[2 * r - 2].shape[1])
    core_mats = mats[2 * r - 2:2 * r + 2]
    kf = _prod(modes)
    w = (kf, bo, bh) if per_mode else (bo, bh)
    y = spec + (bb, bo, nx) if per_mode else (bb,) + spec + (bo, nx)
    acc = (kf, bb, bo) if per_mode else (bb,) + spec + (kx, bo)

    operands = 2 * 2 * _tiled((bb, bh, nx) + spec, sd)     # zr + zi
    operands += 2 * 2 * _tiled(w, sd)                      # wr + wi
    operands += _mats_bytes(core_mats)                     # f/g pairs
    operands += 2 * 2 * _tiled(y, sd)                      # yr + yi
    scratch = 2 * _tiled(acc, ad)
    return LaunchEstimate("core", operands, scratch)


def _wgrad_estimate(spatial, modes, bb, bo, bh, per_mode,
                    pol: PrecisionPolicy, *,
                    with_bypass: bool) -> LaunchEstimate:
    """One fused weight-gradient program (``fused_fnond_wgrad_call``)."""
    from repro.core import spectral

    cd, ad, pd = pol.compute_dtype, pol.accum_dtype, pol.param_dtype
    sp = tuple(spatial)
    kp = spectral._mode_pad(modes)
    rev = _rev_modes(modes)
    mats = spectral.wgrad_operand_mats(sp, tuple(modes),
                                       pol.spectral_dtype, kp)
    dw = ((_prod(rev),) if per_mode else ()) + (bo, bh)    # dW window

    operands = 2 * _tiled((bb, bh) + sp, cd)               # x window
    operands += 2 * _tiled((bb, bo) + sp, cd)              # gz window
    operands += _mats_bytes(mats)
    operands += 2 * 2 * _tiled(dw, pd)                     # dwr + dwi
    scratch = 2 * _tiled(dw, ad)
    if with_bypass:
        operands += 2 * (_tiled((bo, bh), pd)              # dwb + dbias
                         + _tiled((bo, 1), pd))
        scratch += _tiled((bo, bh), ad) + _tiled((bo, 1), ad)
    return LaunchEstimate("wgrad", operands, scratch)


def _rup8(v: int) -> int:
    return -(-int(v) // 8) * 8


def ends_launch_estimate(cfg: FNOConfig, *, batch: int = 8,
                         policy: Optional[PrecisionPolicy] = None,
                         plans=None) -> LaunchEstimate:
    """The ends-fused forward launch — ``engine.fused_fnond_call`` with
    the lifting MLP folded in as a k==0 prologue and the projection MLP
    as an output epilogue (``cfg.fuse_ends``). Models the worst case
    (both ends on one launch — the 1-layer shape; a lift-only first
    layer or proj-only last layer is strictly smaller). Differences vs
    ``block_fwd``:

      * the x window carries raw ``in_channels`` (8-padded) instead of
        hidden, and the y window carries 8-padded ``out_channels``;
      * bo is PINNED to the 8-padded hidden — the projection epilogue
        contracts the full post-activation hidden vector, so the o-grid
        collapses to one step;
      * the l2 lift window rides the k-grid (double-buffered ×2); the l1
        and projection operands use constant index maps (×1);
      * one extra scratch buffer: the lifted activation ``acca``
        [lift_p, bb, *spatial] persisting across the k-loop. This term
        scales with lift×spatial and dominates at full size (fno2d at
        bb=1 still pays 12.5 MiB of scratch; fno3d's 64³ grid needs
        129 MiB and does NOT fit) — fuse_ends is a small-spatial-extent
        optimisation until the lift prologue learns to spatial-block,
        which is why no full-size preset enables it.

    Backward adds no launches: the ends-fused block's VJP re-stages the
    composition, so this forward launch is the only one the flag adds.
    ``check_vmem`` includes it (via ``block_launch_estimates``) exactly
    when the config opts into fuse_ends."""
    from repro import tuning
    from repro.core import spectral
    from repro.tuning.plans import legal_blocks

    h, spatial, modes, per_mode, pol = _norm_shapes(cfg, policy)
    r = len(modes)
    if plans is None:
        plans = tuning.resolve_launch_plans(
            r, hidden=h, spatial=spatial, modes=modes, per_mode=per_mode,
            policy=pol, override=cfg.block_plan)
    bb, _, bh = legal_blocks(r, batch, h, h,
                            *plans.for_launch("block_fwd"))
    op_ = _rup8(h)                        # bo pinned: single o-grid step
    cinp = _rup8(cfg.in_channels)
    lp = _rup8(cfg.lifting_dim or 2 * h)
    coutp = _rup8(cfg.out_channels)

    cd, ad = pol.compute_dtype, pol.accum_dtype
    kp = spectral._mode_pad(modes)
    sp, rev = _kernel_shapes(spatial, modes)
    mats = spectral.realified_operand_mats(tuple(spatial), tuple(modes),
                                           pol.spectral_dtype, False, kp)

    operands = 2 * _tiled((bb, cinp) + sp, cd)             # raw-x window
    operands += _weight_windows(rev, op_, bh, per_mode, cd)  # spectral W
    operands += _mats_bytes(mats)                          # constant mats
    operands += 2 * _tiled((bb, coutp) + sp, cd)           # y window
    operands += 2 * (_tiled((op_, bh), cd)                 # wb + bias
                     + _tiled((op_, 1), cd))
    operands += _tiled((lp, cinp), cd) + _tiled((lp, 1), cd)  # l1 (const)
    operands += 2 * (_tiled((bh, lp), cd)                  # l2 (k-grid)
                     + _tiled((bh, 1), cd))
    operands += (_tiled((lp, op_), cd) + _tiled((lp, 1), cd)  # proj
                 + _tiled((coutp, lp), cd) + _tiled((coutp, 1), cd))

    scratch = _fwd_acc_bytes(rev, bb, op_, per_mode, ad)   # CGEMM acc
    scratch += _tiled((op_, bb) + sp, ad)                  # bypass acc
    scratch += _tiled((lp, bb) + sp, ad)                   # acca (lift)
    return LaunchEstimate("block_fwd_ends", operands, scratch)


def _norm_shapes(cfg_or_shapes, policy):
    """(hidden, spatial, modes, per_mode, policy) from an FNOConfig or a
    ``(hidden, spatial, modes, per_mode)`` tuple."""
    if isinstance(cfg_or_shapes, FNOConfig):
        cfg = cfg_or_shapes
        return (cfg.hidden, tuple(cfg.spatial), cfg.kept_modes,
                cfg.weight_mode == "per_mode", policy or cfg.precision)
    h, spatial, modes, per_mode = cfg_or_shapes
    return (int(h), tuple(spatial), tuple(modes), bool(per_mode),
            policy or PrecisionPolicy())


def launch_estimate(cfg_or_shapes, launch: str,
                    triple: Tuple[int, int, int], *, batch: int = 8,
                    policy: Optional[PrecisionPolicy] = None
                    ) -> LaunchEstimate:
    """Estimate ONE launch kind under an explicit (bb, bo, bh) block
    preference — the autotuner's pruning oracle and the cache staleness
    re-check. The preference is clamped to the actual dims exactly like
    the ops layer does at call time (``plans.legal_blocks``). dx_adjoint
    runs with hidden/out swapped in the real kernel; o == h throughout
    this repo's FNO stacks, so the unswapped estimate is exact."""
    from repro.tuning.plans import legal_blocks

    h, spatial, modes, per_mode, pol = _norm_shapes(cfg_or_shapes, policy)
    bb, bo, bh = legal_blocks(len(modes), batch, h, h, *triple)
    if launch == "core":
        return _core_call_estimate(spatial, modes, bb, bo, bh, per_mode,
                                   pol)
    if launch == "wgrad":
        return _wgrad_estimate(spatial, modes, bb, bo, bh, per_mode, pol,
                               with_bypass=True)
    if launch == "block_fwd":
        return _fused_call_estimate(
            "block_fwd", spatial, modes, bb, bo, bh, per_mode, pol,
            with_epilogue=True, with_gy=False)
    if launch == "gz_recompute":
        return _fused_call_estimate(
            "gz_recompute", spatial, modes, bb, bo, bh, per_mode, pol,
            with_epilogue=True, with_gy=True)
    if launch == "dx_adjoint":
        return _fused_call_estimate(
            "dx_adjoint", spatial, modes, bb, bo, bh, per_mode, pol,
            with_epilogue=True, with_gy=False, adjoint=True)
    raise ValueError(f"unknown launch kind {launch!r}")


def block_launch_estimates(cfg_or_shapes, *, variant: str = "full",
                           batch: int = 8,
                           policy: Optional[PrecisionPolicy] = None,
                           plans=None) -> Dict[str, LaunchEstimate]:
    """Per-launch VMEM estimates for one fused FNO block's full training
    step (forward + the three backward kernels).

    Accepts an ``FNOConfig`` (hidden/modes/spatial/weight_mode read off
    it) or a ``(hidden, spatial, modes, per_mode)`` tuple. ``plans``
    (a ``tuning.LaunchPlans``) pins the block preferences explicitly;
    None resolves them the same way the ops layer will at call time —
    tuned cache first, static defaults as fallback.
    """
    from repro import tuning

    h, spatial, modes, per_mode, pol = _norm_shapes(cfg_or_shapes, policy)
    r = len(modes)
    if plans is None:
        override = (cfg_or_shapes.block_plan
                    if isinstance(cfg_or_shapes, FNOConfig) else None)
        plans = tuning.resolve_launch_plans(
            r, hidden=h, spatial=spatial, modes=modes, per_mode=per_mode,
            policy=pol, override=override)
    shapes = (h, spatial, modes, per_mode)
    one = lambda launch: launch_estimate(shapes, launch,
                                         plans.for_launch(launch),
                                         batch=batch, policy=pol)
    full = variant == "full" or r == 1

    est: Dict[str, LaunchEstimate] = {}
    if full:
        est["block_fwd"] = one("block_fwd")
    else:
        est["core"] = one("core")
    if isinstance(cfg_or_shapes, FNOConfig) and cfg_or_shapes.fuse_ends:
        # The ends-fused first/last-layer launch (worst case: both ends).
        est["block_fwd_ends"] = ends_launch_estimate(
            cfg_or_shapes, batch=batch, policy=pol, plans=plans)
    # Backward is always the fully fused adjoint (one linear map serves
    # both variants — ops._fno_block_vjp_bwd).
    est["gz_recompute"] = one("gz_recompute")
    est["dx_adjoint"] = one("dx_adjoint")
    est["wgrad"] = one("wgrad")
    return est


def check_vmem(configs=None, dtypes: Sequence[str] = ("f32", "bf16"),
               variants: Sequence[str] = ("full", "partial"),
               budget: int = VMEM_BUDGET_BYTES) -> List[Finding]:
    """Estimate every engine launch of the given configs against the VMEM
    budget, at the plans the ops layer would actually resolve (tuned
    cache → defaults). configs: FNOConfigs or legacy (cfg, must_fit)
    pairs — every config must fit now (error severity): since the
    autotuner landed, a full-size config over budget means the committed
    cache lost coverage, not an accepted limitation. Defaults to all FNO
    archs at reduced AND full size."""
    from repro.configs import FNO_IDS, get_config

    if configs is None:
        configs = [get_config(a, reduced=True) for a in FNO_IDS]
        configs += [get_config(a, reduced=False) for a in FNO_IDS]

    findings: List[Finding] = []
    for entry in configs:
        cfg = entry[0] if isinstance(entry, tuple) else entry
        for dtype in dtypes:
            pol = PrecisionPolicy.from_name(dtype)
            for variant in variants:
                ests = block_launch_estimates(cfg, variant=variant,
                                              policy=pol)
                for name, e in ests.items():
                    if e.total_bytes <= budget:
                        continue
                    findings.append(Finding(
                        "vmem-budget",
                        f"{cfg.name}/{variant}/{dtype}/{name}",
                        f"estimated {e.total_bytes / 2**20:.1f} MiB VMEM "
                        f"per program ({e.operand_bytes / 2**20:.1f} operand"
                        f" + {e.scratch_bytes / 2**20:.1f} scratch) exceeds "
                        f"the {budget / 2**20:.0f} MiB budget — no tuned "
                        f"plan covers this shape class; regenerate the "
                        f"cache (scripts/autotune.py) or shrink (bb,bo,bh)"))
    return findings
