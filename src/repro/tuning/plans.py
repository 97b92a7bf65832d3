"""Tuning keys and launch plans — the vocabulary of the block autotuner.

A *plan* is a (bb, bo, bh) block-size preference for ONE engine launch
kind; a :class:`LaunchPlans` bundles the five per-launch plans a fused
FNO block's training step needs and travels through the custom_vjps as a
single hashable nondiff argument. Plans are *preferences*: they are
clamped to the actual dims at call time by :func:`legal_blocks`, the one
rule the ops layer, the tuner and the VMEM estimator share. That is why
the tuning key classes shapes by power-of-two buckets and excludes the
batch size entirely.

Key schema (docs/DESIGN.md §8)::

    r{rank}/{shape_class}/{layout}/{variant}/{dtype}/{launch}
    e.g.  r2/h64-s128x128-m32x32/shared/full/bf16/block_fwd

* ``shape_class`` — hidden (and out, only when it differs), spatial and
  modes extents each rounded UP to the next power of two.
* ``layout`` — "shared" | "per_mode" weight layout.
* ``variant`` — normalized per launch (``launch_variant``): the backward
  launches always key as "full" because the backward pipeline is the
  fully fused adjoint regardless of the forward variant; "core" is the
  partial-fusion middle, so it always keys as "partial".
* ``dtype`` — the policy's compute dtype ("f32"/"bf16"; other dtypes use
  their canonical jnp name).
* ``launch`` — one of :data:`LAUNCH_KINDS`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

Triple = Tuple[int, int, int]

LAUNCH_KINDS = ("block_fwd", "core", "gz_recompute", "dx_adjoint", "wgrad")

# The fusion variant each launch kind belongs to in a cache key. Backward
# launches normalize to "full" (one adjoint serves both variants —
# ops._fno_block_vjp_bwd); the partial-fusion middle is the only
# partial-variant kernel with tunable blocks (the outer DFT stages are
# row-blocked standalone kernels outside this tuner's scope).
_LAUNCH_VARIANT = {"block_fwd": "full", "core": "partial",
                   "gz_recompute": "full", "dx_adjoint": "full",
                   "wgrad": "full"}

_DTYPE_TAGS = {"float32": "f32", "bfloat16": "bf16"}


def launch_variant(launch: str) -> str:
    """The normalized variant a launch kind keys under."""
    return _LAUNCH_VARIANT[launch]


def dtype_tag(compute_dtype: str) -> str:
    """Short dtype tag for keys ("float32" → "f32")."""
    return _DTYPE_TAGS.get(compute_dtype, compute_dtype)


def _p2(n: int) -> int:
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


def shape_class(hidden: int, out: int, spatial: Sequence[int],
                modes: Sequence[int]) -> str:
    """Power-of-two shape bucket: plans transfer across nearby shapes, so
    keys class (hidden, spatial, modes) by next-pow2 and omit ``out`` when
    it equals ``hidden`` (the universal case in this repo's FNO stacks).
    Batch is deliberately absent — bb is clamped at call time."""
    parts = [f"h{_p2(hidden)}"]
    if out != hidden:
        parts.append(f"o{_p2(out)}")
    parts.append("s" + "x".join(str(_p2(s)) for s in spatial))
    parts.append("m" + "x".join(str(_p2(m)) for m in modes))
    return "-".join(parts)


def plan_key(rank: int, klass: str, layout: str, dtype: str,
             launch: str) -> str:
    """Format one cache key (the variant segment derives from launch)."""
    return (f"r{rank}/{klass}/{layout}/{launch_variant(launch)}/"
            f"{dtype}/{launch}")


def parse_key(key: str) -> dict:
    """Parse + validate a cache key; raises ValueError with the defect."""
    parts = key.split("/")
    if len(parts) != 6:
        raise ValueError(f"want 6 '/'-separated segments, got {len(parts)}")
    r, klass, layout, variant, dtype, launch = parts
    if not (r.startswith("r") and r[1:].isdigit() and int(r[1:]) in (1, 2, 3)):
        raise ValueError(f"bad rank segment {r!r}")
    if layout not in ("shared", "per_mode"):
        raise ValueError(f"bad layout segment {layout!r}")
    if launch not in LAUNCH_KINDS:
        raise ValueError(f"unknown launch kind {launch!r}")
    if variant != launch_variant(launch):
        raise ValueError(f"variant {variant!r} inconsistent with launch "
                         f"{launch!r} (want {launch_variant(launch)!r})")
    return {"rank": int(r[1:]), "shape_class": klass, "layout": layout,
            "variant": variant, "dtype": dtype, "launch": launch}


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """One resolved (bb, bo, bh) preference plus its provenance."""

    bb: int
    bo: int
    bh: int
    source: str = "default"  # override | cache | default
    key: str = ""            # the cache key it resolved under

    @property
    def triple(self) -> Triple:
        return (self.bb, self.bo, self.bh)


@dataclasses.dataclass(frozen=True)
class LaunchPlans:
    """The five per-launch (bb, bo, bh) preferences one fused FNO block
    carries through its custom_vjp (a single hashable nondiff argument —
    plain int triples only, so equal plans share jit cache entries).

    ``fwd`` drives the full-variant forward (and the spectral-layer-only
    forward, which is the same kernel minus the epilogue operands);
    ``core`` the partial-fusion middle (== ``fwd`` at rank 1, where
    partial degenerates to full); ``gz``/``dx``/``wgrad`` the three
    backward kernels."""

    fwd: Triple
    core: Triple
    gz: Triple
    dx: Triple
    wgrad: Triple

    _FIELD = {"block_fwd": "fwd", "core": "core", "gz_recompute": "gz",
              "dx_adjoint": "dx", "wgrad": "wgrad"}

    @classmethod
    def uniform(cls, triple: Sequence[int]) -> "LaunchPlans":
        t = tuple(int(v) for v in triple)
        return cls(t, t, t, t, t)

    def for_launch(self, launch: str) -> Triple:
        return getattr(self, self._FIELD[launch])

    def with_override(self, bb: int = 0, bo: int = 0,
                      bh: int = 0) -> "LaunchPlans":
        """Apply explicit nonzero components over every launch's plan
        (the public bb/bo/bh=0 'use resolved' contract)."""
        if not (bb or bo or bh):
            return self
        ov = lambda t: (bb or t[0], bo or t[1], bh or t[2])
        return LaunchPlans(ov(self.fwd), ov(self.core), ov(self.gz),
                           ov(self.dx), ov(self.wgrad))


def normalize_override(override: Optional[Sequence[int]]) -> Triple:
    """Canonicalize a user override (None | (bb, bo, bh) with 0 = keep
    resolved) to a concrete triple of ints."""
    if override is None:
        return (0, 0, 0)
    t = tuple(int(v) for v in override)
    if len(t) != 3 or any(v < 0 for v in t):
        raise ValueError(f"block plan override must be 3 non-negative "
                         f"ints, got {override!r}")
    return t


# ---------------------------------------------------------------------------
# The plan rules: the static fallback and the clamp every launch runs under.
# ---------------------------------------------------------------------------
# Per-rank (bb, bo, bh) kernel block-size defaults — the documented
# FALLBACK when no tuned cache entry matches a workload's tuning key.
# Block selection is owned by ``repro.tuning.resolve_launch_plans``
# (override → tuned cache → this table); nothing outside the resolver
# and the legacy ``analysis.vmem.resolve_blocks`` helper should read it.
# Ranks ≥ 2 hold one sample's whole spatial slab per program, so their
# batch block is 1.
_BLOCK_DEFAULTS = {1: (8, 128, 128), 2: (1, 128, 32), 3: (1, 128, 16)}


def _resolve_blocks(rank, bb, bo, bh):
    dbb, dbo, dbh = _BLOCK_DEFAULTS[rank]
    return bb or dbb, bo or dbo, bh or dbh


def _rup(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m


@functools.lru_cache(maxsize=None)
def _pick_block(dim: int, pref: int) -> int:
    """Clamp a preferred block size to the actual dim with minimal pad
    waste: among the feasible candidates (8-aligned sizes up to pref, or
    every size up to pref when pref < 8 — batch blocks), pick the one
    whose padded total ``_rup(dim, b)`` is smallest, breaking ties toward
    the larger block (fewer grid steps). This keeps prime/odd extents
    from forcing near-2× padding — e.g. dim=129 under pref=128 pads to
    136 via b=8, not to 256 via b=128 — while exact-fit dims still get
    the largest divisor ≤ pref. A preference equal to the dim is taken as
    it is: a block that spans the whole dim is always legal (Li et al.'s
    width 20 runs as one 20-wide block, not padded to 24)."""
    if pref == dim:
        return dim
    if pref < 8:
        cands = range(1, pref + 1)
    else:
        cands = range(8, max(8, min(pref, _rup(dim, 8))) + 1, 8)
    return min(cands, key=lambda b: (_rup(dim, b), -b))


def legal_blocks(rank: int, b: int, o: int, h: int, bb: int, bo: int,
                 bh: int) -> Triple:
    """The (bb, bo, bh) every engine launch of a rank-R layer runs for
    dims (B, O, H) under a block preference — the one clamp the ops
    layer, the tuner and the VMEM estimator share. Mosaic tiles the last
    two dims of every block by (8, 128) unless the block spans the whole
    array dim. bo is the second-minor dim of the weight and bias windows,
    so it is a multiple of 8 (O is padded to it). bh is a free k-loop tile
    (the engine lays weights out hidden-blocked), except at rank 1, where
    it is the second-minor dim of the [bb, bh, N] activation window."""
    bh = _pick_block(h, max(bh, 8) if rank == 1 else bh)
    return _pick_block(b, bb), _pick_block(o, max(bo, 8)), bh


def tiling_legal(rank: int, o: int, h: int, bo: int, bh: int) -> bool:
    """Whether (bo, bh) satisfies the tiling rule for a rank-R layer with
    dims (O, H) — see :func:`legal_blocks`."""
    return ((bo % 8 == 0 or bo == o)
            and (rank > 1 or bh % 8 == 0 or bh == h))


# The end_mlp launch's window: the grid step's input and output windows,
# double-buffered, are held under this many bytes. A window of fno3d-ns's
# projection (20 + 1 channels × 64 rows × 128 lanes) takes 1.4 MB, one of
# fno2d's lift (3 + 64 channels × 128 rows × 128 lanes) 8.8 MB; a larger
# step only shares the per-step cost among more points.
END_MLP_WINDOW_BYTES = 16 * 1024 * 1024


def end_mlp_window(spatial: Sequence[int], channels: int,
                   itemsize: int) -> Tuple[int, int]:
    """(rows, lanes) of an end_mlp grid step over a kernel-layout
    activation with trailing axes `spatial` (rank 1 or 2) and `channels`
    = input + output channels. Rank 2: lanes are one 128-lane column
    where the minor axis has whole ones (the kernel gathers its rows by
    strided loads, which Mosaic runs on 128-lane windows), else the whole
    minor axis; rows are all of s_1 when the window fits
    ``END_MLP_WINDOW_BYTES``, else the largest multiple of 8 dividing
    s_1 that does. Rank 1: rows 0 and lanes the largest multiple of 128
    dividing S that fits, or all of S."""
    def largest(n, unit, align):
        if n * unit <= END_MLP_WINDOW_BYTES or n % align:
            return n
        fit = [t for t in range(align, n, align)
               if n % t == 0 and t * unit <= END_MLP_WINDOW_BYTES]
        return max(fit, default=align)

    per_point = 2 * channels * itemsize
    if len(spatial) == 1:
        return 0, largest(spatial[0], per_point, 128)
    s1, s2 = spatial
    lanes = 128 if s2 % 128 == 0 else s2
    return largest(s1, per_point * lanes, 8), lanes
