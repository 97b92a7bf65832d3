"""FNO configurations — the paper's own architecture (TurboFNO's target).

``fno1d``/``fno2d`` match the paper's evaluated sizes: signal lengths
N1=128 / N2=256 (Table 1), truncation ratios 25% and 50% (Sec. 3.1), hidden
dims 32-128 (Sec. 5). ``fno2d-large`` is the end-to-end training target
(~100M params with per-mode weights). ``fno3d`` is the Navier–Stokes-class
rank-3 workload (Li et al. 2020 §5.3 uses 64³ grids; we keep the same 25%
per-axis truncation) running on the rank-generic fused engine; it has no
published source. ``fno3d-ns`` is Li et al.'s published FNO-3D for
Navier–Stokes at its own widths: per-mode weights on the ± corners, ReLU,
an unactivated last block and a one-layer lift.
"""
import dataclasses

from repro.configs.base import FNOConfig, PrecisionPolicy

ARCH_ID_1D = "fno1d"
ARCH_ID_2D = "fno2d"
ARCH_ID_3D = "fno3d"
ARCH_ID_3D_NS = "fno3d-ns"


def with_precision(cfg: FNOConfig, dtype: str) -> FNOConfig:
    """Apply a ``--dtype`` preset ("f32"/"bf16") to an FNO config.

    The resolved :class:`PrecisionPolicy` travels inside the config, so
    every downstream layer (init, apply, fused kernels, train step,
    roofline byte model) sees the same policy object.
    """
    pol = PrecisionPolicy.from_name(dtype)
    return dataclasses.replace(cfg, dtype=pol.compute_dtype, policy=pol)


def with_fuse_block(cfg: FNOConfig, on: bool = True) -> FNOConfig:
    """Toggle whole-block fusion: on the pallas path each FNO layer
    (spectral + 1×1 bypass + bias + GELU) lowers to ONE pallas_call
    (``kernels/ops.fno_block_nd``) instead of a fused spectral kernel plus
    ~4 XLA epilogue ops. Composes with :func:`with_precision`."""
    return dataclasses.replace(cfg, fuse_block=on)


def with_tp_layout(cfg: FNOConfig, layout: str,
                   overlap: bool = False) -> FNOConfig:
    """Pick the TP inter-layer collective layout: "scatter" (the default —
    each interior layer's psum_scatter emits the next layer's hidden shard,
    half the collective bytes) or "psum" (the PR-5 all-reduce-every-layer
    layout, kept as the parity/fallback layout). overlap=True additionally
    runs the interior reduce-scatter as a ppermute ring so XLA can hide
    the chunk hops under k-loop compute (scattered layout only)."""
    return dataclasses.replace(cfg, tp_layout=layout, tp_overlap=overlap)


def with_fuse_ends(cfg: FNOConfig, on: bool = True) -> FNOConfig:
    """Fold the lifting MLP into the first fused block kernel and the
    projection MLP into the last one (pallas path with fuse_block; ignored
    under TP — see DESIGN.md §6)."""
    return dataclasses.replace(cfg, fuse_ends=on)


def with_block_plan(cfg: FNOConfig, bb: int, bo: int, bh: int) -> FNOConfig:
    """Pin an explicit (bb, bo, bh) launch plan, overriding the tuned
    cache (``repro.tuning``) component-wise — a component of 0 keeps the
    resolved value. Composes with :func:`with_precision` /
    :func:`with_fuse_block`."""
    return dataclasses.replace(cfg, block_plan=(bb, bo, bh))


def fno1d() -> FNOConfig:
    return FNOConfig(
        name="fno1d", ndim=1, hidden=64, num_layers=4,
        in_channels=1, out_channels=1,
        spatial=(256,), modes=(64,),  # 50% of N/2+1 ~ paper's k=64 @ N=256
        weight_mode="shared",
    )


def fno2d() -> FNOConfig:
    return FNOConfig(
        name="fno2d", ndim=2, hidden=64, num_layers=4,
        in_channels=3, out_channels=1,  # (a(x,y), x, y) -> u(x,y)
        spatial=(128, 128), modes=(32, 32),  # 50% truncation per axis
        weight_mode="shared",
    )


def fno2d_large() -> FNOConfig:
    """~100M-param per-mode FNO for the end-to-end training example."""
    return FNOConfig(
        name="fno2d-large", ndim=2, hidden=128, num_layers=4,
        in_channels=3, out_channels=1,
        spatial=(128, 128), modes=(32, 32),
        weight_mode="per_mode",
    )


def fno3d() -> FNOConfig:
    """Rank-3 spectral operator (3D diffusion / Navier–Stokes substrate)."""
    return FNOConfig(
        name="fno3d", ndim=3, hidden=32, num_layers=4,
        in_channels=1, out_channels=1,
        spatial=(64, 64, 64), modes=(16, 16, 16),  # 25%/axis truncation
        weight_mode="shared",
    )


def fno3d_ns() -> FNOConfig:
    """Li et al. 2020 (arXiv:2010.08895) §5.3, FNO-3D for Navier–Stokes at
    ν = 1e-3: ten observed vorticity frames and the (x, y, t) grids on a
    64×64 torus × 40 output steps -> the 40 next frames. fc0 13 -> 20,
    four blocks of width 20 with per-mode weights on the ± corners of 8
    modes (16·16·8 kept bins), ReLU after all but the last, fc1 20 -> 128,
    ReLU, fc2 128 -> 1. The paper's eval-mode BatchNorm folds into each
    block's weights and bias and is left out."""
    return FNOConfig(
        name="fno3d-ns", ndim=3, hidden=20, num_layers=4,
        in_channels=13, out_channels=1,
        spatial=(64, 64, 40), modes=(8, 8, 8),
        weight_mode="per_mode", lifting_dim=128, truncation="corners",
        activation="relu", act_last=False, lift_layers=1,
    )


def reduced_1d() -> FNOConfig:
    import dataclasses
    return dataclasses.replace(
        fno1d(), hidden=16, num_layers=2, spatial=(64,), modes=(16,))


def reduced_2d() -> FNOConfig:
    import dataclasses
    return dataclasses.replace(
        fno2d(), hidden=16, num_layers=2, spatial=(32, 32), modes=(8, 8))


def reduced_3d() -> FNOConfig:
    import dataclasses
    return dataclasses.replace(
        fno3d(), hidden=8, num_layers=2, spatial=(16, 16, 16),
        modes=(4, 4, 4))


def reduced_3d_ns() -> FNOConfig:
    """fno3d-ns cut for the CPU: the merged trailing axis keeps 8·16 bins,
    so the per-mode CGEMM takes the same VPU form as at full size."""
    import dataclasses
    return dataclasses.replace(
        fno3d_ns(), hidden=8, num_layers=2, spatial=(16, 16, 32),
        modes=(4, 4, 16), lifting_dim=16)
