"""Spectral-transform algebra: truncated DFTs as MXU-friendly matmuls.

TurboFNO's GPU kernels prune FFT butterflies whose outputs land in discarded
frequency bands. The TPU-native equivalent (docs/DESIGN.md §3.2) computes the
truncated transform as a dense matmul with only the *kept* rows of the DFT
matrix — pruning becomes row selection, truncation/zero-padding become the
matrix shapes, and everything runs on the MXU.

Conventions: transforms act on the LAST axis. Complex tensors are carried as
(real, imag) pairs of real arrays.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# DFT matrix factories (host-side numpy; cached; O(N·k) memory)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=64)
def rdft_mats(n: int, modes: int, dtype: str = "float32") -> Tuple[np.ndarray, np.ndarray]:
    """Forward truncated real-input DFT:  X[m] = sum_n x[n]·e^{-2πi mn/N}.

    Returns (Cr, Ci), each [n, modes], so that for real x[..., n]:
        Xr = x @ Cr,   Xi = x @ Ci.
    """
    assert modes <= n // 2 + 1, (n, modes)
    m = np.arange(modes)[None, :]
    k = np.arange(n)[:, None]
    ang = 2.0 * np.pi * k * m / n
    return (np.cos(ang).astype(dtype), (-np.sin(ang)).astype(dtype))


@functools.lru_cache(maxsize=64)
def irdft_mats(n: int, modes: int, dtype: str = "float32") -> Tuple[np.ndarray, np.ndarray]:
    """Inverse of (truncate ∘ rFFT) with implicit zero padding:

        y[j] = (1/N)·Σ_{m<modes} c_m·(Xr[m]·cos(2πmj/N) − Xi[m]·sin(2πmj/N)),

    with hermitian fold c_0 = 1, c_m = 2 (m ≥ 1, m < N/2), c_{N/2} = 1.
    Returns (Er, Ei), each [modes, n]:  y = Xr @ Er − Xi @ Ei.
    Exactly equals jnp.fft.irfft(zero-pad(X), n).
    """
    assert modes <= n // 2 + 1
    m = np.arange(modes)[:, None]
    j = np.arange(n)[None, :]
    ang = 2.0 * np.pi * m * j / n
    c = np.full((modes, 1), 2.0)
    c[0] = 1.0
    if modes == n // 2 + 1 and n % 2 == 0:
        c[-1] = 1.0  # Nyquist bin is its own conjugate
    return ((c * np.cos(ang) / n).astype(dtype), (c * np.sin(ang) / n).astype(dtype))


@functools.lru_cache(maxsize=64)
def cdft_mats(n: int, modes: int, inverse: bool = False,
              dtype: str = "float32") -> Tuple[np.ndarray, np.ndarray]:
    """Complex truncated DFT matrix.

    forward: F[k, m] = e^{-2πi km/N},  [n, modes]   (keep first `modes` rows)
    inverse: E[m, j] = e^{+2πi mj/N}/N, [modes, n]  (zero-pad implicit)

    NOTE (paper-faithful): TurboFNO keeps only the FIRST dimX fraction of the
    complex axis — positive low frequencies only, no hermitian pair. The
    truncate→pad→inverse round trip is therefore a projection, not identity
    (classic FNO keeps ± corners instead; see docs/DESIGN.md §3.4).
    """
    if not inverse:
        k = np.arange(n)[:, None]
        m = np.arange(modes)[None, :]
        ang = 2.0 * np.pi * k * m / n
        return (np.cos(ang).astype(dtype), (-np.sin(ang)).astype(dtype))
    m = np.arange(modes)[:, None]
    j = np.arange(n)[None, :]
    ang = 2.0 * np.pi * m * j / n
    return ((np.cos(ang) / n).astype(dtype), (np.sin(ang) / n).astype(dtype))


# ---------------------------------------------------------------------------
# Adjoint (transposed) factories — the backward fused pipeline.
#
# The spectral layer is y = Re(((x·C)∘W)·E): a real-linear map whose matrix
# entries are Re(C[n,m]·W[o,h,m]·E[m,j]). Its adjoint w.r.t. x is therefore
# the SAME fused DFT→CGEMM→iDFT pipeline with every DFT operand transposed
# (no conjugation needed — conjugating all factors at once leaves the real
# part unchanged) and the weight transposed over (out, hidden). These
# factories supply the transposed operands in the orientation the fused
# kernels expect.
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=64)
def rdft_adjoint_mats(n: int, modes: int, dtype: str = "float32") -> Tuple[np.ndarray, np.ndarray]:
    """Transposed truncated-rDFT operands, each [modes, n].

    Used as the backward pipeline's *inverse*-slot operand: the input
    cotangent ends with dx = Tr @ Crᵀ − Ti @ Ciᵀ.
    """
    cr, ci = rdft_mats(n, modes, dtype)
    return np.ascontiguousarray(cr.T), np.ascontiguousarray(ci.T)


@functools.lru_cache(maxsize=64)
def irdft_adjoint_mats(n: int, modes: int, dtype: str = "float32") -> Tuple[np.ndarray, np.ndarray]:
    """Transposed padded-irDFT operands, each [n, modes].

    Used as the backward pipeline's *forward*-slot operand: the output
    cotangent g enters the spectral domain as G = g @ Erᵀ + i·(g @ Eiᵀ).
    """
    er, ei = irdft_mats(n, modes, dtype)
    return np.ascontiguousarray(er.T), np.ascontiguousarray(ei.T)


@functools.lru_cache(maxsize=64)
def cdft_adjoint_mats(n: int, modes: int, inverse: bool = False,
                      dtype: str = "float32") -> Tuple[np.ndarray, np.ndarray]:
    """Transposed complex-DFT operands.

    forward transposed: [modes, n] (backward inverse slot);
    inverse transposed: [n, modes] (backward forward slot).
    """
    fr, fi = cdft_mats(n, modes, inverse, dtype)
    return np.ascontiguousarray(fr.T), np.ascontiguousarray(fi.T)


# ---------------------------------------------------------------------------
# Rank-generic fused-kernel operand bundles (cached host constants).
#
# The engine (kernels/engine.py) consumes a flat tuple of (real, imag)
# operand pairs: R forward stages in kernel order (axis s_R first, each
# [n, k]) then R inverse stages (axis s_1 first, each [k, n]). adjoint=True
# swaps every operand for its transpose — the backward input-cotangent
# pipeline (see the adjoint-factory comment above). These are lru_cached on
# (spatial, modes, dtype, adjoint, pad) so repeated layer calls/traces stop
# rebuilding the O(N·K) matrices. They return NUMPY arrays on purpose:
# jnp constants created inside a jit trace are tracers, and caching a
# tracer across traces is a leak — numpy constants are constified safely
# by whichever trace consumes them.
# ---------------------------------------------------------------------------
def _pad_np(a: np.ndarray, axis: int, to: int) -> np.ndarray:
    if a.shape[axis] >= to:
        return a
    cfg = [(0, 0)] * a.ndim
    cfg[axis] = (0, to - a.shape[axis])
    return np.pad(a, cfg)


def _fused_mat_pairs(spatial, modes, adjoint, dtype):
    """numpy (mr, mi) pairs: R forward-slot then R inverse-slot operands."""
    r = len(spatial)
    fwd, inv = [], []
    for i in range(r):  # forward stages transform axes s_R, s_{R-1}, …, s_1
        ax = r - 1 - i
        n, k = spatial[ax], modes[ax]
        if ax == r - 1:  # the real-input axis
            fwd.append(irdft_adjoint_mats(n, k, dtype) if adjoint
                       else rdft_mats(n, k, dtype))
        else:
            fwd.append(cdft_adjoint_mats(n, k, True, dtype) if adjoint
                       else cdft_mats(n, k, False, dtype))
    for ax in range(r):  # inverse stages transform axes s_1, …, s_R
        n, k = spatial[ax], modes[ax]
        if ax == r - 1:
            inv.append(rdft_adjoint_mats(n, k, dtype) if adjoint
                       else irdft_mats(n, k, dtype))
        else:
            inv.append(cdft_adjoint_mats(n, k, False, dtype) if adjoint
                       else cdft_mats(n, k, True, dtype))
    return fwd + inv


@functools.lru_cache(maxsize=256)
def fused_operand_mats(spatial: Tuple[int, ...], modes: Tuple[int, ...],
                       dtype: str = "float32", adjoint: bool = False,
                       pad_modes_to: int = 0) -> Tuple[np.ndarray, ...]:
    """Flat operand tuple for the rank-generic fused forward/adjoint
    kernel: (cr,ci) per forward stage then (er,ei) per inverse stage.

    pad_modes_to zero-pads every modes axis up to the given extent (used by
    the rank-1 path, where K is the minor lane dim and must be
    128-aligned); padded rows/cols contribute exactly zero through the
    linear pipeline.
    """
    r = len(spatial)
    dt = jnp.dtype(dtype)
    out = []
    for idx, (mr, mi) in enumerate(_fused_mat_pairs(spatial, modes, adjoint,
                                                    "float32")):
        if pad_modes_to:
            axis = 1 if idx < r else 0  # fwd [n,k] pads cols; inv [k,n] rows
            mr = _pad_np(mr, axis, pad_modes_to)
            mi = _pad_np(mi, axis, pad_modes_to)
        out.append(np.asarray(mr, dt))
        out.append(np.asarray(mi, dt))
    return tuple(out)


def realified_plan(rank: int) -> Tuple[Tuple[Tuple[int, str], ...],
                                        Tuple[Tuple[int, str], ...]]:
    """Stage order of the forward kernel's realified DFT chains:
    (forward stages, inverse stages), each a tuple of (spatial axis j,
    form). A "lane" stage contracts the block's minor axis with the packed
    operand on the right; a "sublane" stage contracts the second-minor
    axis with the packed operand on the left, batched over the leading
    axes. The first forward stage has a real input and the last inverse
    stage a real output.

    Rank ≥ 2 starts the forward chain and ends the inverse chain on axis
    s_{R-1} as sublane stages, so neither the input block nor the output
    block is transposed inside the kernel; rank 1 has no second-minor
    spatial axis. The separable transforms commute, so any order gives
    the same spectrum and the same output."""
    if rank == 1:
        return ((0, "lane"),), ((0, "lane"),)
    rest = tuple((j, "lane") for j in range(rank - 3, -1, -1))
    fwd = ((rank - 2, "sublane"), (rank - 1, "lane")) + rest
    inv = ((rank - 1, "lane"),) + rest[::-1] + ((rank - 2, "sublane"),)
    return fwd, inv


@functools.lru_cache(maxsize=256)
def realified_operand_mats(spatial: Tuple[int, ...],
                           modes: Tuple[int, ...], dtype: str = "float32",
                           adjoint: bool = False,
                           pad_modes_to: int = 0) -> Tuple[np.ndarray, ...]:
    """The forward kernel's DFT operands with real and imaginary parts
    packed into one real matrix per stage, in ``realified_plan`` order:
    R forward stages, then R inverse stages. A complex row [zr | zi]
    times the packed matrix is the complex product in one matmul:

      * real input (first forward stage): [Mr | Mi], [n, 2k];
      * complex stage: [[Mr, Mi], [−Mi, Mr]], [2n, 2k] forward and
        [2k, 2n] inverse;
      * real output (last inverse stage): [Mr ; −Mi], [2k, n].

    A sublane stage takes its operand on the left, so it gets the
    transpose: [Mr | Mi]ᵀ, [2k, n], and [Mr ; −Mi]ᵀ, [n, 2k]. Built from
    the same (mr, mi) pairs as ``fused_operand_mats`` (adjoint=True gives
    the input-cotangent set), once per shape, on the host; pad_modes_to
    zero-pads each half's modes extent as there."""
    r = len(spatial)
    pairs = []
    for idx, pair in enumerate(_fused_mat_pairs(spatial, modes, adjoint,
                                                "float32")):
        if pad_modes_to:  # fwd [n,k] pads cols; inv [k,n] rows
            pair = tuple(_pad_np(m, 1 if idx < r else 0, pad_modes_to)
                         for m in pair)
        pairs.append(pair)
    dt = jnp.dtype(dtype)
    return tuple(np.ascontiguousarray(m, dt)
                 for m in pack_realified(pairs, r, np))


def pack_realified(pairs, rank: int, xp=np) -> Tuple:
    """Pack (mr, mi) operand pairs — R forward slots ([n, k], axis s_R
    first) then R inverse slots ([k, n], axis s_1 first), the
    ``fused_operand_mats`` order — into the ``realified_operand_mats``
    forms. xp is numpy (host constants) or jax.numpy (operands given as
    arrays)."""
    def block(mr, mi):
        return xp.concatenate([xp.concatenate([mr, mi], 1),
                               xp.concatenate([-mi, mr], 1)], 0)

    fwd_plan, inv_plan = realified_plan(rank)
    out = []
    for i, (j, form) in enumerate(fwd_plan):
        mr, mi = pairs[rank - 1 - j]
        if i == 0:
            m = xp.concatenate([mr, mi], 1)
            m = m.T if form == "sublane" else m
        else:
            m = block(mr, mi)
        out.append(m)
    for i, (j, form) in enumerate(inv_plan):
        mr, mi = pairs[rank + j]
        if i == rank - 1:
            m = xp.concatenate([mr, -mi], 0)
            m = m.T if form == "sublane" else m
        else:
            m = block(mr, mi)
        out.append(m)
    return tuple(out)


@functools.lru_cache(maxsize=256)
def wgrad_operand_mats(spatial: Tuple[int, ...], modes: Tuple[int, ...],
                       dtype: str = "float32",
                       pad_modes_to: int = 0) -> Tuple[np.ndarray, ...]:
    """Flat operand tuple for the fused weight-gradient kernel: R forward
    stages for the primal spectrum A, then R adjoint-forward stages
    (transposed inverse transforms) that push the output cotangent into the
    spectral domain as Ĝ. All [n, k]-oriented, axis s_R first."""
    r = len(spatial)
    dt = jnp.dtype(dtype)
    pairs = (_fused_mat_pairs(spatial, modes, False, "float32")[:r]
             + _fused_mat_pairs(spatial, modes, True, "float32")[:r])
    out = []
    for mr, mi in pairs:
        if pad_modes_to:
            mr = _pad_np(mr, 1, pad_modes_to)
            mi = _pad_np(mi, 1, pad_modes_to)
        out.append(np.asarray(mr, dt))
        out.append(np.asarray(mi, dt))
    return tuple(out)


# ---------------------------------------------------------------------------
# Batched outer-stage operands (partial fusion, rank ≥ 3).
#
# The paper-faithful partial path transforms the outer axes s_2..s_R with
# standalone kernels. Those stages are separable, so their composition is a
# single matmul with the Kronecker product of the per-axis DFT matrices:
# one kernel launch for ALL outer axes instead of one per axis (ROADMAP
# follow-up). Row index = flattened (s_2..s_R) in natural order; column
# index = flattened (k_R..k_2) — the spectrum layout the fused middle
# expects. Built in f32 on host (cast at the call site like every other
# operand), lru_cached, and complex-carried as (real, imag).
# ---------------------------------------------------------------------------
def _kron_ordered(factors):
    """Combine complex per-axis factors F_j[a_j, b_j] (axis order s_2..s_R)
    into M[(a_2..a_R), (b_R..b_2)]."""
    r1 = len(factors)
    subs_in = [f"{chr(97 + 2 * j)}{chr(98 + 2 * j)}" for j in range(r1)]
    rows = "".join(s[0] for s in subs_in)
    cols = "".join(subs_in[j][1] for j in reversed(range(r1)))
    m = np.einsum(",".join(subs_in) + "->" + rows + cols, *factors)
    nr = int(np.prod([f.shape[0] for f in factors]))
    nc = int(np.prod([f.shape[1] for f in factors]))
    return m.reshape(nr, nc)


@functools.lru_cache(maxsize=64)
def outer_fwd_mats(outer_spatial: Tuple[int, ...],
                   outer_modes: Tuple[int, ...],
                   dtype: str = "float32") -> Tuple[np.ndarray, np.ndarray]:
    """Combined forward operand for the outer axes (s_2..s_R): real input,
    truncated spectrum out. [Πn_j, Πk_j], columns ordered (k_R..k_2)."""
    factors = []
    for n, k in zip(outer_spatial, outer_modes):
        fr, fi = cdft_mats(n, k, False, "float64")
        factors.append(fr + 1j * fi)
    m = _kron_ordered(factors)
    return m.real.astype(dtype), m.imag.astype(dtype)


@functools.lru_cache(maxsize=64)
def outer_inv_mats(outer_spatial: Tuple[int, ...],
                   outer_modes: Tuple[int, ...],
                   dtype: str = "float32") -> Tuple[np.ndarray, np.ndarray]:
    """Combined inverse operand for the outer axes: padded complex inverse
    along s_2..s_{R-1} and hermitian-folded real inverse along s_R, real
    output. [Πk_j, Πn_j], rows ordered (k_R..k_2), columns (s_2..s_R);
    consumed as y = Xr@Er − Xi@Ei (only the real part survives)."""
    factors = []
    last = len(outer_spatial) - 1
    for j, (n, k) in enumerate(zip(outer_spatial, outer_modes)):
        er, ei = (irdft_mats(n, k, "float64") if j == last
                  else cdft_mats(n, k, True, "float64"))
        factors.append(er + 1j * ei)
    # _kron_ordered(F_j[a,b]) lays rows out in factor order and columns
    # reversed; feeding the factors reversed (s_R..s_2) therefore yields
    # rows (k_R..k_2) and columns (s_2..s_R).
    m = _kron_ordered(factors[::-1])
    return m.real.astype(dtype), m.imag.astype(dtype)


# ---------------------------------------------------------------------------
# XLA-path transforms (matmul formulation; fused by XLA, no Pallas)
# ---------------------------------------------------------------------------
def truncated_rdft(x: jax.Array, modes: int) -> Tuple[jax.Array, jax.Array]:
    """rFFT along last axis, keeping the first `modes` bins. Real input."""
    n = x.shape[-1]
    cr, ci = rdft_mats(n, modes, "float32")
    cr, ci = jnp.asarray(cr, x.dtype), jnp.asarray(ci, x.dtype)
    f32 = jnp.float32
    return (jax.lax.dot_general(x, cr, (((x.ndim - 1,), (0,)), ((), ())),
                                preferred_element_type=f32),
            jax.lax.dot_general(x, ci, (((x.ndim - 1,), (0,)), ((), ())),
                                preferred_element_type=f32))


def padded_irdft(xr: jax.Array, xi: jax.Array, n: int) -> jax.Array:
    """Inverse rFFT from `modes` kept bins, zero-padded to length n."""
    modes = xr.shape[-1]
    er, ei = irdft_mats(n, modes, "float32")
    er, ei = jnp.asarray(er, xr.dtype), jnp.asarray(ei, xr.dtype)
    dims = (((xr.ndim - 1,), (0,)), ((), ()))
    f32 = jnp.float32
    return (jax.lax.dot_general(xr, er, dims, preferred_element_type=f32)
            - jax.lax.dot_general(xi, ei, dims, preferred_element_type=f32))


def truncated_cdft(xr: jax.Array, xi: jax.Array,
                   modes: int) -> Tuple[jax.Array, jax.Array]:
    """Complex DFT along last axis keeping first `modes` bins."""
    n = xr.shape[-1]
    fr, fi = cdft_mats(n, modes, False, "float32")
    fr, fi = jnp.asarray(fr, xr.dtype), jnp.asarray(fi, xr.dtype)
    dims = (((xr.ndim - 1,), (0,)), ((), ()))
    f32 = jnp.float32
    dot = lambda a, b: jax.lax.dot_general(a, b, dims, preferred_element_type=f32)
    return dot(xr, fr) - dot(xi, fi), dot(xr, fi) + dot(xi, fr)


def padded_icdft(xr: jax.Array, xi: jax.Array, n: int) -> Tuple[jax.Array, jax.Array]:
    """Inverse complex DFT from first-`modes` bins zero-padded to n."""
    modes = xr.shape[-1]
    er, ei = cdft_mats(n, modes, True, "float32")
    er, ei = jnp.asarray(er, xr.dtype), jnp.asarray(ei, xr.dtype)
    dims = (((xr.ndim - 1,), (0,)), ((), ()))
    f32 = jnp.float32
    dot = lambda a, b: jax.lax.dot_general(a, b, dims, preferred_element_type=f32)
    return dot(xr, er) - dot(xi, ei), dot(xr, ei) + dot(xi, er)


# ---------------------------------------------------------------------------
# FLOP accounting (paper Fig. 5 analogue — see benchmarks/bench_prune.py)
# ---------------------------------------------------------------------------
def fft_flops(n: int) -> float:
    """Real-op count of a full radix-2 complex FFT (5 N log2 N convention)."""
    return 5.0 * n * np.log2(n)


def pruned_fft_ops(n: int, modes: int) -> int:
    """Butterfly-output count of a DIF FFT pruned to the first `modes` bins.

    Recursive decimation-in-frequency: the top stage produces an even-bin
    branch (sums) and an odd-bin branch (diffs+twiddles); a branch is computed
    only if it feeds a kept bin. Keeping bins [0, k): evens need ceil(k/2),
    odds need floor(k/2). One "op" = one butterfly output (paper Fig. 5
    counting: full 4-point FFT = 8 ops; k=1 → 3 ops (37.5%); k=2 → 6 (75%)).
    """
    if modes <= 0 or n <= 1:
        return 0
    ke, ko = (modes + 1) // 2, modes // 2
    ops = (n // 2 if ke else 0) + (n // 2 if ko else 0)
    return ops + pruned_fft_ops(n // 2, ke) + pruned_fft_ops(n // 2, ko)


def fft_ops(n: int) -> int:
    """Butterfly-output count of the full FFT (same counting as above)."""
    return int(n * np.log2(n))


def pruned_fft_flops(n: int, modes: int) -> float:
    """Pruned-FFT real-op estimate, scaled to the 5·N·log2(N) convention."""
    return fft_flops(n) * pruned_fft_ops(n, modes) / fft_ops(n)


def truncated_dft_matmul_flops(n: int, modes: int, complex_input: bool) -> float:
    """FLOPs of the MXU truncated-DFT formulation (per signal)."""
    mults = 2 if not complex_input else 4
    return 2.0 * mults * n * modes  # 2 flops per MAC
