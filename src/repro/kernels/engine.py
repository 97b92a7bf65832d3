"""Rank-generic fused truncated-DFT → CGEMM → padded-iDFT Pallas engine.

This is the single home of the paper's core contribution (§4.3) mapped to
TPU, generalized over spatial rank R (1/2/3, and any R the block shapes
fit): every fused launch of every rank is emitted by the factories below,
so every optimization lands exactly once.

Grid and accumulator layout (identical for every rank):

  * grid = (batch tiles, out-channel tiles, hidden tiles) with the HIDDEN
    axis innermost — the FFT "pencils" are selected along the GEMM k-loop
    direction exactly as in paper Fig. 6(c);
  * per program, the truncated forward DFT chain of the x-block is computed
    straight into VMEM registers and consumed as the CGEMM A-tile — the
    shared-memory forwarding of Fig. 7 with no HBM round trip;
  * the inverse DFT chain runs as the CGEMM epilogue on the VMEM
    accumulator — Fig. 8;
  * truncation/zero-padding/pruning are implicit in the DFT operand shapes.

The forward kernel carries each complex tensor as ONE real array whose
real and imaginary parts are the two halves of one axis, and does every
complex contraction as one real MXU matmul against an operand packed on
the host (``spectral.realified_operand_mats``; the shared CGEMM weight
[[wr, −wi], [wi, wr]]): a truncated mode extent of 32 would otherwise
fill a quarter of the 128-wide MXU tile, in 2–4 matmuls per stage. For
rank 2:

    x[bb,bh,s_1,s_2] ─(s_1, operand on the left)→ [bb,bh,re|im K_1,s_2]
      ─(s_2)→ A[bb,bh,K_1,re|im K_2]
    A ·(re|im bh) [[wr,−wi],[wi,wr]]  → acc[bb,K_2,K_1,re|im bo] (shared W)
    acc ─(K_2)→ [bb,bo,K_1,re|im s_2] ─(K_1, on the left)→ y[bb,bo,s_1,s_2]

(``realified_plan`` gives the stage order for any rank.) Neither the x
block nor the y block is transposed in the kernel; the halves move
between axes on the small spectra.

For per-mode weights W[bo,bh,K_1..K_R] the CGEMM is not packed: packing
would double the weight bytes read per step. Where the spectrum's
complex minor axis fills whole 128-lane vregs (``vpu_cgemm``) it runs on
the VPU, four elementwise products per hidden channel over the modes as
the spectrum lays them out (W laid out [H,O,K_1,K_2]), into two
accumulators [bb,bo,K_1,K_2]: with a few dozen channels each mode's
matmul would fill a sliver of an MXU tile, and a [∏K,bo,bh] weight
window would be padded to 128 lanes. Otherwise it stays four real
matmuls batched over the flattened spectral axes (W laid out [∏K,bo,bh]
outside the kernel) into two accumulators [∏K,bb,bo]. Rank 1 pads its
modes to 128 lanes; rank 2 is the full-fusion 2D kernel; rank 3 runs as
rank 2 over its trailing axes merged (``spectral.kernel_layout``).

Three kernel families:

  * ``fused_fnond_call``       — full fusion (whole layer, real in/out);
    with adjoint DFT operands and (out,hidden)-swapped weights the same
    kernel is the backward input-cotangent pipeline. Optional BLOCK
    EPILOGUE (``wb``/``bias``/``act``): the 1×1 bypass conv of the
    standard FNO block ``gelu(spectral(h) + bypass(h) + bias)`` contracts
    the same hidden axis as the CGEMM k-loop, so its GEMM rides the same
    grid into a third VMEM accumulator and the last-k epilogue applies
    ``+bypass → +bias → activation`` (GELU, ReLU or none) before the
    single ref write — one pallas_call for the whole FNO block.
    ``act="gelu_vjp"``/``"relu_vjp"`` is the backward recompute: the
    epilogue forms ``gz = gy·act'(z)`` from the recomputed pre-activation
    without materializing z in HBM.
  * ``fused_fnond_core_call``  — paper-faithful partial fusion: only the
    DFT stage adjacent to the CGEMM is fused (complex in/out); the outer
    R-1 transforms run as standalone kernels (dft.py), matching TurboFNO,
    which fuses only the FFT stage next to the GEMM.
  * ``fused_fnond_wgrad_call`` — fused rank-reduction weight gradient:
    both the primal spectrum A and the cotangent spectrum Ĝ are computed
    in VMEM and consumed by the reduction without an HBM round trip.
    ``with_bypass=True`` additionally emits the bypass-weight cotangent
    ``dW_b = Σ gz·xᵀ`` and ``dbias = Σ gz`` from the x/gz refs the
    spectral reduction already holds in VMEM — no extra HBM pass.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.spectral import realified_plan
from repro.kernels import LAUNCH_NAMES, _compiler_params

_SEMANTICS = ("parallel", "parallel", "arbitrary")

# Version tag of the engine's launch geometry — the facts a persisted
# block-size plan depends on: the grid axes and their meaning, which
# operands are grid-blocked vs constant-index, and the accumulator/scratch
# layout per launch kind. The tuning cache (repro.tuning) stamps this into
# its meta and the contract linter refuses a cache tuned against another
# signature. BUMP THE VERSION whenever a change to the kernels below moves
# bytes in or out of a program's VMEM window (new operands, scratch shape
# changes, grid reorderings) — stale winners would otherwise keep passing.
# The fused-ends operands (lift/proj, PR 8) do NOT bump it: they are new
# OPTIONAL operands absent from every launch kind the cache tunes — an
# ends-fused launch reuses the block_fwd plan with bo pinned to the padded
# O, so tuned winners for the default launches stay exactly valid.
BLOCK_SIGNATURE = ("fnond-v4:grid=(b/bb,o/bo,h/bh);wgrad-grid=(o/bo,h/bh,"
                   "b/bb);acc=realified[bb*K,2bo]@accum+bypass;"
                   "per_mode-acc=flat_modes|vpu[bb,bo,K1,K2];"
                   "weights=[H/bh,O,bh]|vpu[H,O,K1,K2];"
                   "shared-weights=[H/bh,O/bo,2bo,2bh];"
                   "fwd-rank3=merged[s1,s2*s3];"
                   "mats=realified;vmem=96MiB;launches=block_fwd,"
                   "gz_recompute,dx_adjoint,wgrad,core")


def _matmul(acc_dtype: str, interpret: bool):
    """The kernels' one ``dot_general``: accumulates at the accumulator
    dtype (PrecisionPolicy.accum_dtype — f32 under the bf16 policy, so
    only the ref-write boundaries cast down).

    An interpreted kernel (off-TPU) widens its operands to that dtype
    first. XLA:CPU cannot execute a bf16×bf16→f32 dot once it sits in a
    jitted loop ("Unsupported element type for DotThunk::Execute"), and
    since a bf16 product is exact in f32 the result is the same. A kernel
    compiled for the TPU hands its operands to the MXU as they are."""
    acc = jnp.dtype(acc_dtype)

    def mm(a, b, dims):
        if interpret:
            a, b = a.astype(acc), b.astype(acc)
        return jax.lax.dot_general(a, b, dims, preferred_element_type=acc)
    return mm


def _dot(mm, a, b, axis):
    """Contract `axis` of a with dim 0 of b; the new dim is appended last."""
    return mm(a, b, (((axis,), (0,)), ((), ())))


def _cstage(mm, zr, zi, mr, mi, axis):
    """One complex DFT stage: (zr + i·zi) · (mr + i·mi) along `axis`.

    zi=None marks a real input (the first rDFT stage) — the imaginary
    products vanish.
    """
    if zi is None:
        return _dot(mm, zr, mr, axis), _dot(mm, zr, mi, axis)
    return (_dot(mm, zr, mr, axis) - _dot(mm, zi, mi, axis),
            _dot(mm, zr, mi, axis) + _dot(mm, zi, mr, axis))


def _dft_chain(mm, z, mats, rank):
    """Run the forward DFT chain over the trailing `rank` spatial axes.

    z: [bb,bc,s_1..s_R] real; mats: flat (mr, mi) pairs in stage order
    (axis s_R first). Returns the spectrum pair [bb,bc,K_R..K_1].
    """
    zr, zi = z, None
    for i in range(rank):
        zr, zi = _cstage(mm, zr, zi, mats[2 * i][...],
                         mats[2 * i + 1][...], 1 + rank - i)
    return zr, zi


# ---------------------------------------------------------------------------
# Realified chains (the forward kernel). A complex tensor is ONE real array
# whose axes carry labels — "b", "h" (the contracted channels), "o", ("s",
# j), ("k", j) — and one of whose axes, `cx`, holds the real part in its
# first half and the imaginary part in its second. Each DFT stage is then
# one real matmul with a packed operand (spectral.realified_operand_mats,
# in spectral.realified_plan order), and so is the shared-weight CGEMM
# (``_packed_weight``). Between contractions the halves move to the next
# contracted axis: split along one axis, stacked along another — on major
# or sublane axes, or on lanes at whole-vreg offsets. Where the halves sit
# on the lane axis at an offset that is not a multiple of 128 the minor
# axes are swapped first, so the split runs on sublanes.
# ---------------------------------------------------------------------------
def _moveaxis(z, axes, lab, pos):
    """Move the axis labelled `lab` to position `pos` (negative)."""
    i = axes.index(lab)
    if i == len(axes) + pos:
        return z, axes
    rest = axes[:i] + axes[i + 1:]
    at = len(rest) + pos + 1
    return (jnp.moveaxis(z, i, pos), rest[:at] + (lab,) + rest[at:])


def _halves(z, i):
    """The (real, imaginary) halves of z along axis i."""
    n = z.shape[i] // 2
    return (jax.lax.slice_in_dim(z, 0, n, axis=i),
            jax.lax.slice_in_dim(z, n, 2 * n, axis=i))


def _restack(z, axes, cx, dst):
    """Move the [re; im] halves from axis `cx` to axis `dst`."""
    if axes[-1] == cx and (z.shape[-1] // 2) % 128:
        z = jnp.swapaxes(z, -1, -2)
        axes = axes[:-2] + (axes[-1], axes[-2])
    return (jnp.concatenate(_halves(z, axes.index(cx)),
                            axis=axes.index(dst)), axes)


def _stage(mm, form, z, axes, cx, src, dst, m):
    """One realified DFT stage: contract axis `src` (with the halves when
    z is complex, cx not None) against the packed operand m, on the right
    ("lane") or on the left, batched over the leading axes ("sublane").
    The result axis `dst` takes src's place."""
    if cx is not None and cx != src:
        z, axes = _restack(z, axes, cx, src)
    if form == "lane":
        z, axes = _moveaxis(z, axes, src, -1)
        z = _dot(mm, z, m, z.ndim - 1)
    else:
        z, axes = _moveaxis(z, axes, src, -2)
        lead = z.shape[:-2]
        zb = z.reshape((-1,) + z.shape[-2:])
        mb = jnp.broadcast_to(m, zb.shape[:1] + m.shape)
        z = mm(mb, zb, (((2,), (1,)), ((0,), (0,))))
        z = z.reshape(lead + z.shape[1:])
    i = axes.index(src)
    return z, axes[:i] + (dst,) + axes[i + 1:]


# ---------------------------------------------------------------------------
# Hidden-blocked operand layout. Mosaic tiles the last two dims of every
# block by (8, 128) unless the block spans the whole array dim, so a
# [bo, bh] window of an [O, H] weight would force bh to all of H or a
# multiple of 128. The engine therefore hands Mosaic [O, H] operands as
# [H/bh, O, bh] — each hidden block its own leading index, its minor dim
# the whole (bh-wide) array dim — so bh stays a free k-loop tile. The
# re-layout is an XLA transpose of a weight-sized array, outside the
# kernel.
# ---------------------------------------------------------------------------
def _hidden_blocked(w: jax.Array, bh: int) -> jax.Array:
    """[…, R, H] -> [H/bh, …, R, bh]."""
    h = w.shape[-1]
    return jnp.moveaxis(w.reshape(w.shape[:-1] + (h // bh, bh)), -2, 0)


def _hidden_unblocked(w: jax.Array) -> jax.Array:
    """[H/bh, …, R, bh] -> […, R, H] (inverse of ``_hidden_blocked``)."""
    t = jnp.moveaxis(w, 0, -2)
    return t.reshape(t.shape[:-2] + (t.shape[-2] * t.shape[-1],))


def _modes_leading(w: jax.Array, rank: int) -> jax.Array:
    """Per-mode weight [O, H, K_1..K_R] -> [∏K, O, H], modes flattened in
    the spectrum's K_R..K_1 order: one leading batch dim for the CGEMM
    (Mosaic's matmul batches over at most one dim)."""
    t = jnp.transpose(w, tuple(range(1 + rank, 1, -1)) + (0, 1))
    return t.reshape((-1,) + w.shape[:2])


def _modes_major(t: jax.Array) -> jax.Array:
    """Spectrum [bb, c, K_R..K_1] -> [∏K, bb, c]: the flattened modes as
    the leading (batch) dim of a per-mode CGEMM or weight reduction."""
    t = t.reshape(t.shape[:2] + (-1,))
    return jnp.swapaxes(jnp.swapaxes(t, 1, 2), 0, 1)


def _hidden_spec(bo: int, bh: int, index_map) -> pl.BlockSpec:
    """Window of a hidden-blocked [H/bh, O, bh] operand; index_map gives
    (hidden block, out block)."""
    return pl.BlockSpec((None, bo, bh),
                        lambda *g: tuple(index_map(*g)) + (0,))


_GELU_C = 0.7978845608028654  # sqrt(2/pi)
_GELU_A = 0.044715


def _dgelu(z):
    """d/dz of the tanh-approximate GELU (jax.nn.gelu approximate=True,
    the activation core/fno.py applies): with u = c·(z + a·z³),
    gelu'(z) = ½(1+tanh u) + ½·z·(1−tanh²u)·c·(1+3a·z²)."""
    z2 = z * z
    t = jnp.tanh(_GELU_C * z * (1.0 + _GELU_A * z2))
    return 0.5 * (1.0 + t) + 0.5 * z * (1.0 - t * t) * _GELU_C * (
        1.0 + 3.0 * _GELU_A * z2)


# ---------------------------------------------------------------------------
# Full fusion: [rDFT → cDFT… → CGEMM → icDFT… → irDFT] in one kernel.
# With the block epilogue (has_wb): the bypass GEMM x·W_bᵀ accumulates in a
# third VMEM scratch during the same hidden k-loop, and the last-k epilogue
# computes gelu(iDFT(acc) + bypass + bias) before the single ref write.
#
# Fused MODEL ENDS (has_lift / has_proj — the lifting and projection MLPs
# folded into the first/last block kernel, DESIGN.md §6):
#   * has_lift: the x ref is the RAW model input [bb, C_in, s…] (constant
#     over the k grid). At k==0 the lift prologue computes the inner
#     activation a = gelu(W_l1ᵀ·x + b_l1) once into a scratch that persists
#     across the hidden loop; every k step then forms its hidden block
#     h_k = W_l2ᵀ[k]·a + b_l2[k] in VMEM and feeds it to the DFT chain and
#     bypass MAC — the lifted activations never round-trip HBM.
#   * has_proj: requires a single out-channel grid step (bo = padded O,
#     the projection contracts the FULL hidden width). The epilogue pushes
#     the activated block output straight through the projection MLP —
#     y = W_p2ᵀ·gelu(W_p1ᵀ·z + b_p1) + b_p2 — and the ref write emits the
#     model OUTPUT channels [bb, C_out, s…].
# ---------------------------------------------------------------------------
def vpu_cgemm(rank: int, modes: Tuple[int, ...]) -> bool:
    """Whether a per-mode CGEMM of a rank-`rank` kernel over kept `modes`
    runs on the VPU: the forward chain leaves the rank-2 spectrum as
    [bb, bh, K_1, re K_2 | im K_2], and each half is whole vregs when
    K_2 is a multiple of 128 (the merged rank-3 layout of Li et al.'s
    FNO-3D: 16·8 modes)."""
    return rank == 2 and modes[-1] % 128 == 0


def fwd_contractions(rank: int, per_mode: bool, has_wb: bool = False,
                     has_lift: bool = False, has_proj: bool = False,
                     vpu: bool = False) -> Tuple[int, int]:
    """(contractions, realified) that one grid step of a forward kernel
    issues — every ``dot_general`` of the body, the k==0 lift prologue
    and the last-k epilogue included. Realified: each of the 2R DFT
    stages and the shared-weight CGEMM, one real matmul per complex
    product. The per-mode CGEMM stays 4 real matmuls over two
    accumulators, or none where it runs on the VPU (``vpu_cgemm``); the
    bypass and the end MLPs are real products. ``rank`` is the kernel's
    (``spectral.kernel_layout``: 2 for a rank-3 layer). The kernel checks
    this count as it traces."""
    realified = 2 * rank + (0 if per_mode else 1)
    plain = (4 * (per_mode and not vpu) + has_wb + 2 * has_lift
             + 2 * has_proj)
    return realified + plain, realified


def _packed_weight(wr: jax.Array, wi: jax.Array, bo: int,
                   bh: int) -> jax.Array:
    """Shared [O,H] weight pair -> the realified CGEMM operand
    [[wr, −wi], [wi, wr]] cut into its (out, hidden) windows:
    [H/bh, O/bo, 2bo, 2bh], each window whole in its minor dims."""
    o, h = wr.shape

    def blocked(w):
        return w.reshape(o // bo, bo, h // bh, bh).transpose(2, 0, 1, 3)

    wr, wi = blocked(wr), blocked(wi)
    return jnp.concatenate([jnp.concatenate([wr, -wi], -1),
                            jnp.concatenate([wi, wr], -1)], -2)


def _packed_modes(mats, rank: int) -> Tuple[int, ...]:
    """(k_1..k_R) read off the forward operands of
    ``spectral.realified_operand_mats``."""
    k = {j: (m.shape[0] if form == "sublane" else m.shape[1]) // 2
         for (j, form), m in zip(realified_plan(rank)[0], mats)}
    return tuple(k[j] for j in range(rank))


@functools.lru_cache(maxsize=None)
def _make_fwd_kernel(rank: int, per_mode: bool, acc_dtype: str = "float32",
                     has_wb: bool = False, has_bias: bool = False,
                     act: str = "linear", has_lift: bool = False,
                     has_proj: bool = False, interpret: bool = False,
                     rev_modes: Tuple[int, ...] = (), vpu: bool = False):
    r = rank
    acc = jnp.dtype(acc_dtype)
    mm = _matmul(acc_dtype, interpret)
    has_gy = act in ("gelu_vjp", "relu_vjp")
    fwd_plan, inv_plan = realified_plan(r)
    spatial_axes = tuple(("s", j) for j in range(r))
    want = fwd_contractions(r, per_mode, has_wb, has_lift, has_proj, vpu)

    def kernel(*refs):
        nw = 2 if per_mode else 1
        x_ref, w_refs = refs[0], refs[1:1 + nw]
        pos = 1 + nw
        fwd = refs[pos:pos + r]
        inv = refs[pos + r:pos + 2 * r]
        pos += 2 * r
        wb_ref = bias_ref = gy_ref = accb = acca = None
        lift_refs = proj_refs = None
        if has_wb:
            wb_ref = refs[pos]
            pos += 1
        if has_bias:
            bias_ref = refs[pos]
            pos += 1
        if has_gy:
            gy_ref = refs[pos]
            pos += 1
        if has_lift:
            lift_refs = refs[pos:pos + 4]  # l1w [L,Ci], l1b, l2w, l2b
            pos += 4
        if has_proj:
            proj_refs = refs[pos:pos + 4]  # p1w [L,O], p1b, p2w, p2b
            pos += 4
        y_ref = refs[pos]
        accs = refs[pos + 1:pos + 1 + nw]
        pos += 1 + nw
        if has_wb:
            accb = refs[pos]
            pos += 1
        if has_lift:
            acca = refs[pos]

        issued = [0, 0]  # contractions, realified ones

        def counted(realified):
            def f(a, b, dims):
                issued[0] += 1
                issued[1] += realified
                return mm(a, b, dims)
            return f

        rmm, pmm = counted(True), counted(False)

        def _colvec(ref, nd):
            # [D,1] operand broadcast over the trailing batch/spatial dims.
            # Widened to the accumulator dtype first: Mosaic has no
            # rank-changing shape cast for a packed (bf16) column.
            return ref[...].astype(acc).reshape((-1,) + (1,) * nd)

        @pl.when(pl.program_id(2) == 0)
        def _init():
            for a in accs:
                a[...] = jnp.zeros_like(a)
            if has_wb:
                accb[...] = jnp.zeros_like(accb)
            if has_lift:
                # Lift prologue, once per (i,j): a = gelu(W_l1ᵀ·x + b_l1)
                # → [L, bb, s…], persisted across the hidden k-loop.
                a = pmm(lift_refs[0][...], x_ref[...],
                        (((1,), (1,)), ((), ())))
                a = a + _colvec(lift_refs[1], 1 + r)
                acca[...] = jax.nn.gelu(a, approximate=True)

        if has_lift:
            # This k step's hidden block: h_k = W_l2ᵀ[k]·a + b_l2[k],
            # realigned [L,bb,…]→[bb,bh,…] by a major-axes swap.
            hk = pmm(lift_refs[2][...], acca[...], (((1,), (0,)), ((), ())))
            hk = hk + _colvec(lift_refs[3], 1 + r)
            xblk = jnp.swapaxes(hk, 0, 1).astype(x_ref.dtype)
        else:
            xblk = x_ref[...]

        # Truncated forward DFT chain, realified — the FFT writing its
        # A-tile to "shared memory" (VMEM registers).
        z, axes, cx = xblk, ("b", "h") + spatial_axes, None
        for (j, form), m in zip(fwd_plan, fwd):
            z, axes = _stage(rmm, form, z, axes, cx, ("s", j), ("k", j),
                             m[...])
            cx = ("k", j)

        # CGEMM over hidden (the k-loop MAC).
        if vpu:
            # Elementwise over the modes, where they lie: A [bb,bh,K_1,K_2]
            # halves against W [bh,bo,K_1,K_2], summed over the hidden
            # block -> [bb,bo,K_1,K_2].
            assert axes == ("b", "h", ("k", 0), ("k", 1)) and cx == axes[3]
            ar, ai = (t[:, :, None] for t in _halves(z, 3))
            wr, wi = w_refs[0][...], w_refs[1][...]
            accs[0][...] += jnp.sum(ar * wr - ai * wi, axis=1)
            accs[1][...] += jnp.sum(ar * wi + ai * wr, axis=1)
        elif per_mode:
            # Four real matmuls: the halves in the K_R..K_1 order of the
            # weight, the modes flattened into the one batch dim: A
            # [∏K,bb,bh] against W [∏K,bo,bh] -> [∏K,bb,bo].
            canon = ("b", "h") + tuple(("k", j) for j in reversed(range(r)))
            perm = tuple(axes.index(a) for a in canon)
            ar, ai = (_modes_major(jnp.transpose(t, perm))
                      for t in _halves(z, axes.index(cx)))
            wr, wi = w_refs[0][...], w_refs[1][...]
            dims = (((2,), (2,)), ((0,), (0,)))
            accs[0][...] += pmm(ar, wr, dims) - pmm(ai, wi, dims)
            accs[1][...] += pmm(ar, wi, dims) + pmm(ai, wr, dims)
        else:
            # One real matmul: the halves stacked on the hidden axis
            # against [[wr, −wi], [wi, wr]] -> [bb, K…, re o | im o].
            z, axes = _restack(z, axes, cx, "h")
            t = rmm(z, w_refs[0][...], (((1,), (1,)), ((), ())))
            spec_shape, spec_axes = t.shape, axes[:1] + axes[2:] + ("o",)
            accs[0][...] += t.reshape(accs[0].shape)

        if has_wb:
            # Bypass GEMM riding the same k-loop MAC: W_b[bo,bh]·x[bb,bh,s…]
            # → [bo,bb,s…]. The bo-leading layout keeps the minor (spatial)
            # dims in place so the epilogue's realign is a major-axes swap.
            accb[...] += pmm(wb_ref[...], xblk, (((1,), (1,)), ((), ())))

        @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
        def _epilogue():
            # Padded inverse DFT chain, realified; the last stage emits
            # only the real part (real output).
            if vpu:  # [bb,bo,K_1,K_2] pair -> [bb, bo, K_1, re|im K_2]
                z = jnp.concatenate([a[...] for a in accs], axis=3)
                axes, cx = ("b", "o", ("k", 0), ("k", 1)), ("k", 1)
            elif per_mode:  # [∏K,bb,bo] pair -> [bb, re K_R | im K_R, …, bo]
                shared = (accs[0].shape[1],) + rev_modes + (accs[0].shape[2],)
                z = jnp.concatenate(
                    [jnp.swapaxes(a[...], 0, 1).reshape(shared)
                     for a in accs], axis=1)
                axes = (("b",) + tuple(("k", j) for j in reversed(range(r)))
                        + ("o",))
                cx = ("k", r - 1)
            else:
                z, axes, cx = accs[0][...].reshape(spec_shape), spec_axes, "o"
            for i, ((j, form), m) in enumerate(zip(inv_plan, inv)):
                z, axes = _stage(rmm, form, z, axes, cx, ("k", j), ("s", j),
                                 m[...])
                cx = ("s", j) if i < r - 1 else None
            out_axes = ("b", "o") + spatial_axes
            if axes != out_axes:
                z = jnp.transpose(z, tuple(axes.index(a) for a in out_axes))
            # Block epilogue: + bypass + bias → activation, all on the
            # f32 VMEM values — HBM sees only the final activation.
            if has_wb:
                z = z + jnp.swapaxes(accb[...], 0, 1)
            if has_bias:  # widen before the reshape (see _colvec)
                z = z + bias_ref[...].astype(acc).reshape((1, -1) + (1,) * r)
            if act == "gelu":
                z = jax.nn.gelu(z, approximate=True)
            elif act == "relu":
                z = jnp.maximum(z, 0.0)
            elif act == "gelu_vjp":
                z = gy_ref[...].astype(acc) * _dgelu(z)
            elif act == "relu_vjp":
                z = jnp.where(z > 0.0, gy_ref[...].astype(acc), 0.0)
            if has_proj:
                # Projection epilogue on the activated block output z
                # [bb,O,s…] (bo == padded O — single j step): the pointwise
                # MLP contracts the full hidden width in VMEM and the ref
                # write emits the model's output channels.
                a2 = pmm(proj_refs[0][...], z.astype(acc),
                         (((1,), (1,)), ((), ())))
                a2 = jax.nn.gelu(a2 + _colvec(proj_refs[1], 1 + r),
                                 approximate=True)
                out = pmm(proj_refs[2][...], a2, (((1,), (0,)), ((), ())))
                z = jnp.swapaxes(out + _colvec(proj_refs[3], 1 + r), 0, 1)
            y_ref[...] = z.astype(y_ref.dtype)

        assert tuple(issued) == want, (issued, want)

    return kernel


@functools.partial(jax.jit, static_argnames=("bb", "bo", "bh", "name",
                                             "interpret", "out_dtype",
                                             "acc_dtype", "act"))
def fused_fnond_call(x: jax.Array, wr: jax.Array, wi: jax.Array,
                     *mats: jax.Array, bb: int, bo: int, bh: int, name: str,
                     interpret: bool = False, out_dtype: str = None,
                     acc_dtype: str = "float32", wb: jax.Array = None,
                     bias: jax.Array = None, gy: jax.Array = None,
                     act: str = "linear", lift: Tuple = None,
                     proj: Tuple = None) -> jax.Array:
    """Whole rank-R FNO spectral layer — or FNO block — in one kernel.

    x: [B,H,s_1..s_R] real; w: [O,H] or [O,H,K_1..K_R]; mats: the 2R
    packed real operands — R forward stages then R inverse stages in
    ``spectral.realified_plan`` order, as produced by
    ``spectral.realified_operand_mats``. Returns y [B,O,s_1..s_R] real.
    ``name`` is the launch kind (``LAUNCH_NAMES``: block_fwd, block_ends,
    spectral_fwd, gz_recompute or dx_adjoint), which the caller knows and
    the operands do not: the adjoint differs only in its DFT operands.

    All of B,O,H must divide by (bb,bo,bh); spatial/modes dims are whole
    blocks (ops.py pads). out_dtype overrides the output dtype (default:
    x.dtype — the backward pass emits dx at the primal dtype straight from
    the f32 accumulator); acc_dtype is the VMEM accumulator dtype
    (PrecisionPolicy.accum_dtype).

    Block epilogue (all optional):
    wb [O,H] accumulates the 1×1 bypass GEMM alongside the CGEMM k-loop;
    bias [O,1] adds per-out-channel; act picks the epilogue nonlinearity —
    "linear" (default), "gelu" or "relu" (forward block), or "gelu_vjp" /
    "relu_vjp" (backward recompute: requires gy [B,O,s_1..s_R] and emits
    gy·act'(z)). A per-mode weight whose CGEMM runs on the VPU
    (``vpu_cgemm``) comes as [O,H,K_1,K_2].

    Fused model ends (forward block kernels only — incompatible with gy):
    lift = (l1w [L,C_in], l1b [L,1], l2w [H,L], l2b [H,1]) folds the
    lifting MLP into the kernel — x is then the RAW input [B,C_in,s…] and
    each k step derives its hidden block in VMEM (prologue at k==0 caches
    the inner activation). proj = (p1w [L,O], p1b [L,1], p2w [C_out,L],
    p2b [C_out,1]) folds the projection MLP into the epilogue — requires
    bo == O (single out-channel grid step) and the result is
    [B,C_out,s…]. These launches reuse the block_fwd tuned plan with bo
    pinned; the default launches are unchanged (BLOCK_SIGNATURE stable).
    """
    r = x.ndim - 2
    b = x.shape[0]
    h = lift[2].shape[0] if lift is not None else x.shape[1]
    spatial = x.shape[2:]
    o = wr.shape[0]
    per_mode = wr.ndim == 2 + r
    assert len(mats) == 2 * r, (len(mats), r)
    assert act in ("linear", "gelu", "relu", "gelu_vjp", "relu_vjp"), act
    assert name in LAUNCH_NAMES, name
    assert (gy is not None) == act.endswith("_vjp"), act
    assert gy is None or (lift is None and proj is None), \
        "fused ends are forward-only (backward is the staged vjp)"
    assert proj is None or bo == o, \
        "the projection epilogue contracts the full padded O: bo must == O"
    modes = _packed_modes(mats, r)
    rev_modes = modes[::-1]  # the per-mode weight's flattening order
    vpu = per_mode and vpu_cgemm(r, modes)
    grid = (b // bb, o // bo, h // bh)
    zr = (0,) * r

    if lift is not None:
        # Raw-input block: full (small) channel dim, constant over k.
        x_spec = pl.BlockSpec((bb, x.shape[1]) + spatial,
                              lambda i, j, k: (i, 0) + zr)
    else:
        x_spec = pl.BlockSpec((bb, bh) + spatial,
                              lambda i, j, k: (i, k) + zr)
    if vpu:
        ws = [jnp.swapaxes(w, 0, 1) for w in (wr, wi)]  # [H, O, K_1, K_2]
        w_specs = [pl.BlockSpec((bh, bo) + modes,
                                lambda i, j, k: (k, j, 0, 0))] * 2
        acc_shapes = [(bb, bo) + modes] * 2
    elif per_mode:
        ws = [_hidden_blocked(_modes_leading(w, r), bh) for w in (wr, wi)]
        kf = ws[0].shape[1]
        w_specs = [pl.BlockSpec((None, kf, bo, bh),
                                lambda i, j, k: (k, 0, j, 0))] * 2
        acc_shapes = [(kf, bb, bo)] * 2
    else:
        ws = [_packed_weight(wr, wi, bo, bh)]
        w_specs = [pl.BlockSpec((None, None, 2 * bo, 2 * bh),
                                lambda i, j, k: (k, j, 0, 0))]
        # [bb, K…, re o | im o] with its leading axes merged.
        acc_shapes = [(bb * math.prod(modes), 2 * bo)]
    m_specs = [pl.BlockSpec(m.shape, lambda i, j, k: (0, 0)) for m in mats]
    out_ch = proj[2].shape[0] if proj is not None else o
    if proj is not None:
        y_spec = pl.BlockSpec((bb, out_ch) + spatial,
                              lambda i, j, k: (i, 0) + zr)
    else:
        y_spec = pl.BlockSpec((bb, bo) + spatial,
                              lambda i, j, k: (i, j) + zr)

    operands = [x, *ws, *mats]
    in_specs = [x_spec, *w_specs] + m_specs
    acc = jnp.dtype(acc_dtype)
    scratch = [pltpu.VMEM(shape, acc) for shape in acc_shapes]
    if wb is not None:
        operands.append(_hidden_blocked(wb, bh))
        in_specs.append(_hidden_spec(bo, bh, lambda i, j, k: (k, j)))
        scratch.append(pltpu.VMEM((bo, bb) + spatial, acc))
    if bias is not None:
        operands.append(bias)
        in_specs.append(pl.BlockSpec((bo, 1), lambda i, j, k: (j, 0)))
    if gy is not None:
        operands.append(gy)
        in_specs.append(y_spec)
    if lift is not None:
        l1w, l1b, l2w, l2b = lift
        # The hidden-row operands as [H/bh, bh, …]: whole-dim windows.
        operands += [l1w, l1b, l2w.reshape(h // bh, bh, -1),
                     l2b.reshape(h // bh, bh, 1)]
        in_specs += [
            pl.BlockSpec(l1w.shape, lambda i, j, k: (0, 0)),
            pl.BlockSpec(l1b.shape, lambda i, j, k: (0, 0)),
            pl.BlockSpec((None, bh, l2w.shape[1]),
                         lambda i, j, k: (k, 0, 0)),
            pl.BlockSpec((None, bh, 1), lambda i, j, k: (k, 0, 0)),
        ]
    if proj is not None:
        operands += list(proj)
        in_specs += [pl.BlockSpec(p.shape, lambda i, j, k: (0, 0))
                     for p in proj]
    if lift is not None:
        # The persisted lift activation a [L, bb, s…] (k-invariant).
        scratch.append(pltpu.VMEM((lift[0].shape[0], bb) + spatial, acc))

    return pl.pallas_call(
        _make_fwd_kernel(r, per_mode, acc_dtype, wb is not None,
                         bias is not None, act, lift is not None,
                         proj is not None, interpret, rev_modes, vpu),
        grid=grid,
        in_specs=in_specs,
        out_specs=y_spec,
        name=name,
        out_shape=jax.ShapeDtypeStruct((b, out_ch) + spatial,
                                       jnp.dtype(out_dtype or x.dtype)),
        scratch_shapes=scratch,
        compiler_params=_compiler_params(dimension_semantics=_SEMANTICS),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# Paper-faithful partial fusion: [cDFT_s1 → CGEMM → icDFT_s1] on complex
# input whose outer axes were already transformed by standalone kernels.
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _make_core_kernel(n_spec: int, per_mode: bool,
                      acc_dtype: str = "float32", interpret: bool = False,
                      rev_modes: Tuple[int, ...] = ()):
    s = n_spec  # trailing already-spectral axes (K_R .. K_2)
    mm = _matmul(acc_dtype, interpret)

    def kernel(zr_ref, zi_ref, wr_ref, wi_ref, fr_ref, fi_ref,
               gr_ref, gi_ref, yr_ref, yi_ref, accr, acci):
        @pl.when(pl.program_id(2) == 0)
        def _init():
            accr[...] = jnp.zeros_like(accr)
            acci[...] = jnp.zeros_like(acci)

        # Truncated cDFT along s_1 (the GEMM-adjacent stage): contract
        # dim 2 -> [bb,bh,K_R..K_2,K_1].
        ar, ai = _cstage(mm, zr_ref[...], zi_ref[...], fr_ref[...],
                         fi_ref[...], 2)
        wr, wi = wr_ref[...], wi_ref[...]
        if per_mode:  # as in the full-fusion kernel: [∏K,bb,bh]·[∏K,bo,bh]
            ar, ai = _modes_major(ar), _modes_major(ai)
            dims = (((2,), (2,)), ((0,), (0,)))
        else:
            dims = (((1,), (1,)), ((), ()))

        def dg(a, w):
            return mm(a, w, dims)

        accr[...] += dg(ar, wr) - dg(ai, wi)
        acci[...] += dg(ar, wi) + dg(ai, wr)

        @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
        def _epilogue():
            # Padded icDFT along s_1 (complex output pair).
            tr, ti = accr[...], acci[...]
            if per_mode:  # [∏K,bb,bo] -> [K_R..K_1,bb,bo]
                tr, ti = (t.reshape(rev_modes + t.shape[1:])
                          for t in (tr, ti))
            axis = s if per_mode else 1 + s
            tr, ti = _cstage(mm, tr, ti, gr_ref[...], gi_ref[...], axis)
            yr_ref[...] = tr.astype(yr_ref.dtype)
            yi_ref[...] = ti.astype(yi_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("bb", "bo", "bh", "interpret",
                                             "acc_dtype"))
def fused_fnond_core_call(zr: jax.Array, zi: jax.Array, wr: jax.Array,
                          wi: jax.Array, fr: jax.Array, fi: jax.Array,
                          gr: jax.Array, gi: jax.Array, *, bb: int, bo: int,
                          bh: int, interpret: bool = False,
                          acc_dtype: str = "float32"
                          ) -> Tuple[jax.Array, jax.Array]:
    """Partial-fusion middle: z [B,H,s_1,K_R..K_2] complex pair (outer
    stages already applied); w [O,H] or [O,H,K_1..K_R]; f [s_1,K_1];
    g [K_1,s_1]. Returns the y pair — [B,K_R..K_2,O,s_1] shared, or
    [K_R..K_2,B,O,s_1] per-mode (caller transposes)."""
    b, h, nx = zr.shape[:3]
    spec = zr.shape[3:]
    s = len(spec)
    o = wr.shape[0]
    per_mode = wr.ndim > 2
    kx = fr.shape[1]
    grid = (b // bb, o // bo, h // bh)
    zs = (0,) * s

    z_spec = pl.BlockSpec((bb, bh, nx) + spec,
                          lambda i, j, k: (i, k, 0) + zs)
    if per_mode:
        wr, wi = (_hidden_blocked(_modes_leading(w, s + 1), bh)
                  for w in (wr, wi))
        w_spec = pl.BlockSpec((None, wr.shape[1], bo, bh),
                              lambda i, j, k: (k, 0, j, 0))
        y_shape = spec + (b, o, nx)
        y_spec = pl.BlockSpec(spec + (bb, bo, nx),
                              lambda i, j, k: zs + (i, j, 0))
        acc_shape = (math.prod(spec) * kx, bb, bo)
    else:
        wr, wi = _hidden_blocked(wr, bh), _hidden_blocked(wi, bh)
        w_spec = _hidden_spec(bo, bh, lambda i, j, k: (k, j))
        y_shape = (b,) + spec + (o, nx)
        y_spec = pl.BlockSpec((bb,) + spec + (bo, nx),
                              lambda i, j, k: (i,) + zs + (j, 0))
        acc_shape = (bb,) + spec + (kx, bo)
    mat = lambda m: pl.BlockSpec(m.shape, lambda i, j, k: (0, 0))
    out_sd = jax.ShapeDtypeStruct(y_shape, zr.dtype)

    acc = jnp.dtype(acc_dtype)
    return pl.pallas_call(
        _make_core_kernel(s, per_mode, acc_dtype, interpret, spec + (kx,)),
        grid=grid,
        in_specs=[z_spec, z_spec, w_spec, w_spec, mat(fr), mat(fi),
                  mat(gr), mat(gi)],
        out_specs=[y_spec, y_spec],
        out_shape=[out_sd, out_sd],
        name="core",
        scratch_shapes=[pltpu.VMEM(acc_shape, acc),
                        pltpu.VMEM(acc_shape, acc)],
        compiler_params=_compiler_params(dimension_semantics=_SEMANTICS),
        interpret=interpret,
    )(zr, zi, wr, wi, fr, fi, gr, gi)


# ---------------------------------------------------------------------------
# Fused weight gradient (backward pass of the spectral layer).
#
# With A = the truncated rank-R spectrum of x ([B,H,K_R..K_1]) and
# Ĝ = the output cotangent pushed into the spectral domain through the
# transposed inverse transforms ([B,O,K_R..K_1]), the weight cotangent is
#
#     dW[o,h(,modes)] = conj( Σ_b Ĝ[b,o,…]·A[b,h,…] )   (Σ_modes too when
#                                                        shared)
#
# — a fused rank reduction: both spectra are computed straight into VMEM
# and consumed without an HBM round trip, mirroring the forward kernel's
# Fig. 7 forwarding. Grid = (out, hidden, batch) with BATCH innermost as
# the accumulation loop.
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _make_wgrad_kernel(rank: int, per_mode: bool,
                       acc_dtype: str = "float32",
                       with_bypass: bool = False, interpret: bool = False):
    r = rank
    acc = jnp.dtype(acc_dtype)
    mm = _matmul(acc_dtype, interpret)

    def kernel(*refs):
        x_ref, g_ref = refs[:2]
        xm = refs[2:2 + 2 * r]          # forward-spectrum operands (A)
        gm = refs[2 + 2 * r:2 + 4 * r]  # adjoint forward operands (Ĝ)
        pos = 2 + 4 * r
        dwr_ref, dwi_ref = refs[pos:pos + 2]
        pos += 2
        dwb_ref = db_ref = accwb = accdb = None
        if with_bypass:
            dwb_ref, db_ref = refs[pos:pos + 2]
            pos += 2
        accr, acci = refs[pos:pos + 2]
        if with_bypass:
            accwb, accdb = refs[pos + 2:pos + 4]

        @pl.when(pl.program_id(2) == 0)
        def _init():
            accr[...] = jnp.zeros_like(accr)
            acci[...] = jnp.zeros_like(acci)
            if with_bypass:
                accwb[...] = jnp.zeros_like(accwb)
                accdb[...] = jnp.zeros_like(accdb)

        def flat(t):  # [bb,c,…] -> [bb,c,∏…]: a minor-dims merge
            return t.reshape(t.shape[:2] + (-1,))

        # A: [bb,bh,K_R..K_1] and Ĝ: [bb,bo,K_R..K_1], spectral axes
        # flattened into the lane dim. Mosaic's matmul contracts exactly
        # one dim and batches at most one, so the reduction over batch
        # and modes is a batch-dim matmul over the modes, then a sum.
        ar, ai = (flat(t) for t in _dft_chain(mm, x_ref[...], xm, r))
        hr, hi = (flat(t) for t in _dft_chain(mm, g_ref[...], gm, r))

        if per_mode:
            # Modes become the batch dim ([K,bb,c]); contract batch ->
            # [K,bo,bh], the accumulator's layout (rev_modes flattened).
            ar, ai, hr, hi = (_modes_major(t) for t in (ar, ai, hr, hi))
            dims = (((1,), (1,)), ((0,), (0,)))
        else:
            # Batch over bb, contract the flattened modes -> [bb,bo,bh].
            dims = (((2,), (2,)), ((0,), (0,)))

        def rdot(p, q):
            out = mm(p, q, dims)
            return out if per_mode else jnp.sum(out, axis=0)

        accr[...] += rdot(hr, ar) - rdot(hi, ai)
        acci[...] += rdot(hr, ai) + rdot(hi, ar)

        if with_bypass:
            # Bypass cotangents from the refs already resident in VMEM:
            # dW_b = Σ_{b,s} gz·x and dbias = Σ_{b,s} gz, contracted
            # over the flattened spatial dims — no extra HBM pass.
            g3, x3 = flat(g_ref[...]), flat(x_ref[...])
            accwb[...] += jnp.sum(
                mm(g3, x3, (((2,), (2,)), ((0,), (0,)))), axis=0)
            accdb[...] += jnp.sum(jnp.sum(g3.astype(acc), axis=2,
                                          keepdims=True), axis=0)

        @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
        def _epilogue():
            # dW = conj(acc): real part as-is, imaginary part negated.
            dwr_ref[...] = accr[...].astype(dwr_ref.dtype)
            dwi_ref[...] = (-acci[...]).astype(dwi_ref.dtype)
            if with_bypass:  # real operands — no conjugation
                dwb_ref[...] = accwb[...].astype(dwb_ref.dtype)
                db_ref[...] = accdb[...].astype(db_ref.dtype)

    return kernel


@functools.partial(
    jax.jit, static_argnames=("bb", "bo", "bh", "per_mode", "interpret",
                              "out_dtype", "acc_dtype", "with_bypass"))
def fused_fnond_wgrad_call(x: jax.Array, g: jax.Array, *mats: jax.Array,
                           bb: int, bo: int, bh: int, per_mode: bool,
                           interpret: bool = False, out_dtype: str = None,
                           acc_dtype: str = "float32",
                           with_bypass: bool = False
                           ) -> Tuple[jax.Array, ...]:
    """x: [B,H,s_1..s_R] primal; g: [B,O,s_1..s_R] cotangent; mats: flat
    (mr, mi) pairs — R forward stages for x then R adjoint-forward stages
    for g (each [n,k], axis s_R first), as produced by
    ``spectral.wgrad_operand_mats``.

    Returns (dwr, dwi): [O,H] shared, or [∏K,O,H] per-mode with the modes
    flattened in K_R..K_1 order (caller reshapes and transposes back to
    [O,H,K_1..K_R]). out_dtype sets the dW emission
    dtype (PrecisionPolicy.param_dtype under mixed precision: cotangents
    accumulate at acc_dtype in VMEM, dW is cast once at the ref write).

    with_bypass=True (the fused-block backward) appends the bypass-GEMM
    cotangents to the return — (dwr, dwi, dwb [O,H], dbias [O,1]) — formed
    from the x/g refs the spectral reduction already holds in VMEM.
    """
    r = x.ndim - 2
    b, h = x.shape[:2]
    spatial = x.shape[2:]
    o = g.shape[1]
    assert len(mats) == 4 * r, (len(mats), r)
    rev_modes = tuple(m.shape[1] for m in mats[:2 * r:2])
    grid = (o // bo, h // bh, b // bb)
    zr = (0,) * r

    x_spec = pl.BlockSpec((bb, bh) + spatial, lambda i, j, kb: (kb, j) + zr)
    g_spec = pl.BlockSpec((bb, bo) + spatial, lambda i, j, kb: (kb, i) + zr)
    m_specs = [pl.BlockSpec(m.shape, lambda i, j, kb: (0, 0)) for m in mats]
    # dW leaves hidden-blocked ([H/bh, …, O, bh]) and is re-laid out
    # after the call.
    if per_mode:
        kf = math.prod(rev_modes)
        dw_spec = pl.BlockSpec((None, kf, bo, bh),
                               lambda i, j, kb: (j, 0, i, 0))
        dw_shape = (h // bh, kf, o, bh)
        acc_shape = (kf, bo, bh)
    else:
        dw_spec = _hidden_spec(bo, bh, lambda i, j, kb: (j, i))
        dw_shape = (h // bh, o, bh)
        acc_shape = (bo, bh)
    od = jnp.dtype(out_dtype or x.dtype)
    out_sd = jax.ShapeDtypeStruct(dw_shape, od)

    acc = jnp.dtype(acc_dtype)
    out_specs = [dw_spec, dw_spec]
    out_shape = [out_sd, out_sd]
    scratch = [pltpu.VMEM(acc_shape, acc), pltpu.VMEM(acc_shape, acc)]
    if with_bypass:
        # dwb [O,H] per (i,j) block; dbias [O,1] is j-independent — every
        # j program re-derives and writes the identical block (idempotent).
        out_specs += [_hidden_spec(bo, bh, lambda i, j, kb: (j, i)),
                      pl.BlockSpec((bo, 1), lambda i, j, kb: (i, 0))]
        out_shape += [jax.ShapeDtypeStruct((h // bh, o, bh), od),
                      jax.ShapeDtypeStruct((o, 1), od)]
        scratch += [pltpu.VMEM((bo, bh), acc), pltpu.VMEM((bo, 1), acc)]
    out = pl.pallas_call(
        _make_wgrad_kernel(r, per_mode, acc_dtype, with_bypass, interpret),
        grid=grid,
        in_specs=[x_spec, g_spec] + m_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        name="wgrad",
        scratch_shapes=scratch,
        compiler_params=_compiler_params(dimension_semantics=_SEMANTICS),
        interpret=interpret,
    )(x, g, *mats)
    dwr, dwi = _hidden_unblocked(out[0]), _hidden_unblocked(out[1])
    if with_bypass:
        return dwr, dwi, _hidden_unblocked(out[2]), out[3]
    return dwr, dwi
