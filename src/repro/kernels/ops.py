"""Public jit'd wrappers around the Pallas kernels.

Handles (8,128)-alignment padding, block-size selection, and path dispatch:

  path="ref"    — jnp.fft staged oracle (the "PyTorch baseline")
  path="xla"    — truncated-DFT matmul formulation, fused by XLA (runs on
                  any backend; this is what the distributed dry-run lowers)
  path="pallas" — the fused TurboFNO kernels (interpret=True off-TPU)

Padding rules: modes K and channel dims are padded with zeros — padded DFT
rows/weight entries contribute exactly zero through the linear pipeline, so
results are sliced back without error.

Mixed precision: the spectral layers take an optional PrecisionPolicy. The
compute-dtype casts live inside the custom_vjp, so the caller's primal and
cotangent dtypes are preserved while the kernels run at the policy's
compute dtype with f32 accumulators (ROADMAP.md §Precision policy).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import PrecisionPolicy
from repro.core import spectral
from repro.kernels import cgemm as cgemm_k
from repro.kernels import dft as dft_k
from repro.kernels import engine
from repro.kernels import ref as ref_k
from repro.tuning import resolve_launch_plans
from repro.tuning.plans import (LaunchPlans, _pick_block, _rup, end_mlp_window,
                                 legal_blocks)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret(flag: Optional[bool]) -> bool:
    return (not on_tpu()) if flag is None else flag


def _pad_axis(x: jax.Array, axis: int, to: int) -> jax.Array:
    pad = to - x.shape[axis]
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


def _blocks(x, o, bb, bo, bh):
    """Resolve (bb,bo,bh) block sizes and padded (B,O,H) for x[B,H,...]."""
    b, h = x.shape[:2]
    bb, bo, bh = legal_blocks(x.ndim - 2, b, o, h, bb, bo, bh)
    return bb, bo, bh, _rup(b, bb), _rup(o, bo), _rup(h, bh)


# ---------------------------------------------------------------------------
# Standalone truncated-DFT kernels (paper §3.3 — FFT w/ built-in filtering)
#
# All four transforms share one shape recipe: flatten the leading dims to
# rows, lane-align the modes axis to 128 (forward operands pad columns,
# inverse operands pad rows — and the inverse *inputs* pad their modes
# axis to match), row-block, invoke the dft.py kernel, un-pad. `_rowwise`
# holds that recipe once; each wrapper only picks the operand factory,
# kernel, and path dispatch.
# ---------------------------------------------------------------------------
def _rowwise(call, rows, mats, out_modes: int, block_rows: int,
             interpret: Optional[bool], pad_in_to: int = 0):
    """Run a row-blocked standalone DFT kernel.

    rows: input arrays [..., K_in] sharing leading dims; mats: broadcast
    DFT operands; out_modes: slice of the kernel's last dim to keep (0 =
    keep all); pad_in_to: zero-pad the inputs' last axis first (inverse
    transforms whose operands were row-padded).
    """
    lead = rows[0].shape[:-1]
    m = int(np.prod(lead)) if lead else 1
    br = _pick_block(m, block_rows)
    mp = _rup(m, br)
    if pad_in_to:
        rows = [_pad_axis(r, -1, pad_in_to) for r in rows]
    rows2d = [_pad_axis(r.reshape(m, r.shape[-1]), 0, mp) for r in rows]
    out = call(*rows2d, *mats, br, _interpret(interpret))
    single = not isinstance(out, (tuple, list))
    outs = (out,) if single else tuple(out)
    outs = tuple(o[:m, :out_modes or o.shape[-1]].reshape(
        *lead, out_modes or o.shape[-1]) for o in outs)
    return outs[0] if single else outs


def _dft_operands(mats, dtype, pad_axis: int, to: int):
    return tuple(_pad_axis(jnp.asarray(a, dtype), pad_axis, to)
                 for a in mats)


def truncated_rdft(x: jax.Array, modes: int, *, path: str = "pallas",
                   block_rows: int = 256,
                   interpret: Optional[bool] = None,
                   operand_dtype: Optional[str] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """rFFT along the last axis keeping `modes` bins. x: [..., N].

    operand_dtype overrides the DFT-matrix dtype (defaults to x.dtype;
    PrecisionPolicy.spectral_dtype on the partial-fusion path)."""
    if path == "ref":
        return ref_k.ref_truncated_rdft(x, modes)
    if path == "xla":
        return spectral.truncated_rdft(x, modes)
    mats = _dft_operands(spectral.rdft_mats(x.shape[-1], modes),
                         operand_dtype or x.dtype, 1, _rup(modes, 128))
    return _rowwise(dft_k._rdft_call, [x], mats, modes, block_rows,
                    interpret)


def padded_irdft(xr: jax.Array, xi: jax.Array, n: int, *,
                 path: str = "pallas", block_rows: int = 256,
                 interpret: Optional[bool] = None,
                 operand_dtype: Optional[str] = None) -> jax.Array:
    """Inverse rFFT from `modes` bins zero-padded to length n."""
    if path == "ref":
        return ref_k.ref_padded_irdft(xr, xi, n)
    if path == "xla":
        return spectral.padded_irdft(xr, xi, n)
    kp = _rup(xr.shape[-1], 128)
    mats = _dft_operands(spectral.irdft_mats(n, xr.shape[-1]),
                         operand_dtype or xr.dtype, 0, kp)
    return _rowwise(dft_k._irdft_call, [xr, xi], mats, 0, block_rows,
                    interpret, pad_in_to=kp)


def truncated_cdft(xr: jax.Array, xi: jax.Array, modes: int, *,
                   path: str = "pallas", block_rows: int = 256,
                   interpret: Optional[bool] = None,
                   operand_dtype: Optional[str] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """Complex DFT along the last axis keeping the first `modes` bins.

    operand_dtype overrides the DFT-matrix dtype (defaults to xr.dtype;
    PrecisionPolicy.spectral_dtype on the partial-fusion path — the same
    contract the real-input wrappers above already honor)."""
    if path == "ref":
        return ref_k.ref_truncated_cdft(xr, xi, modes)
    if path == "xla":
        return spectral.truncated_cdft(xr, xi, modes)
    mats = _dft_operands(spectral.cdft_mats(xr.shape[-1], modes),
                         operand_dtype or xr.dtype, 1, _rup(modes, 128))
    return _rowwise(dft_k._cdft_call, [xr, xi], mats, modes, block_rows,
                    interpret)


def padded_icdft(xr: jax.Array, xi: jax.Array, n: int, *,
                 path: str = "pallas", block_rows: int = 256,
                 interpret: Optional[bool] = None,
                 operand_dtype: Optional[str] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Inverse complex DFT from first-`modes` bins zero-padded to n.

    operand_dtype: see ``truncated_cdft``."""
    if path == "ref":
        return ref_k.ref_padded_icdft(xr, xi, n)
    if path == "xla":
        return spectral.padded_icdft(xr, xi, n)
    kp = _rup(xr.shape[-1], 128)
    mats = _dft_operands(spectral.cdft_mats(n, xr.shape[-1], True),
                         operand_dtype or xr.dtype, 0, kp)
    return _rowwise(dft_k._cdft_call, [xr, xi], mats, 0, block_rows,
                    interpret, pad_in_to=kp)


# ---------------------------------------------------------------------------
# Standalone CGEMM
# ---------------------------------------------------------------------------
def cgemm(ar: jax.Array, ai: jax.Array, br: jax.Array, bi: jax.Array, *,
          path: str = "pallas", bm: int = 128, bn: int = 128, bk: int = 128,
          interpret: Optional[bool] = None) -> Tuple[jax.Array, jax.Array]:
    """(M,K)x(K,N) complex matmul."""
    if path in ("ref", "xla"):
        return ref_k.ref_cgemm(ar, ai, br, bi)
    m, k = ar.shape
    _, n = br.shape
    bm, bn, bk = _pick_block(m, bm), _pick_block(n, bn), _pick_block(k, bk)
    mp, np_, kp = _rup(m, bm), _rup(n, bn), _rup(k, bk)
    pad2 = lambda a, r, c: _pad_axis(_pad_axis(a, 0, r), 1, c)
    cr, ci = cgemm_k.cgemm_call(
        pad2(ar, mp, kp), pad2(ai, mp, kp), pad2(br, kp, np_),
        pad2(bi, kp, np_), bm=bm, bn=bn, bk=bk,
        interpret=_interpret(interpret))
    return cr[:m, :n], ci[:m, :n]


# ---------------------------------------------------------------------------
# Fused FNO spectral layers and blocks (the paper's contribution),
# rank-generic.
#
# One implementation serves every spatial rank: the engine
# (kernels/engine.py) emits the fused forward, adjoint, and weight-gradient
# pallas_calls for any R, and the helpers below only handle padding, block
# selection, and operand caching. ``_fnond_fused`` is the one place a
# forward-family launch is laid out (kernel layout, plan clamp, padding).
#
# The pallas path is wrapped in a custom_vjp so training can stay on the
# fused kernels end-to-end. The spectral layer is the block with no bypass,
# no bias and a linear epilogue, so ONE custom_vjp family
# (``_fno_block_nd_pallas``) serves both. The layer is
# y = Re(((x·C…)∘W)·…E) — real-linear in both x and W — so:
#   * dx is the SAME fused DFT→CGEMM→iDFT pipeline run on the cotangent
#     with transposed DFT operands (spectral.realified_operand_mats
#     adjoint=True) and the weight swapped over (out, hidden);
#   * dW is the fused rank-reduction kernel (engine.fused_fnond_wgrad_call):
#     conj(Σ_b Ĝ·A) with both spectra computed in-kernel.
# ---------------------------------------------------------------------------
def _modes_key(modes) -> Tuple[int, ...]:
    return tuple(int(m) for m in modes)


def _default_policy(x, wr) -> PrecisionPolicy:
    """Policy inferred from the operands (legacy behavior): compute and
    spectral operands at x.dtype, dW at the weight dtype, f32 accumulate."""
    xd = jnp.dtype(x.dtype).name
    return PrecisionPolicy(param_dtype=jnp.dtype(wr.dtype).name,
                           compute_dtype=xd, spectral_dtype=xd)


def _pallas_setup(x, wr, modes, policy, bb, bo, bh, block_plan
                  ) -> Tuple[PrecisionPolicy, LaunchPlans]:
    """The policy and per-launch block plans a pallas custom_vjp runs
    under: ``policy`` (None infers one from the operands), and the
    tuned-cache resolver keyed on (rank, shape class, layout, per-launch
    variant, dtype) for the weight's (out, hidden), with explicit nonzero
    bb/bo/bh (or an ``FNOConfig.block_plan`` triple) overriding
    component-wise and ``_BLOCK_DEFAULTS`` as the fallback."""
    pol = policy or _default_policy(x, wr)
    override = tuple(block_plan) if block_plan else None
    plans = resolve_launch_plans(
        len(modes), hidden=wr.shape[1], out=wr.shape[0],
        spatial=tuple(x.shape[2:]), modes=modes,
        per_mode=wr.ndim == 2 + len(modes), policy=pol,
        override=override)
    return pol, plans.with_override(bb, bo, bh)


def _cast(pol, *arrays):
    """The cast contract of the custom_vjps: their primals (and so the
    cotangents the caller sees) keep the caller's dtypes, while every
    operand is cast here, inside the custom_vjp, to ``pol.compute_dtype``
    and the kernels accumulate at ``pol.accum_dtype`` (ROADMAP.md
    §Precision policy). None (no bypass, no bias) passes through."""
    cp = jnp.dtype(pol.compute_dtype)
    return tuple(None if a is None else a.astype(cp) for a in arrays)


def _trailing(a, shape):
    """a [B or O, H or O, …] with its trailing axes reshaped to `shape`:
    into or out of ``spectral.kernel_layout`` (rank 3 merges its last two
    axes, a row-major reshape)."""
    return a.reshape(a.shape[:2] + tuple(shape))


def _fnond_fused(x, wr, wi, modes, bb, bo, bh, interpret, pol,
                 adjoint: bool = False, out_dtype: str = None,
                 wb=None, bias=None, gy=None, act: str = "linear",
                 truncation: str = "low", lift=None, proj=None):
    """Pad to block multiples and invoke the rank-generic fused kernel.

    adjoint=True runs the input-cotangent pipeline: transposed DFT
    operands; the caller passes (out, hidden)-swapped weights. out_dtype
    overrides the emission dtype (backward emits dx at the primal dtype
    straight from the accumulator). wb [O,H] / bias [O] / act extend the
    kernel with the block epilogue (bypass GEMM riding the k-loop,
    +bias → activation at the ref write); gy feeds the "gelu_vjp" /
    "relu_vjp" backward-recompute epilogue. lift / proj fold the model's
    end MLPs into the launch (``_ends_fused_impl``): x is then the raw
    model input and the result the model output. ``modes`` are kept
    counts (``spectral.kept_modes``). A rank-3 layer runs in its kernel
    layout (``spectral.kernel_layout``): x, gy, y and a per-mode weight
    are reshaped around the call, which XLA folds away between blocks.
    """
    spatial = tuple(x.shape[2:])
    ksp, kmodes = spectral.kernel_layout(spatial, modes)
    x = _trailing(x, ksp)
    gy = None if gy is None else _trailing(gy, ksp)
    per_mode = wr.ndim == 2 + len(modes)
    if per_mode:
        wr, wi = (_trailing(w, kmodes) for w in (wr, wi))
    b = x.shape[0]
    o, h = wr.shape[:2]
    kp = spectral._mode_pad(modes)
    bb, bo, bh = legal_blocks(len(ksp), b, o, h, bb, bo, bh)
    bp, op_, hp = _rup(b, bb), _rup(o, bo), _rup(h, bh)
    mats = spectral.realified_operand_mats(
        spatial, _modes_key(modes), pol.spectral_dtype, adjoint, kp,
        truncation)
    ends = lift is not None or proj is not None
    cin = _rup(x.shape[1], 8) if lift is not None else hp
    xpad = _pad_axis(_pad_axis(x, 0, bp), 1, cin)

    def wpad(w):
        if per_mode and kp:
            w = _pad_axis(w, 2, kp)
        return _pad_axis(_pad_axis(w, 0, op_), 1, hp)

    wbp = None if wb is None else _pad_axis(_pad_axis(wb, 0, op_), 1, hp)
    biasp = None if bias is None else _pad_axis(bias[:, None], 0, op_)
    gyp = None if gy is None else _pad_axis(_pad_axis(gy, 0, bp), 1, op_)
    cout = o if proj is None else proj[3].shape[0]
    if ends:
        lift, proj = _engine_ends(lift, proj, cin, hp, op_)
    name = ("block_ends" if ends else
            "dx_adjoint" if adjoint else
            "gz_recompute" if act.endswith("_vjp") else
            "spectral_fwd" if wb is None else "block_fwd")
    y = engine.fused_fnond_call(xpad, wpad(wr), wpad(wi), *mats,
                                bb=bb, bo=bo, bh=bh, name=name,
                                interpret=interpret,
                                out_dtype=out_dtype,
                                acc_dtype=pol.accum_dtype,
                                wb=wbp, bias=biasp, gy=gyp, act=act,
                                lift=lift, proj=proj)
    return _trailing(y[:b, :cout], spatial)


def _outer_fwd_batched(x, spatial, modes, interpret, operand_dtype=None,
                       block_rows=256):
    """All outer forward stages (axes s_2..s_R) in ONE kernel launch.

    The separable outer transforms collapse into a single matmul with the
    Kronecker-combined operand (spectral.outer_fwd_mats) instead of one
    standalone DFT launch per axis. x: [B,H,s_1..s_R] real; returns the
    pair [B,H,s_1,K_R..K_2]."""
    r = len(spatial)
    ok = tuple(modes[1:])
    kk = int(np.prod(ok))
    mats = _dft_operands(
        spectral.outer_fwd_mats(tuple(spatial[1:]), ok),
        operand_dtype or x.dtype, 1, _rup(kk, 128))
    lead = x.shape[:3]
    xf = x.reshape(*lead, -1)
    zr, zi = _rowwise(dft_k._rdft_call, [xf], mats, kk, block_rows,
                      interpret)
    shape = lead + tuple(modes[r - 1:0:-1])  # (K_R .. K_2)
    return zr.reshape(shape), zi.reshape(shape)


def _outer_inv_batched(tr, ti, spatial, interpret, operand_dtype=None,
                       block_rows=256):
    """All outer inverse stages in one launch (adjoint of
    _outer_fwd_batched): t [B,O,s_1,K_R..K_2] complex pair → real
    [B,O,s_1,s_2..s_R] via the combined padded-inverse operand."""
    ok = tuple(tr.shape[3:][::-1])  # trailing (K_R..K_2) → (k_2..k_R)
    kk = int(np.prod(ok))
    kp = _rup(kk, 128)
    mats = _dft_operands(
        spectral.outer_inv_mats(tuple(spatial[1:]), ok),
        operand_dtype or tr.dtype, 0, kp)
    lead = tr.shape[:3]
    flat = lambda t: t.reshape(*lead, -1)
    y = _rowwise(dft_k._irdft_call, [flat(tr), flat(ti)], mats, 0,
                 block_rows, interpret, pad_in_to=kp)
    return y.reshape(lead + tuple(spatial[1:]))


def _fnond_partial(x, wr, wi, modes, bb, bo, bh, interpret, pol):
    """Paper-faithful partial fusion for rank R ≥ 2: the outer R-1 forward
    and inverse transforms run as standalone kernels (dft.py); only
    [cDFT_s1 → CGEMM → icDFT_s1] — the stages adjacent to the GEMM — are
    fused, matching TurboFNO §4.3. Rank 1 has no outer stages (partial ==
    full). Rank ≥ 3 batches all outer axes into one launch per direction
    (Kronecker-combined operands)."""
    r = len(modes)
    if r == 1:
        return _fnond_fused(x, wr, wi, modes, bb, bo, bh, interpret, pol)
    b, h = x.shape[:2]
    spatial = x.shape[2:]
    o = wr.shape[0]
    per_mode = wr.ndim == 2 + r
    bb, bo, bh, bp, op_, hp = _blocks(x, o, bb, bo, bh)
    xpad = _pad_axis(_pad_axis(x, 0, bp), 1, hp)

    # Outer forward stages: rank 2 is a single rDFT along s_2; rank ≥ 3
    # runs ALL outer axes (s_2..s_R) as one batched kernel launch. The
    # operands follow pol.spectral_dtype like the fused middle's.
    if r == 2:
        zr, zi = truncated_rdft(xpad, modes[-1], path="pallas",
                                interpret=interpret,
                                operand_dtype=pol.spectral_dtype)
    else:
        zr, zi = _outer_fwd_batched(xpad, spatial, modes, interpret,
                                    pol.spectral_dtype)

    # Fused middle on [B,H,s_1,K_R..K_2].
    mats = spectral.fused_operand_mats(
        tuple(spatial), _modes_key(modes), pol.spectral_dtype)
    fr, fi = mats[2 * r - 2], mats[2 * r - 1]  # forward cDFT along s_1
    gr, gi = mats[2 * r], mats[2 * r + 1]      # inverse cDFT along s_1
    wp = lambda w: _pad_axis(_pad_axis(w, 0, op_), 1, hp)
    yr, yi = engine.fused_fnond_core_call(
        zr, zi, wp(wr), wp(wi), fr, fi, gr, gi,
        bb=bb, bo=bo, bh=bh, interpret=interpret,
        acc_dtype=pol.accum_dtype)

    # Restore [B,O,s_1,K_R..K_2] layout and slice the channel padding.
    s = r - 1
    if per_mode:  # kernel emits [K_R..K_2, B, O, s_1]
        perm = (s, s + 1, s + 2) + tuple(range(s))
    else:  # kernel emits [B, K_R..K_2, O, s_1]
        perm = (0, s + 1, s + 2) + tuple(range(1, s + 1))
    tr = jnp.transpose(yr, perm)[:b, :o]
    ti = jnp.transpose(yi, perm)[:b, :o]

    # Outer inverse stages, mirrored: single irDFT at rank 2, one batched
    # launch at rank ≥ 3.
    if r == 2:
        return padded_irdft(tr, ti, spatial[-1], path="pallas",
                            interpret=interpret,
                            operand_dtype=pol.spectral_dtype)
    return _outer_inv_batched(tr, ti, spatial, interpret,
                              pol.spectral_dtype)


def _fnond_wgrad(x, gy, modes, bb, bo, bh, interpret, per_mode, pol,
                 out_dtype: str = None, with_bypass: bool = False,
                 truncation: str = "low"):
    """Fused weight cotangent: conj(Σ_b Ĝ·A) rank reduction; dW emitted at
    out_dtype (the param dtype under mixed precision). with_bypass=True
    (fused-block backward) appends (dwb [O,H], dbias [O]) from the same
    kernel."""
    r = len(modes)
    b, h = x.shape[:2]
    o = gy.shape[1]
    kp = spectral._mode_pad(modes)
    bb, bo, bh, bp, op_, hp = _blocks(x, o, bb, bo, bh)
    mats = spectral.wgrad_operand_mats(
        tuple(x.shape[2:]), _modes_key(modes), pol.spectral_dtype, kp,
        truncation)
    xpad = _pad_axis(_pad_axis(x, 0, bp), 1, hp)
    gpad = _pad_axis(_pad_axis(gy, 0, bp), 1, op_)
    out = engine.fused_fnond_wgrad_call(
        xpad, gpad, *mats, bb=bb, bo=bo, bh=bh, per_mode=per_mode,
        interpret=interpret, out_dtype=out_dtype,
        acc_dtype=pol.accum_dtype, with_bypass=with_bypass)
    dwr, dwi = out[:2]
    extra = (out[2][:o, :h], out[3][:o, 0]) if with_bypass else ()
    if per_mode:  # kernel emits [∏(K_R..K_1),O,H] -> [O,H,K_1..K_R]
        rev = tuple(m.shape[1] for m in mats[:2 * r:2])
        perm = (r, r + 1) + tuple(range(r - 1, -1, -1))
        sl = (slice(o), slice(h)) + tuple(slice(m) for m in modes)
        unflat = lambda d: jnp.transpose(d.reshape(rev + d.shape[1:]), perm)
        return (unflat(dwr)[sl], unflat(dwi)[sl]) + extra
    return (dwr[:o, :h], dwi[:o, :h]) + extra


def _check_partial(variant, truncation):
    """The paper-faithful partial fusion keeps TurboFNO's low corner."""
    if variant != "full" and truncation != "low":
        raise NotImplementedError(
            f"variant={variant!r} keeps the low corner only; "
            f"truncation={truncation!r} runs on the full-fusion variant")


def _fnond_xla(x, wr, wi, modes, pol=None, truncation: str = "low"):
    """Staged matmul formulation of the rank-R layer, fused by XLA.

    With a policy, operands are cast to the compute dtype first and the
    result is emitted at it — the parity reference for the pallas path at
    matching precision (accumulation stays f32 via preferred_element_type
    inside the spectral helpers). ``modes`` are kept counts."""
    if pol is not None:
        cp = jnp.dtype(pol.compute_dtype)
        x, wr, wi = x.astype(cp), wr.astype(cp), wi.astype(cp)
    r = len(modes)
    spatial = x.shape[2:]
    per_mode = wr.ndim == 2 + r
    zr, zi = spectral.truncated_rdft(x, modes[-1])
    for j in range(1, r):  # cDFT along s_{R-1}…s_1 -> [B,H,K_R..K_1]
        zr = jnp.moveaxis(zr, -(j + 1), -1)
        zi = jnp.moveaxis(zi, -(j + 1), -1)
        zr, zi = spectral.truncated_cdft(zr, zi, modes[r - 1 - j],
                                         truncation)
    fwd = "uvw"[:r]           # K_1..K_R (the weight layout order)
    rev = fwd[::-1]           # K_R..K_1 (the spectrum layout order)
    eq = (f"oh{fwd},bh{rev}->bo{rev}" if per_mode
          else f"oh,bh{rev}->bo{rev}")
    yr = jnp.einsum(eq, wr, zr) - jnp.einsum(eq, wi, zi)
    yi = jnp.einsum(eq, wr, zi) + jnp.einsum(eq, wi, zr)
    for j in range(r - 1):  # icDFT along s_1…s_{R-1}
        yr, yi = spectral.padded_icdft(yr, yi, spatial[j], truncation)
        yr = jnp.moveaxis(yr, -1, 2 + j)
        yi = jnp.moveaxis(yi, -1, 2 + j)
    y = spectral.padded_irdft(yr, yi, spatial[-1])
    return y.astype(x.dtype) if pol is not None else y


def _spectral_oracle(x, wr, wi, modes, path, policy, truncation):
    """The staged ref/xla spectral layer at kept counts ``modes``: the
    parity oracle the pallas path is held to."""
    if path == "ref":
        if policy is not None:  # oracle runs in f32, emits at compute dtype
            y32 = ref_k.ref_fnond(x.astype(jnp.float32),
                                  wr.astype(jnp.float32),
                                  wi.astype(jnp.float32), modes, truncation)
            return y32.astype(policy.compute_dtype)
        return ref_k.ref_fnond(x, wr, wi, modes, truncation)
    assert path == "xla", path
    return _fnond_xla(x, wr, wi, modes, policy, truncation)


def spectral_layer_nd(x: jax.Array, wr: jax.Array, wi: jax.Array,
                      modes: Sequence[int], *, path: str = "pallas",
                      variant: str = "full", bb: int = 0, bo: int = 0,
                      bh: int = 0,
                      interpret: Optional[bool] = None,
                      policy: Optional[PrecisionPolicy] = None,
                      block_plan: Optional[Tuple[int, int, int]] = None,
                      truncation: str = "low") -> jax.Array:
    """The FNO spectral layer at any rank R: y = Re(((x·C…)∘W)·…E).

    x: [B,H,s_1..s_R]; w: [O,H] shared or [O,H,k_1..k_R] per-mode, one
    ``modes`` entry per spatial axis. truncation: "low" keeps the first k
    bins of every axis (TurboFNO); "corners" the ± corners of Li et al.
    (a per-mode weight [O,H,2k_1,..,2k_{R-1},k_R]).

    variant: "full" fuses the entire layer in one kernel (beyond-paper,
    docs/DESIGN.md §3.4); "partial" fuses only the cDFT/CGEMM/icDFT stages
    around the CGEMM (paper-faithful) and runs the outer transforms as
    standalone launches, one batched launch per direction at rank ≥ 3. At
    rank 1 there are no outer stages, and "partial" means "full".

    path="pallas" is the block family with no bypass, no bias and a linear
    epilogue (``fno_block_nd``'s custom_vjp): jax.grad routes through the
    fused dx-adjoint and wgrad kernels, never falling back to XLA. policy
    sets the mixed-precision contract (bf16 kernel I/O with f32
    accumulators); None infers a uniform policy from the operand dtypes.
    Block sizes resolve through ``repro.tuning.resolve_launch_plans``
    (tuned cache → ``_BLOCK_DEFAULTS``); nonzero bb/bo/bh or a
    ``block_plan`` triple override component-wise. path="ref"/"xla" are
    the staged parity oracles.
    """
    modes = spectral.kept_modes(modes, truncation)
    if path in ("ref", "xla"):
        return _spectral_oracle(x, wr, wi, modes, path, policy, truncation)
    if len(modes) == 1:
        variant = "full"
    _check_partial(variant, truncation)
    pol, plans = _pallas_setup(x, wr, modes, policy, bb, bo, bh, block_plan)
    return _fno_block_nd_pallas(x, wr, wi, None, None, modes, variant, plans,
                                _interpret(interpret), pol, "linear", None,
                                truncation)


# ---------------------------------------------------------------------------
# Fused FNO BLOCK (beyond the spectral layer): the standard FNO block
# y = gelu(spectral(x) + bypass(x) + bias) (Li et al. 2020) in ONE
# pallas_call on the full-fusion path. The 1×1 bypass conv contracts the
# same hidden axis as the engine's CGEMM k-loop, so it rides the existing
# grid into a third VMEM accumulator and +bias → +spectral → gelu happen
# in the iDFT epilogue — the per-layer XLA ops (bypass GEMM, bias, sum,
# GELU) and their ~4 HBM round trips on B·H·∏s tensors disappear.
#
# End-to-end differentiable via its custom_vjp, which also serves the
# spectral layer alone (wb=None, bias=None, act="linear"):
#   * gz: one fused kernel recomputes the pre-activation z through the
#     same forward pipeline and emits gz = gy·gelu'(z) (act="gelu_vjp") —
#     z never touches HBM; a linear block skips it (gz = gy);
#   * dx = spectral_adjoint(gz) + gz·W_b: the SAME block kernel with
#     adjoint DFT operands, (out,hidden)-swapped spectral weight, and the
#     transposed bypass (if any) riding the k-loop;
#   * dW (and dW_b, dbias with a bypass): the wgrad kernel emits them all
#     from the refs it already holds in VMEM.
# The backward always runs the fully fused pipeline — partial and full
# compute the same function, so one adjoint serves both variants.
# ---------------------------------------------------------------------------
def _block_tail(s, x, wb, bias, out_dtype, act: str = "gelu"):
    """The staged block epilogue — XLA bypass GEMM + bias + activation on a
    spectral output s. Shared by the oracle paths AND the partial-variant
    pallas path so the parity target and the implementation can never
    diverge: z accumulates in f32, the single down-cast is the return."""
    byp = jnp.einsum("oh,bh...->bo...", wb.astype(x.dtype), x,
                     preferred_element_type=jnp.float32)
    z = (s.astype(jnp.float32) + byp
         + bias.astype(jnp.float32).reshape((1, -1) + (1,) * (x.ndim - 2)))
    return activate(z, act).astype(out_dtype)


ACTIVATIONS = ("gelu", "relu", "linear")


def activate(z, act: str):
    """The block activation: tanh-approximate GELU, ReLU, or none."""
    if act == "gelu":
        return jax.nn.gelu(z)
    if act == "relu":
        return jax.nn.relu(z)
    assert act == "linear", act
    return z


def _fno_block_oracle(x, wr, wi, wb, bias, modes, path, pol, act="gelu",
                      truncation="low"):
    """Staged parity oracle: spectral layer (ref/xla path) + XLA bypass +
    bias + activation — the exact math the one-kernel pallas path fuses.
    ``modes`` are kept counts."""
    s = _spectral_oracle(x, wr, wi, modes, path, pol, truncation)
    cp = jnp.dtype(pol.compute_dtype) if pol is not None else x.dtype
    return _block_tail(s, x.astype(cp), wb, bias, s.dtype, act)


def _fno_block_impl(x, wr, wi, wb, bias, modes, variant, plans,
                    interpret, pol, act, out_dtype, truncation):
    # `plans` is the per-launch LaunchPlans bundle (hashable nondiff arg):
    # the forward variants read fwd/core, the backward gz/dx/wgrad.
    # out_dtype (default: the compute dtype) overrides the single ref-write
    # emission — the TP-sharded dispatch keeps the partial pre-activations
    # at the accumulator dtype through the psum.
    od = jnp.dtype(out_dtype or pol.compute_dtype)
    x, wr, wi, wb, bias = _cast(pol, x, wr, wi, wb, bias)
    if variant == "full":
        return _fnond_fused(x, wr, wi, modes, *plans.fwd, interpret, pol,
                            wb=wb, bias=bias, act=act, out_dtype=od.name,
                            truncation=truncation)
    # Paper-faithful partial fusion keeps the multi-kernel spectral
    # pipeline; the block tail (bypass+bias+act) runs as XLA ops. The
    # BACKWARD still uses the fully fused adjoint (one linear map).
    s = _fnond_partial(x, wr, wi, modes, *plans.core, interpret, pol)
    return s if wb is None else _block_tail(s, x, wb, bias, od, act)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _fno_block_nd_pallas(x, wr, wi, wb, bias, modes, variant, plans,
                         interpret, pol, act, out_dtype, truncation):
    return _fno_block_impl(x, wr, wi, wb, bias, modes, variant, plans,
                           interpret, pol, act, out_dtype, truncation)


def _fno_block_vjp_fwd(x, wr, wi, wb, bias, modes, variant, plans,
                       interpret, pol, act, out_dtype, truncation):
    y = _fno_block_impl(x, wr, wi, wb, bias, modes, variant, plans,
                        interpret, pol, act, out_dtype, truncation)
    return y, (x, wr, wi, wb, bias)


def _fno_block_vjp_bwd(modes, variant, plans, interpret, pol, act,
                       out_dtype, truncation, res, gy):
    x, wr, wi, wb, bias = res
    xc, wrc, wic, wbc, biasc, gyc = _cast(pol, x, wr, wi, wb, bias, gy)
    if act != "linear":
        # (1) recompute the pre-activation through the fused forward and
        # form gz = gy·act'(z) in the epilogue — z never reaches HBM.
        gz = _fnond_fused(xc, wrc, wic, modes, *plans.gz, interpret, pol,
                          wb=wbc, bias=biasc, gy=gyc, act=act + "_vjp",
                          truncation=truncation)
    else:
        # Linear block (the spectral layer, a model's unactivated last
        # block, the TP-sharded partial): z IS the output, so the incoming
        # cotangent is gz directly — no recompute kernel.
        gz = gyc
    # (2) dx = spectral_adjoint(gz) + gz·W_b: the same block kernel with
    # adjoint operands, swapped spectral weight, transposed bypass, linear
    # epilogue; dx emitted at the primal dtype from the f32 accumulator.
    dx = _fnond_fused(gz, jnp.swapaxes(wrc, 0, 1), jnp.swapaxes(wic, 0, 1),
                      modes, *plans.dx, interpret, pol, adjoint=True,
                      out_dtype=jnp.dtype(x.dtype).name,
                      wb=None if wb is None else jnp.swapaxes(wbc, 0, 1),
                      truncation=truncation)
    # (3) dW (with a bypass also dW_b, dbias) from ONE wgrad kernel,
    # emitted at the param dtype straight from the f32 accumulators.
    dw = _fnond_wgrad(
        xc, gz, modes, *plans.wgrad, interpret,
        per_mode=wr.ndim == 2 + len(modes), pol=pol,
        out_dtype=jnp.dtype(wr.dtype).name, with_bypass=wb is not None,
        truncation=truncation)
    grads = (dx.astype(x.dtype), dw[0].astype(wr.dtype),
             dw[1].astype(wi.dtype))
    if wb is None:
        return grads + (None, None)
    return grads + (dw[2].astype(wb.dtype), dw[3].astype(bias.dtype))


_fno_block_nd_pallas.defvjp(_fno_block_vjp_fwd, _fno_block_vjp_bwd)


def fno_block_nd(x: jax.Array, wr: jax.Array, wi: jax.Array, wb: jax.Array,
                 bias: jax.Array, modes: Sequence[int], *,
                 path: str = "pallas", variant: str = "full",
                 bb: int = 0, bo: int = 0, bh: int = 0,
                 interpret: Optional[bool] = None,
                 policy: Optional[PrecisionPolicy] = None,
                 act: str = "gelu",
                 out_dtype: Optional[str] = None,
                 block_plan: Optional[Tuple[int, int, int]] = None,
                 truncation: str = "low"
                 ) -> jax.Array:
    """One whole FNO block: y = act(spectral(x) + x·W_bᵀ + bias).

    x: [B,H,s_1..s_R]; wr/wi: [O,H] or [O,H,k_1..k_R] spectral weight
    (``truncation="corners"``: [O,H,2k_1,..,2k_{R-1},k_R], the ± corners
    of Li et al.; default "low", TurboFNO's first k bins);
    wb: [O,H] bypass 1×1 conv (y_o += Σ_h x_h·wb[o,h]); bias: [O].

    path="pallas" + variant="full" lowers the ENTIRE block to one
    pallas_call, and jax.grad stays on fused kernels for all four
    cotangents (dx, dW, dW_b, dbias) via custom_vjp. variant="partial"
    keeps the paper-faithful multi-kernel spectral pipeline (XLA block
    tail) but shares the same fused backward. path="ref"/"xla" are the
    staged parity oracles. Block sizes come from the tuned-plan resolver
    (override → ``tuning/cache`` → ``_BLOCK_DEFAULTS``); nonzero bb/bo/bh
    or ``block_plan`` override component-wise across all five launches.
    policy: see spectral_layer_nd.

    act: "gelu" (the standard block), "relu" (Li et al.'s FNO-3D) or
    "linear" (pre-activation only — a model's unactivated last block, and
    the TP-sharded dispatch, which reduces partial pre-activations with a
    psum BEFORE the nonlinearity; its backward skips the gz-recompute
    kernel).

    out_dtype (pallas path only) overrides the ref-write emission dtype —
    the TP dispatch emits partials at the accumulator dtype so the psum
    stays f32 under the bf16 policy (ROADMAP.md §Precision policy).
    """
    modes = spectral.kept_modes(modes, truncation)
    assert act in ACTIVATIONS, act
    if path in ("ref", "xla"):
        return _fno_block_oracle(x, wr, wi, wb, bias, modes, path, policy,
                                 act, truncation)
    _check_partial(variant, truncation)
    pol, plans = _pallas_setup(x, wr, modes, policy, bb, bo, bh, block_plan)
    return _fno_block_nd_pallas(x, wr, wi, wb, bias, modes, variant, plans,
                                _interpret(interpret), pol, act, out_dtype,
                                truncation)


# ---------------------------------------------------------------------------
# Fused MODEL ENDS (docs/DESIGN.md §6): the pointwise lifting MLP folded
# into the FIRST fused block kernel and the projection MLP into the LAST
# one. Both MLPs are channel-pointwise, so the lift rides the engine's
# hidden k-loop (each k step derives its hidden block from the raw input
# in VMEM) and the projection runs as the iDFT epilogue's tail — the
# lifted/projected activations, ~2·B·lift·∏s elements per step at the
# model boundary, never round-trip HBM. Forward is ONE pallas_call (so an
# ends-fused L-layer model still traces exactly L pallas_calls); the
# BACKWARD is the jax.vjp of the staged composition below — recompute-
# based, XLA-fused, sharing `_block_tail`/`_fnond_xla` with the parity
# oracles so the adjoint math can never diverge from the target.
#
# Scope: single-device and pure-DP dispatch only. Under TP the hidden
# k-loop is sharded — the lift's inner activation would have to be
# computed per-shard (replicated flops) and the projection consumes the
# FULL hidden vector, which only exists after the final layer's psum; the
# ends therefore stay staged XLA ops under TP (core.fno guards).
# ---------------------------------------------------------------------------
def _pointwise(w, b, x):
    """Channel-pointwise dense matching core.fno._dense: y follows x's
    dtype, the bias broadcast happens before the cast so its grad
    reduction accumulates upstream in f32."""
    y = jnp.einsum("bc...,cd->bd...", x, w.astype(x.dtype))
    bb_ = b.reshape((1, -1) + (1,) * (y.ndim - 2))
    return y + jnp.broadcast_to(bb_, y.shape).astype(x.dtype)


def _ends_staged(x, wr, wi, wb, bias, ends, modes, path, pol):
    """Staged lift → block → projection composition — the parity oracle
    for the ends-fused kernel AND its backward's recompute target."""
    lift, proj = ends
    h = x
    if lift is not None:
        l1w, l1b, l2w, l2b = lift
        h = jax.nn.gelu(_pointwise(l1w, l1b, h))
        h = _pointwise(l2w, l2b, h)
    z = _fno_block_oracle(h, wr, wi, wb, bias, modes, path, pol, "gelu")
    if proj is not None:
        p1w, p1b, p2w, p2b = proj
        z = jax.nn.gelu(_pointwise(p1w, p1b, z))
        z = _pointwise(p2w, p2b, z)
    return z


def _engine_ends(lift, proj, cin, hp, op_):
    """The end MLPs in the engine's layout: each weight transposed to
    [out, in] and each bias a column, padded to the kernel's channel
    padding — the lift from the raw input's ``cin`` channels to the
    padded hidden ``hp``, the projection from the padded out channels
    ``op_``; the inner widths to multiples of 8."""
    col = lambda v, to: _pad_axis(v[:, None], 0, to)
    mat = lambda w, rto, cto: _pad_axis(
        _pad_axis(jnp.swapaxes(w, 0, 1), 0, rto), 1, cto)
    engine_lift = engine_proj = None
    if lift is not None:
        l1w, l1b, l2w, l2b = lift
        lp = _rup(l1w.shape[1], 8)
        engine_lift = (mat(l1w, lp, cin), col(l1b, lp),
                       mat(l2w, hp, lp), col(l2b, hp))
    if proj is not None:
        p1w, p1b, p2w, p2b = proj
        lp2 = _rup(p1w.shape[1], 8)
        coutp = _rup(p2w.shape[1], 8)
        engine_proj = (mat(p1w, lp2, op_), col(p1b, lp2),
                       mat(p2w, coutp, lp2), col(p2b, coutp))
    return engine_lift, engine_proj


def _ends_fused_impl(x, wr, wi, wb, bias, ends, modes, plans, interpret,
                     pol):
    """The single ends-fused launch (``_fnond_fused`` with the end MLPs).
    Reuses the block_fwd tuned plan; the proj epilogue pins bo to the
    padded O (it contracts the full hidden width), so the out-channel
    grid collapses to one step."""
    x, wr, wi, wb, bias = _cast(pol, x, wr, wi, wb, bias)
    lift, proj = (None if e is None else _cast(pol, *e) for e in ends)
    bb, bo, bh = plans.fwd
    if proj is not None:
        bo = _rup(wr.shape[0], 8)
    return _fnond_fused(x, wr, wi, modes, bb, bo, bh, interpret, pol,
                        wb=wb, bias=bias, act="gelu", lift=lift, proj=proj)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _fno_block_ends_pallas(x, wr, wi, wb, bias, ends, modes, plans,
                           interpret, pol):
    return _ends_fused_impl(x, wr, wi, wb, bias, ends, modes, plans,
                            interpret, pol)


def _ends_vjp_fwd(x, wr, wi, wb, bias, ends, modes, plans, interpret, pol):
    y = _ends_fused_impl(x, wr, wi, wb, bias, ends, modes, plans,
                         interpret, pol)
    return y, (x, wr, wi, wb, bias, ends)


def _ends_vjp_bwd(modes, plans, interpret, pol, res, gy):
    # The staged composition is the adjoint target: jax.vjp recomputes the
    # forward through the XLA-fused staging (the same math the kernel
    # fuses) and transposes it — every cotangent lands at its primal's
    # dtype because the casts live inside `_ends_staged`'s callees.
    x, wr, wi, wb, bias, ends = res
    _, vjp = jax.vjp(
        lambda x_, wr_, wi_, wb_, b_, e_: _ends_staged(
            x_, wr_, wi_, wb_, b_, e_, modes, "xla", pol),
        x, wr, wi, wb, bias, ends)
    return vjp(gy.astype(jnp.dtype(pol.compute_dtype)))


_fno_block_ends_pallas.defvjp(_ends_vjp_fwd, _ends_vjp_bwd)


def fno_block_ends_nd(x: jax.Array, wr: jax.Array, wi: jax.Array,
                      wb: jax.Array, bias: jax.Array,
                      modes: Sequence[int], *,
                      lift: Optional[Tuple] = None,
                      proj: Optional[Tuple] = None,
                      path: str = "pallas", variant: str = "full",
                      bb: int = 0, bo: int = 0, bh: int = 0,
                      interpret: Optional[bool] = None,
                      policy: Optional[PrecisionPolicy] = None,
                      block_plan: Optional[Tuple[int, int, int]] = None
                      ) -> jax.Array:
    """``fno_block_nd`` with the model's end MLPs folded into the kernel.

    lift = (l1w [C_in,L], l1b [L], l2w [L,H], l2b [H]) — core.fno's
    lift1/lift2 params; x is then the RAW model input [B,C_in,s…].
    proj = (p1w [H,L], p1b [L], p2w [L,C_out], p2b [C_out]) — proj1/proj2;
    the result is the model output [B,C_out,s…]. Either end may be None
    (first vs last layer of a multi-layer model); both on a 1-layer model.

    path="pallas" runs ONE pallas_call (variant "full" only) and is
    differentiable: the custom_vjp backward is the jax.vjp of the staged
    composition — recompute-based, so nothing extra is saved for backward.
    path="ref"/"xla" are the staged parity oracles.
    """
    modes = _modes_key(modes)
    ends = (lift, proj)
    if path in ("ref", "xla"):
        return _ends_staged(x, wr, wi, wb, bias, ends, modes, path,
                            policy or _default_policy(x, wr))
    assert variant == "full", \
        "fused ends require the full-fusion variant (partial stays staged)"
    pol, plans = _pallas_setup(x, wr, modes, policy, bb, bo, bh, block_plan)
    return _fno_block_ends_pallas(x, wr, wi, wb, bias, ends, modes, plans,
                                  _interpret(interpret), pol)


# ---------------------------------------------------------------------------
# The staged ends as one launch (``engine.end_mlp_call``): the model's lift
# or projection MLP over an activation in the forward kernels' layout. The
# number of bf16 products per f32 contraction follows the operands and the
# precision the staged einsum would run at; the backward is the jax.vjp of
# the staged chain, recomputed in XLA, so gradients are what they were.
# ---------------------------------------------------------------------------
_TERMS_AT = {None: 1, "default": 1, "bfloat16": 1, "BF16_BF16_F32": 1,
             "high": 3, "tensorfloat32": 3, "BF16_BF16_F32_X3": 3}


def end_mlp_terms(dtype) -> int:
    """The bf16 products (``engine.END_MLP_TERMS``) an f32 contraction of
    `dtype` operands is made of where the launch runs, as XLA resolves
    the einsum it replaces: one for bf16 operands; for f32 on the TPU, 1
    at the default matmul precision, 3 at "high", 6 at "highest"; 6
    elsewhere, where XLA computes f32 dots in f32 whatever the
    precision."""
    if jnp.dtype(dtype) != jnp.float32:
        return 1
    if not on_tpu():
        return 6
    return _TERMS_AT.get(jax.config.jax_default_matmul_precision, 6)


def _end_mlp_staged(x, layers, act):
    """The staged chain ``end_mlp`` fuses (core.fno's ``_dense`` →
    activation → ``_dense``): its parity oracle and its backward."""
    for i, (w, b) in enumerate(layers):
        if i:
            x = activate(x, act)
        x = _pointwise(w, b, x)
    return x


def _end_mlp_impl(x, layers, act, terms, interpret):
    rows, lanes = end_mlp_window(
        x.shape[2:], x.shape[1] + layers[-1][0].shape[1],
        jnp.dtype(x.dtype).itemsize)
    return engine.end_mlp_call(
        x, tuple((jnp.swapaxes(w, 0, 1), b) for w, b in layers), act=act,
        terms=terms, rows=rows, lanes=lanes, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _end_mlp_pallas(x, layers, act, terms, interpret):
    return _end_mlp_impl(x, layers, act, terms, interpret)


def _end_mlp_vjp_fwd(x, layers, act, terms, interpret):
    return _end_mlp_impl(x, layers, act, terms, interpret), (x, layers)


def _end_mlp_vjp_bwd(act, terms, interpret, res, gy):
    _, vjp = jax.vjp(lambda x_, l_: _end_mlp_staged(x_, l_, act), *res)
    return vjp(gy)


_end_mlp_pallas.defvjp(_end_mlp_vjp_fwd, _end_mlp_vjp_bwd)


def end_mlp(x: jax.Array, layers: Sequence[Tuple[jax.Array, jax.Array]],
            act: str = "gelu", *,
            interpret: Optional[bool] = None) -> jax.Array:
    """A model end's pointwise MLP — one Linear, or Linear → act → Linear
    — over the channels of x in ONE pallas_call (``end_mlp``).

    x: [B, C, s_1, s_2] or [B, C, S], the forward kernels' layout
    (``spectral.kernel_layout``), at the compute dtype; layers: (w [C, D],
    b [D]) pairs as core.fno's ``_dense`` params. Computes what
    ``_end_mlp_staged`` computes at the precision its einsums would run
    at (``end_mlp_terms``), with the inner activation kept in VMEM;
    differentiable through the staged chain's VJP."""
    layers = tuple((w, b) for w, b in layers)
    return _end_mlp_pallas(x, layers, act, end_mlp_terms(x.dtype),
                           _interpret(interpret))


# ---------------------------------------------------------------------------
# DP×TP shard_map dispatch of the fused block (docs/DESIGN.md §6).
#
# DP shards the leading batch axis over `batch_axes`; TP shards the HIDDEN
# axis — the engine's k-loop contraction — over `model_axis`, so every
# shard runs the SAME fused kernel on its hidden slice and produces a
# partial pre-activation. Two layouts complete the sharded contraction:
#
#   tp_layout="scatter" (production): a psum_scatter over the model axis
#     emits the NEXT layer's hidden shard directly — (tp-1)/tp of the
#     tensor crosses the wire and the output lands already sharded
#     P(batch, model), so the inter-layer re-shard disappears. The
#     collective is ``sharding.scatter_sum`` — a custom_vjp whose backward
#     is the mirrored all_gather — so jax.grad stays end-to-end
#     differentiable through the scattered layout. tp_overlap=True runs
#     the same reduction as a ppermute ring (tp-1 async chunk hops XLA
#     can hide under neighboring k-loop compute).
#
#   tp_layout="psum" (legacy/final-layer): ONE lax.psum per layer on the
#     pre-activation — 2(tp-1)/tp wire bytes, replicated output. The FINAL
#     TP layer always uses this: the projection consumes the full hidden
#     vector, so there is no next shard to scatter into.
#
# Either way bias + GELU apply only after the cross-shard reduction (a
# nonlinearity cannot commute past a sharded contraction), as XLA ops on
# the reduced value while the kernel keeps act="linear". Every spec is
# guard_spec-ed: an axis that does not divide its dim degrades to
# replication instead of erroring.
# ---------------------------------------------------------------------------
def fno_block_nd_sharded(x: jax.Array, wr: jax.Array, wi: jax.Array,
                         wb: jax.Array, bias: jax.Array,
                         modes: Sequence[int], *, mesh,
                         batch_axes: Sequence[str] = ("data",),
                         model_axis: Optional[str] = "model",
                         variant: str = "full", bb: int = 0, bo: int = 0,
                         bh: int = 0, interpret: Optional[bool] = None,
                         policy: Optional[PrecisionPolicy] = None,
                         act: str = "gelu",
                         tp_layout: str = "psum",
                         tp_overlap: bool = False,
                         ends: Optional[Tuple] = None,
                         block_plan: Optional[Tuple[int, int, int]] = None,
                         truncation: str = "low"
                         ) -> jax.Array:
    """``fno_block_nd`` under shard_map on a (DP×TP) mesh — the production
    dispatch behind ``core.spectral_conv.apply_fno_block_nd`` whenever a
    ``sharding_context`` is active. Fully differentiable: shard_map
    transposes the collectives for the backward (psum → replication;
    scatter_sum carries its own mirrored-all_gather custom_vjp), and each
    shard's backward stays on the fused adjoint/wgrad kernels.

    tp_layout: "psum" replicates the layer output (one all-reduce);
    "scatter" emits it sharded P(batch, model) over the hidden axis via
    psum_scatter — half the wire bytes; the caller threads "scatter" for
    interior TP layers and "psum" for the final one (core.fno.apply_fno).
    tp_overlap=True (scattered only) uses the ppermute-ring reduction.

    ends: optional (lift, proj) tuple for ``fno_block_ends_nd`` — pure-DP
    meshes only (the end params replicate across shards); core.fno keeps
    the ends staged whenever TP is on.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import (compat_shard_map, guard_spec,
                                            ring_scatter_sum, scatter_sum)

    assert tp_layout in ("psum", "scatter"), tp_layout
    modes = _modes_key(modes)
    r = len(modes)
    sp0 = (None,) * r
    pol = policy or _default_policy(x, wr)
    b_axes = tuple(a for a in batch_axes if a in mesh.shape)
    b_ent = (b_axes if len(b_axes) > 1 else b_axes[0]) if b_axes else None
    tp = mesh.shape.get(model_axis, 1) if model_axis else 1
    o = wr.shape[0]
    xspec = guard_spec(P(b_ent, model_axis if tp > 1 else None, *sp0),
                       x.shape, mesh)
    tp_on = tp > 1 and xspec[1] is not None
    # The scattered layout additionally needs the OUTPUT channel dim to
    # divide tp (each shard keeps 1/tp of it); degrade to psum otherwise.
    scatter = tp_layout == "scatter" and tp_on and o % tp == 0
    h_ent = model_axis if tp_on else None
    wspec = guard_spec(P(None, h_ent, *((None,) * (wr.ndim - 2))),
                       wr.shape, mesh)
    wbspec = guard_spec(P(None, h_ent), wb.shape, mesh)
    bspec = P(model_axis) if scatter else P(None)
    out_spec = P(xspec[0], model_axis if scatter else None, *sp0)
    kw = dict(variant=variant, bb=bb, bo=bo, bh=bh, interpret=interpret,
              policy=pol, block_plan=block_plan)
    has_ends = ends is not None and any(e is not None for e in ends)
    if has_ends:
        # Ends replicate — pure-DP dispatch only (core.fno guards TP off).
        assert not tp_on and act == "gelu" and truncation == "low", (
            tp_on, act, truncation)
        ends_specs = jax.tree_util.tree_map(
            lambda a: P(*(None,) * a.ndim), ends)
        fn = compat_shard_map(
            lambda xl, wrl, wil, wbl, bl, el: fno_block_ends_nd(
                xl, wrl, wil, wbl, bl, modes, lift=el[0], proj=el[1],
                path="pallas", **kw),
            mesh, in_specs=(xspec, wspec, wspec, wbspec, bspec, ends_specs),
            out_specs=out_spec)
        return fn(x, wr, wi, wb, bias, ends)

    def local(xl, wrl, wil, wbl, bl):
        if not tp_on:
            return fno_block_nd(xl, wrl, wil, wbl, bl, modes,
                                path="pallas", act=act,
                                truncation=truncation, **kw)
        # Partial pre-activations emit at the ACCUMULATOR dtype (f32 under
        # the bf16 policy) so the cross-shard contraction — reduction +
        # bias + activation — stays f32 end-to-end; the single down-cast
        # to the compute dtype is the return (same contract as the
        # in-kernel epilogue it replaces).
        # The kernel's bias slot gets a full-width zero (under the
        # scattered layout bl is this shard's 1/tp slice — the real bias
        # applies only after the reduction, on the scattered chunk).
        z = fno_block_nd(xl, wrl, wil, wbl,
                         jnp.zeros((wrl.shape[0],), bl.dtype), modes,
                         path="pallas", act="linear",
                         out_dtype=pol.accum_dtype, truncation=truncation,
                         **kw)
        if scatter:
            # bl arrives pre-sliced to this shard's chunk (bspec).
            z = (ring_scatter_sum(z, model_axis, tp) if tp_overlap
                 else scatter_sum(z, model_axis))
        else:
            z = jax.lax.psum(z, model_axis)
        z = z + bl.astype(z.dtype).reshape((1, -1) + (1,) * r)
        return activate(z, act).astype(jnp.dtype(pol.compute_dtype))

    fn = compat_shard_map(
        local, mesh,
        in_specs=(xspec, wspec, wspec, wbspec, bspec),
        out_specs=out_spec)
    return fn(x, wr, wi, wb, bias)
