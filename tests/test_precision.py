"""Mixed-precision policy: bf16 spectral stack vs the f32 XLA reference.

Documented tolerances (ROADMAP.md §Precision policy): bf16 has an 8-bit
mantissa, so with f32 VMEM accumulators the fused layers hold ~1% relative
error forward and backward; casts happen only at ref-write boundaries —
outputs at the compute dtype, dx at the primal input dtype, dW at the
param dtype (f32 master weights under the bf16 preset).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import PrecisionPolicy
from repro.configs.fno import with_precision
from repro.kernels import ops

BF16 = PrecisionPolicy.from_name("bf16")
# bf16 I/O with f32 accumulation: observed max rel error ~0.5% across the
# rank sweep; 2% headroom. Gradients see the forward's bf16 error twice
# (once through the nonlinear readout's cotangent, once through the
# adjoint pipeline), so they get 5%.
TOL_BF16 = dict(rtol=2e-2, atol=2e-2)
TOL_BF16_GRAD = dict(rtol=5e-2, atol=5e-2)

_CASES = {
    1: ((48,), (11,)),
    2: ((16, 32), (5, 9)),
    3: ((8, 8, 16), (3, 3, 5)),
}


def _mk(rng, *s, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(scale * rng.normal(size=s), dtype)


def _layer_fn(rank, modes, path, policy=None, variant="full"):
    return lambda x, wr, wi: ops.spectral_layer_nd(
        x, wr, wi, modes, path=path, variant=variant, policy=policy)


def _allclose_rel(a, b, **tol):
    """assert_allclose with the tolerance scaled to the reference
    magnitude (bf16 error is relative to the output scale)."""
    scale = max(float(jnp.abs(b).max()), 1.0)
    np.testing.assert_allclose(np.asarray(a, np.float32) / scale,
                               np.asarray(b, np.float32) / scale, **tol)


@pytest.mark.parametrize("rank", [1, 2, 3])
@pytest.mark.parametrize("weight_mode", ["shared", "per_mode"])
@pytest.mark.parametrize("variant", ["full", "partial"])
def test_bf16_forward_matches_f32_xla(rank, weight_mode, variant):
    """pallas bf16 policy forward vs the f32 XLA reference, every rank,
    both weight layouts, both fusion variants."""
    if rank == 1 and variant == "partial":
        pytest.skip("rank 1 has no partial variant")
    spatial, modes = _CASES[rank]
    rng = np.random.default_rng(rank * 11 + len(spatial))
    x = _mk(rng, 2, 8, *spatial)
    wshape = (6, 8) if weight_mode == "shared" else (6, 8) + modes
    wr = _mk(rng, *wshape, scale=1.0 / 8)
    wi = _mk(rng, *wshape, scale=1.0 / 8)
    y = _layer_fn(rank, modes, "pallas", BF16, variant)(x, wr, wi)
    assert y.dtype == jnp.bfloat16  # emitted at the compute dtype
    yref = _layer_fn(rank, modes, "xla")(x, wr, wi)
    _allclose_rel(y, yref, **TOL_BF16)


@pytest.mark.parametrize("rank", [1, 2, 3])
@pytest.mark.parametrize("variant", ["full", "partial"])
def test_bf16_grads_match_f32_xla(rank, variant):
    """jax.grad through the bf16 fused pipeline (adjoint + wgrad kernels)
    vs f32 XLA: dx and dW agree to bf16 tolerance, and the cotangents are
    emitted at the PRIMAL dtypes — dx at x.dtype, dW at the f32 param
    dtype ("accumulate cotangents in f32 VMEM, emit dW at param dtype")."""
    if rank == 1 and variant == "partial":
        pytest.skip("rank 1 has no partial variant")
    spatial, modes = _CASES[rank]
    rng = np.random.default_rng(rank * 7)
    x = _mk(rng, 2, 8, *spatial)
    wr = _mk(rng, 6, 8, scale=1.0 / 8)
    wi = _mk(rng, 6, 8, scale=1.0 / 8)

    def grads(fn):
        loss = lambda x, wr, wi: jnp.sum(
            jnp.sin(fn(x, wr, wi).astype(jnp.float32)))
        return jax.grad(loss, argnums=(0, 1, 2))(x, wr, wi)

    gp = grads(_layer_fn(rank, modes, "pallas", BF16, variant))
    gx = grads(_layer_fn(rank, modes, "xla"))
    for name, a, b in zip(("dx", "dwr", "dwi"), gp, gx):
        assert a.dtype == jnp.float32, name  # primal (param/master) dtype
        _allclose_rel(a, b, err_msg=name, **TOL_BF16_GRAD)


def test_bf16_permode_wgrad_dtype_and_parity():
    """Per-mode weights: dW keeps the [O,H,k1,k2] layout and the f32 param
    dtype under the bf16 policy."""
    rng = np.random.default_rng(3)
    x = _mk(rng, 2, 8, 16, 32)
    wr = _mk(rng, 6, 8, 5, 9, scale=1.0 / 8)
    wi = _mk(rng, 6, 8, 5, 9, scale=1.0 / 8)

    def grads(fn):
        loss = lambda x, wr, wi: jnp.sum(
            jnp.sin(fn(x, wr, wi).astype(jnp.float32)))
        return jax.grad(loss, argnums=(1, 2))(x, wr, wi)

    gp = grads(_layer_fn(2, (5, 9), "pallas", BF16))
    gx = grads(_layer_fn(2, (5, 9), "xla"))
    for a, b in zip(gp, gx):
        assert a.dtype == jnp.float32 and a.shape == (6, 8, 5, 9)
        _allclose_rel(a, b, **TOL_BF16_GRAD)


@pytest.mark.parametrize("rank", [1, 2, 3])
@pytest.mark.parametrize("variant", ["full", "partial"])
def test_bf16_fused_block_forward_and_grads(rank, variant):
    """The fused FNO block under the bf16 policy: forward within 2e-2 of
    the f32 XLA oracle, all four cotangents within 5e-2, and the emission
    dtypes honor the cast contract — y at the compute dtype, dx at the
    primal x dtype, dW/dW_b/dbias at the (f32 master) param dtype."""
    if rank == 1 and variant == "partial":
        pytest.skip("rank 1 has no partial variant")
    spatial, modes = _CASES[rank]
    rng = np.random.default_rng(rank * 13)
    x = _mk(rng, 2, 8, *spatial)
    wr = _mk(rng, 6, 8, scale=1.0 / 8)
    wi = _mk(rng, 6, 8, scale=1.0 / 8)
    wb = _mk(rng, 6, 8, scale=1.0 / 8)
    bias = _mk(rng, 6, scale=0.3)

    def block(path, policy=None):
        kw = {"policy": policy} if policy is not None else {}
        return lambda *a: ops.fno_block_nd(
            *a, modes, path=path,
            variant=variant if path == "pallas" else "full", **kw)

    y = block("pallas", BF16)(x, wr, wi, wb, bias)
    assert y.dtype == jnp.bfloat16
    _allclose_rel(y, block("xla")(x, wr, wi, wb, bias), **TOL_BF16)

    def grads(fn):
        loss = lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32)))
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(x, wr, wi, wb, bias)

    gp = grads(block("pallas", BF16))
    gx = grads(block("xla"))
    for name, a, b in zip(("dx", "dwr", "dwi", "dwb", "dbias"), gp, gx):
        assert a.dtype == jnp.float32, name  # primal / master-param dtype
        _allclose_rel(a, b, err_msg=name, **TOL_BF16_GRAD)


def test_policy_presets():
    f32 = PrecisionPolicy.from_name("f32")
    assert f32 == PrecisionPolicy.from_name("float32") == PrecisionPolicy()
    assert not f32.is_mixed
    bf16 = PrecisionPolicy.from_name("bf16")
    assert bf16 == PrecisionPolicy.from_name("bfloat16")
    assert bf16.is_mixed
    assert bf16.compute_dtype == bf16.spectral_dtype == "bfloat16"
    assert bf16.param_dtype == bf16.accum_dtype == "float32"
    assert bf16.grad_acc_dtype == "float32"
    # non-preset dtype names keep the historical FNOConfig.dtype contract:
    # a uniform policy at that dtype (f32 accumulation)
    f64 = PrecisionPolicy.from_name("float64")
    assert f64.param_dtype == f64.compute_dtype == "float64"
    assert f64.accum_dtype == "float32"
    cfg = with_precision(get_config("fno2d", reduced=True), "bf16")
    assert cfg.precision == bf16 and cfg.dtype == "bfloat16"
    assert get_config("fno2d", reduced=True).precision == f32


def test_operand_mats_cache_keys_on_dtype():
    """Bugfix satellite: the lru_cached bundle builders key on the operand
    dtype — a bf16 trace must never be served a cached f32 bundle."""
    from repro.core import spectral as sp
    a32 = sp.fused_operand_mats((16, 16), (5, 5), "float32", False, 0)
    a16 = sp.fused_operand_mats((16, 16), (5, 5), "bfloat16", False, 0)
    assert all(m.dtype == jnp.float32 for m in a32)
    assert all(m.dtype == jnp.bfloat16 for m in a16)
    assert not any(x is y for x, y in zip(a32, a16))
    w32 = sp.wgrad_operand_mats((16, 16), (5, 5), "float32", 0)
    w16 = sp.wgrad_operand_mats((16, 16), (5, 5), "bfloat16", 0)
    assert all(m.dtype == jnp.bfloat16 for m in w16)
    assert not any(x is y for x, y in zip(w32, w16))
    # the batched outer-stage builders follow the same contract
    o32 = sp.outer_fwd_mats((8, 16), (3, 5), "float32")
    assert all(m.dtype == np.float32 for m in o32)
    i32 = sp.outer_inv_mats((8, 16), (3, 5), "float32")
    assert i32[0].shape == (15, 128) and o32[0].shape == (128, 15)


def test_outer_batched_matches_staged_chain():
    """Rank-3 partial satellite: the Kronecker-combined outer operands
    reproduce the per-axis transform chain they replaced."""
    from repro.core import spectral as sp
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 3, 4, 8, 16)), jnp.float32)
    # staged: rDFT along s_3 (keep 5), then cDFT along s_2 (keep 3)
    zr, zi = sp.truncated_rdft(x, 5)
    zr, zi = (jnp.moveaxis(z, -2, -1) for z in (zr, zi))
    zr, zi = sp.truncated_cdft(zr, zi, 3)  # [2,3,4,K3=5,K2=3]
    mr, mi = sp.outer_fwd_mats((8, 16), (3, 5))
    xf = x.reshape(2, 3, 4, -1)
    br = xf @ jnp.asarray(mr)
    bi = xf @ jnp.asarray(mi)
    np.testing.assert_allclose(np.asarray(br).reshape(2, 3, 4, 5, 3),
                               np.asarray(zr), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(bi).reshape(2, 3, 4, 5, 3),
                               np.asarray(zi), rtol=1e-4, atol=1e-4)
    # inverse: staged icDFT along K_2 then irDFT along K_3
    tr, ti = sp.padded_icdft(zr, zi, 8)
    tr, ti = (jnp.moveaxis(t, -1, 3) for t in (tr, ti))
    y = sp.padded_irdft(tr, ti, 16)  # [2,3,4,8,16]
    er, ei = sp.outer_inv_mats((8, 16), (3, 5))
    zf_r = zr.reshape(2, 3, 4, -1)
    zf_i = zi.reshape(2, 3, 4, -1)
    yb = zf_r @ jnp.asarray(er) - zf_i @ jnp.asarray(ei)
    np.testing.assert_allclose(np.asarray(yb).reshape(2, 3, 4, 8, 16),
                               np.asarray(y), rtol=1e-4, atol=1e-4)


def test_train_step_bf16_smoke():
    """bf16 convergence smoke: the fused-path mixed-precision train step
    overfits one batch (loss drops), keeps master params in f32, and
    tracks the f32 run."""
    from repro.core import fno as fno_mod
    from repro.optim import AdamW
    from repro.optim.schedule import constant
    from repro.train.train_step import make_train_step

    rng = np.random.default_rng(0)
    losses = {}
    for dname in ("f32", "bf16"):
        cfg = with_precision(get_config("fno2d", reduced=True), dname)
        params = fno_mod.init_fno(jax.random.PRNGKey(0), cfg)
        assert all(l.dtype == jnp.float32
                   for l in jax.tree_util.tree_leaves(params))
        opt = AdamW(lr=constant(3e-3))
        step = jax.jit(make_train_step(cfg, opt, fno_path="pallas"))
        state = opt.init(params)
        batch = {"x": _mk(rng, 2, cfg.in_channels, *cfg.spatial),
                 "y": _mk(rng, 2, cfg.out_channels, *cfg.spatial)}
        hist, gnorms = [], []
        for _ in range(5):
            params, state, m = step(params, state, batch)
            hist.append(float(m["loss"]))
            gnorms.append(float(m["grad_norm"]))
        # master params stay f32 through the AdamW update
        assert all(l.dtype == jnp.float32
                   for l in jax.tree_util.tree_leaves(params))
        assert np.isfinite(hist).all()
        assert hist[-1] < hist[0], hist
        losses[dname] = (hist, gnorms)
        rng = np.random.default_rng(0)  # same batch for both runs
    np.testing.assert_allclose(losses["bf16"][0][0], losses["f32"][0][0],
                               rtol=3e-2)
    # grad-norm parity guards the bias-grad reduction: a bf16 sum over a
    # coherent cotangent field swamps (sticks at its first power of two)
    # unless the cast-VJP upcasts it to f32 first (core/fno._dense).
    np.testing.assert_allclose(losses["bf16"][1][0], losses["f32"][1][0],
                               rtol=5e-2)


def test_fno_model_bytes_predicts_bf16_reduction():
    """The dtype-aware roofline byte model: bf16 halves the compute-dtype
    traffic while master-param terms stay f32, so the predicted ratio
    lands strictly between 0.5 and 1."""
    from repro.roofline.analysis import dtype_bytes, fno_model_bytes

    assert dtype_bytes("float32") == dtype_bytes("f32") == 4
    assert dtype_bytes("bfloat16") == dtype_bytes("bf16") == 2
    cfg = get_config("fno2d", reduced=False)
    for variant in ("full", "partial"):
        b32 = fno_model_bytes(cfg, 4, variant=variant)
        b16 = fno_model_bytes(with_precision(cfg, "bf16"), 4,
                              variant=variant)
        ratio = b16 / b32
        assert 0.5 < ratio < 0.9, (variant, ratio)
    # inference has no param-master traffic beyond the weight reads
    i32 = fno_model_bytes(cfg, 4, training=False)
    i16 = fno_model_bytes(with_precision(cfg, "bf16"), 4, training=False)
    assert abs(i16 / i32 - 0.5) < 1e-6
    # partial fusion moves strictly more bytes than full fusion
    assert fno_model_bytes(cfg, 4, variant="partial") > fno_model_bytes(
        cfg, 4, variant="full")
    # whole-block fusion (PR 4) strictly reduces modeled traffic again —
    # the spectral-y / bypass-y / sum / GELU round trips disappear
    for training in (True, False):
        assert fno_model_bytes(cfg, 4, fuse_block=True,
                               training=training) < fno_model_bytes(
            cfg, 4, fuse_block=False, training=training), training
    # and cfg.fuse_block is the default source of the flag
    from repro.configs.fno import with_fuse_block
    assert fno_model_bytes(with_fuse_block(cfg), 4) == fno_model_bytes(
        cfg, 4, fuse_block=True)


def test_grad_acc_dtype_follows_policy():
    """make_train_step picks the policy's grad-accumulation dtype for the
    microbatch buffer (the existing grad_acc_dtype hook, now policy-fed)."""
    from repro.core import fno as fno_mod
    from repro.optim import AdamW
    from repro.optim.schedule import constant
    from repro.train.train_step import make_train_step

    cfg = dataclasses.replace(
        with_precision(get_config("fno2d", reduced=True), "bf16"),
        policy=dataclasses.replace(PrecisionPolicy.from_name("bf16"),
                                   grad_acc_dtype="bfloat16"))
    params = fno_mod.init_fno(jax.random.PRNGKey(0), cfg)
    opt = AdamW(lr=constant(1e-3))
    step = jax.jit(make_train_step(cfg, opt, fno_path="xla",
                                   microbatches=2))
    rng = np.random.default_rng(1)
    batch = {"x": _mk(rng, 4, cfg.in_channels, *cfg.spatial),
             "y": _mk(rng, 4, cfg.out_channels, *cfg.spatial)}
    p, s, m = step(params, opt.init(params), batch)
    assert bool(jnp.isfinite(m["loss"]))
    assert all(l.dtype == jnp.float32
               for l in jax.tree_util.tree_leaves(p))
