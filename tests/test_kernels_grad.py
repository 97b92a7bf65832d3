"""Differentiability of the fused Pallas spectral layers.

jax.grad through path="pallas" must match path="xla" (which XLA
differentiates automatically) to 1e-4 in f32 — for dx, dwr, and dwi, in
1D/2D/3D, shared and per-mode weights, full and partial fusion. Plus a
train_step smoke test with fno_path="pallas" proving the trainer never
falls back to XLA.

A nonlinear readout (sin) makes the incoming cotangent non-trivial so the
adjoint pipeline is exercised with a dense, structured gy.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops

TOL = dict(rtol=1e-4, atol=1e-4)


def _mk(rng, *s, scale=1.0):
    return jnp.asarray(scale * rng.normal(size=s), jnp.float32)


def _grads(layer_fn, x, wr, wi):
    loss = lambda x, wr, wi: jnp.sum(jnp.sin(layer_fn(x, wr, wi)))
    return jax.grad(loss, argnums=(0, 1, 2))(x, wr, wi)


def _assert_grads_match(make_fn, x, wr, wi):
    gp = _grads(make_fn("pallas"), x, wr, wi)
    gx = _grads(make_fn("xla"), x, wr, wi)
    for name, a, b in zip(("dx", "dwr", "dwi"), gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), err_msg=name,
                                   **TOL)


CASES_1D = [
    # B, H, O, N, K
    (2, 8, 6, 64, 17),
    (3, 16, 16, 128, 33),
]


@pytest.mark.parametrize("b,h,o,n,k", CASES_1D)
@pytest.mark.parametrize("weight_mode", ["shared", "per_mode"])
def test_grad_fused_fno1d(b, h, o, n, k, weight_mode):
    rng = np.random.default_rng(b * 13 + k)
    x = _mk(rng, b, h, n)
    wshape = (o, h) if weight_mode == "shared" else (o, h, k)
    wr = _mk(rng, *wshape, scale=1.0 / h)
    wi = _mk(rng, *wshape, scale=1.0 / h)
    mk = lambda p: lambda x, wr, wi: ops.spectral_layer_nd(
        x, wr, wi, (k,), path=p)
    _assert_grads_match(mk, x, wr, wi)


CASES_2D = [
    # B, H, O, X, Y, KX, KY
    (2, 8, 6, 16, 32, 5, 9),
    (1, 12, 12, 32, 32, 8, 8),
]


@pytest.mark.parametrize("b,h,o,x_,y_,kx,ky", CASES_2D)
@pytest.mark.parametrize("variant", ["full", "partial"])
def test_grad_fused_fno2d_shared(b, h, o, x_, y_, kx, ky, variant):
    rng = np.random.default_rng(x_ * 3 + ky)
    x = _mk(rng, b, h, x_, y_)
    wr = _mk(rng, o, h, scale=1.0 / h)
    wi = _mk(rng, o, h, scale=1.0 / h)
    mk = lambda p: lambda x, wr, wi: ops.spectral_layer_nd(
        x, wr, wi, (kx, ky), path=p, variant=variant if p == "pallas"
        else "full")
    _assert_grads_match(mk, x, wr, wi)


@pytest.mark.parametrize("b,h,o,x_,y_,kx,ky", CASES_2D[:1])
@pytest.mark.parametrize("variant", ["full", "partial"])
def test_grad_fused_fno2d_permode(b, h, o, x_, y_, kx, ky, variant):
    rng = np.random.default_rng(7)
    x = _mk(rng, b, h, x_, y_)
    wr = _mk(rng, o, h, kx, ky, scale=1.0 / h)
    wi = _mk(rng, o, h, kx, ky, scale=1.0 / h)
    mk = lambda p: lambda x, wr, wi: ops.spectral_layer_nd(
        x, wr, wi, (kx, ky), path=p, variant=variant if p == "pallas"
        else "full")
    _assert_grads_match(mk, x, wr, wi)


CASES_3D = [
    # B, H, O, X, Y, Z, KX, KY, KZ
    (1, 4, 4, 8, 8, 16, 3, 3, 5),
]


@pytest.mark.parametrize("b,h,o,x_,y_,z_,kx,ky,kz", CASES_3D)
@pytest.mark.parametrize("weight_mode", ["shared", "per_mode"])
@pytest.mark.parametrize("variant", ["full", "partial"])
def test_grad_fused_fno3d(b, h, o, x_, y_, z_, kx, ky, kz, weight_mode,
                          variant):
    rng = np.random.default_rng(z_ + kz)
    x = _mk(rng, b, h, x_, y_, z_)
    wshape = ((o, h) if weight_mode == "shared"
              else (o, h, kx, ky, kz))
    wr = _mk(rng, *wshape, scale=1.0 / h)
    wi = _mk(rng, *wshape, scale=1.0 / h)
    mk = lambda p: lambda x, wr, wi: ops.spectral_layer_nd(
        x, wr, wi, (kx, ky, kz), path=p, variant=variant if p == "pallas"
        else "full")
    _assert_grads_match(mk, x, wr, wi)


def test_grad_linearity_in_cotangent():
    """The bwd pass is linear: vjp(a·g1 + g2) = a·vjp(g1) + vjp(g2)."""
    rng = np.random.default_rng(3)
    x = _mk(rng, 2, 8, 64)
    wr, wi = _mk(rng, 8, 8, scale=1 / 8), _mk(rng, 8, 8, scale=1 / 8)
    f = lambda x: ops.spectral_layer_nd(x, wr, wi, (17,), path="pallas")
    y, vjp = jax.vjp(f, x)
    g1 = _mk(rng, *y.shape)
    g2 = _mk(rng, *y.shape)
    lhs = vjp(2.5 * g1 + g2)[0]
    rhs = 2.5 * vjp(g1)[0] + vjp(g2)[0]
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), rtol=1e-4,
                               atol=1e-4)


_BLOCK_CASES = {
    1: ((64,), (17,)),
    2: ((16, 32), (5, 9)),
    3: ((8, 8, 16), (3, 3, 5)),
}


def _block_grads(fn, x, wr, wi, wb, bias):
    loss = lambda *a: jnp.sum(jnp.sin(fn(*a)))
    return jax.grad(loss, argnums=(0, 1, 2, 3, 4))(x, wr, wi, wb, bias)


def _assert_rel(name, a, b, tol=1e-4):
    scale = max(float(jnp.abs(jnp.asarray(b)).max()), 1.0)
    np.testing.assert_allclose(np.asarray(a) / scale, np.asarray(b) / scale,
                               rtol=tol, atol=tol, err_msg=name)


@pytest.mark.parametrize("rank", [1, 2, 3])
@pytest.mark.parametrize("weight_mode", ["shared", "per_mode"])
@pytest.mark.parametrize("variant", ["full", "partial"])
def test_grad_fused_block(rank, weight_mode, variant):
    """All four fused-block cotangents — dx, dW (re+im), dW_b, dbias —
    match jax.grad through the XLA oracle, every rank, both weight
    layouts, both fusion variants (the backward is the fully fused
    pipeline either way: gz recompute + dx adjoint + extended wgrad)."""
    if rank == 1 and variant == "partial":
        pytest.skip("rank 1 has no partial variant")
    spatial, modes = _BLOCK_CASES[rank]
    rng = np.random.default_rng(rank * 17 + len(spatial))
    x = _mk(rng, 2, 8, *spatial)
    wshape = (6, 8) if weight_mode == "shared" else (6, 8) + modes
    wr = _mk(rng, *wshape, scale=1.0 / 8)
    wi = _mk(rng, *wshape, scale=1.0 / 8)
    wb = _mk(rng, 6, 8, scale=1.0 / 8)
    bias = _mk(rng, 6, scale=0.3)
    mk = lambda p: lambda *a: ops.fno_block_nd(
        *a, modes, path=p, variant=variant if p == "pallas" else "full")
    gp = _block_grads(mk("pallas"), x, wr, wi, wb, bias)
    gx = _block_grads(mk("xla"), x, wr, wi, wb, bias)
    for name, a, b in zip(("dx", "dwr", "dwi", "dwb", "dbias"), gp, gx):
        _assert_rel(name, a, b)


def test_train_step_pallas_path():
    """One AdamW train step end-to-end on the fused path: loss finite,
    params move, and the metrics match the XLA path to tolerance."""
    from repro.configs import get_config
    from repro.core import fno as fno_mod
    from repro.optim import AdamW
    from repro.optim.schedule import constant
    from repro.train.train_step import make_train_step

    cfg = get_config("fno2d", reduced=True)
    key = jax.random.PRNGKey(0)
    params = fno_mod.init_fno(key, cfg)
    opt = AdamW(lr=constant(1e-3))
    rng = np.random.default_rng(0)
    batch = {"x": _mk(rng, 2, cfg.in_channels, *cfg.spatial),
             "y": _mk(rng, 2, cfg.out_channels, *cfg.spatial)}

    outs = {}
    for path in ("xla", "pallas"):
        step = jax.jit(make_train_step(cfg, opt, fno_path=path))
        p, s, m = step(params, opt.init(params), batch)
        assert bool(jnp.isfinite(m["loss"]))
        assert float(m["grad_norm"]) > 0.0
        moved = jax.tree_util.tree_map(
            lambda a, b: float(jnp.abs(a - b).max()), params, p)
        assert max(jax.tree_util.tree_leaves(moved)) > 0.0
        outs[path] = m
    np.testing.assert_allclose(float(outs["pallas"]["loss"]),
                               float(outs["xla"]["loss"]), rtol=1e-4)
    np.testing.assert_allclose(float(outs["pallas"]["grad_norm"]),
                               float(outs["xla"]["grad_norm"]), rtol=1e-3)
