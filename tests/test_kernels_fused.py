"""The paper's contribution: fused FFT->CGEMM->iFFT kernels vs the staged
jnp.fft oracle — 1D/2D/3D (one rank-generic engine), shared and per-mode
weights, partial (paper-faithful) and full (beyond-paper) fusion,
shape/dtype sweeps."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref as ref_k

TOL32 = dict(rtol=2e-4, atol=2e-4)


def _mk(rng, *s, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(scale * rng.normal(size=s), dtype)


CASES_1D = [
    # B, H, O, N, K
    (4, 24, 16, 64, 17),
    (2, 64, 64, 256, 64),  # paper's FFT size / 50% truncation (Table 1)
    (1, 8, 8, 128, 32),  # paper's 25% truncation
    (3, 16, 32, 128, 65),
]


@pytest.mark.parametrize("b,h,o,n,k", CASES_1D)
@pytest.mark.parametrize("weight_mode", ["shared", "per_mode"])
def test_fused_fno1d(b, h, o, n, k, weight_mode):
    rng = np.random.default_rng(b * 7 + k)
    x = _mk(rng, b, h, n)
    wshape = (o, h) if weight_mode == "shared" else (o, h, k)
    wr = _mk(rng, *wshape, scale=1.0 / h)
    wi = _mk(rng, *wshape, scale=1.0 / h)
    y = ops.spectral_layer_1d(x, wr, wi, k, path="pallas")
    yref = ref_k.ref_fno1d(x, wr, wi, k)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref), **TOL32)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_fused_fno1d_bf16(dtype):
    rng = np.random.default_rng(5)
    x = _mk(rng, 2, 16, 64, dtype=dtype)
    wr = _mk(rng, 8, 16, dtype=dtype, scale=1 / 16)
    wi = _mk(rng, 8, 16, dtype=dtype, scale=1 / 16)
    y = ops.spectral_layer_1d(x, wr, wi, 16, path="pallas")
    yref = ref_k.ref_fno1d(x.astype(jnp.float32), wr.astype(jnp.float32),
                           wi.astype(jnp.float32), 16)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(yref),
                               rtol=0.05, atol=0.05)


CASES_2D = [
    # B, H, O, X, Y, KX, KY
    (2, 12, 8, 32, 32, 9, 9),
    (1, 16, 16, 64, 64, 16, 16),  # 50% per-axis truncation (paper 2D)
    (2, 8, 8, 32, 64, 8, 17),
]


@pytest.mark.parametrize("b,h,o,x_,y_,kx,ky", CASES_2D)
@pytest.mark.parametrize("variant", ["full", "partial"])
def test_fused_fno2d_shared(b, h, o, x_, y_, kx, ky, variant):
    rng = np.random.default_rng(x_ + ky)
    x = _mk(rng, b, h, x_, y_)
    wr = _mk(rng, o, h, scale=1.0 / h)
    wi = _mk(rng, o, h, scale=1.0 / h)
    y = ops.spectral_layer_2d(x, wr, wi, (kx, ky), path="pallas",
                              variant=variant)
    yref = ref_k.ref_fno2d(x, wr, wi, (kx, ky))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref), **TOL32)


@pytest.mark.parametrize("b,h,o,x_,y_,kx,ky", CASES_2D[:2])
@pytest.mark.parametrize("variant", ["full", "partial"])
def test_fused_fno2d_permode(b, h, o, x_, y_, kx, ky, variant):
    """Per-mode weights through BOTH fusion variants — "partial" is the
    paper-faithful scheme, newly folded into the engine's weight-layout
    axis."""
    rng = np.random.default_rng(99)
    x = _mk(rng, b, h, x_, y_)
    wr = _mk(rng, o, h, kx, ky, scale=1.0 / h)
    wi = _mk(rng, o, h, kx, ky, scale=1.0 / h)
    y = ops.spectral_layer_2d(x, wr, wi, (kx, ky), path="pallas",
                              variant=variant)
    yref = ref_k.ref_fno2d(x, wr, wi, (kx, ky))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref), **TOL32)


def test_fused_fno2d_permode_partial_matches_xla():
    """Engine per-mode partial vs the XLA reference (satellite parity)."""
    rng = np.random.default_rng(31)
    x = _mk(rng, 2, 8, 16, 32)
    wr = _mk(rng, 8, 8, 5, 9, scale=1.0 / 8)
    wi = _mk(rng, 8, 8, 5, 9, scale=1.0 / 8)
    y = ops.spectral_layer_2d(x, wr, wi, (5, 9), path="pallas",
                              variant="partial")
    yx = ops.spectral_layer_2d(x, wr, wi, (5, 9), path="xla")
    np.testing.assert_allclose(np.asarray(y), np.asarray(yx), **TOL32)


CASES_3D = [
    # B, H, O, X, Y, Z, KX, KY, KZ
    (1, 4, 4, 8, 8, 16, 3, 3, 5),
    (2, 8, 8, 16, 16, 16, 4, 4, 4),  # reduced fno3d shape (25% truncation)
]


@pytest.mark.parametrize("b,h,o,x_,y_,z_,kx,ky,kz", CASES_3D)
@pytest.mark.parametrize("variant", ["full", "partial"])
def test_fused_fno3d_shared(b, h, o, x_, y_, z_, kx, ky, kz, variant):
    rng = np.random.default_rng(x_ + kz)
    x = _mk(rng, b, h, x_, y_, z_)
    wr = _mk(rng, o, h, scale=1.0 / h)
    wi = _mk(rng, o, h, scale=1.0 / h)
    y = ops.spectral_layer_3d(x, wr, wi, (kx, ky, kz), path="pallas",
                              variant=variant)
    yref = ref_k.ref_fnond(x, wr, wi, (kx, ky, kz))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref), **TOL32)


@pytest.mark.parametrize("b,h,o,x_,y_,z_,kx,ky,kz", CASES_3D[:1])
@pytest.mark.parametrize("variant", ["full", "partial"])
def test_fused_fno3d_permode(b, h, o, x_, y_, z_, kx, ky, kz, variant):
    rng = np.random.default_rng(7)
    x = _mk(rng, b, h, x_, y_, z_)
    wr = _mk(rng, o, h, kx, ky, kz, scale=1.0 / h)
    wi = _mk(rng, o, h, kx, ky, kz, scale=1.0 / h)
    y = ops.spectral_layer_3d(x, wr, wi, (kx, ky, kz), path="pallas",
                              variant=variant)
    yref = ref_k.ref_fnond(x, wr, wi, (kx, ky, kz))
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref), **TOL32)


def test_compat_wrappers_match_oracle():
    """The fused_fno{1d,2d} compat wrappers must keep their positional
    operand contract wired to the engine correctly (they have no other
    callers in-repo)."""
    from repro.core import spectral as sp
    from repro.kernels import fused_fno1d as f1d, fused_fno2d as f2d
    rng = np.random.default_rng(11)
    # 1D: B,H,O already block multiples; rank-1 mats are 128-padded.
    x = _mk(rng, 2, 8, 64)
    wr, wi = _mk(rng, 8, 8, scale=1 / 8), _mk(rng, 8, 8, scale=1 / 8)
    mats = sp.fused_operand_mats((64,), (17,), "float32", False, 128)
    y = f1d.fused_fno1d_call(x, wr, wi, *mats, 2, 8, 8, True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref_k.ref_fno1d(x, wr, wi, 17)),
                               **TOL32)
    # 2D full: rank ≥ 2 needs no mode padding.
    x2 = _mk(rng, 2, 8, 16, 32)
    mats = sp.fused_operand_mats((16, 32), (5, 9), "float32", False, 0)
    y2 = f2d.fused_fno2d_full_call(x2, wr, wi, *mats, 2, 8, 8, True)
    np.testing.assert_allclose(
        np.asarray(y2), np.asarray(ref_k.ref_fno2d(x2, wr, wi, (5, 9))),
        **TOL32)


def test_operand_mats_cached():
    """The rank-generic operand factories are lru_cached: repeated layer
    traces must reuse the same host constants instead of rebuilding the
    O(N·K) matrices (satellite: mats caching)."""
    from repro.core import spectral as sp
    a = sp.fused_operand_mats((16, 16), (5, 5), "float32", False, 0)
    b = sp.fused_operand_mats((16, 16), (5, 5), "float32", False, 0)
    assert all(x is y for x, y in zip(a, b))
    c = sp.wgrad_operand_mats((16, 16), (5, 5), "float32", 0)
    d = sp.wgrad_operand_mats((16, 16), (5, 5), "float32", 0)
    assert all(x is y for x, y in zip(c, d))
    assert len(a) == 8 and len(c) == 8  # 4 stages × (re, im) at rank 2


@pytest.mark.parametrize("spatial,modes,pad", [
    ((64,), (17,), 128), ((64,), (17,), 0), ((16, 32), (5, 9), 0),
    ((16, 32), (5, 9), 8), ((8, 8, 16), (3, 3, 5), 0)])
@pytest.mark.parametrize("adjoint", [False, True])
def test_realified_operands_reproduce_complex_product(spatial, modes, pad,
                                                      adjoint):
    """Each packed operand of the forward kernel, applied to random
    [re | im] rows, gives the complex product with the (mr, mi) pair of
    ``fused_operand_mats`` it packs: real in (first forward stage),
    complex, real out (last inverse stage); sublane stages as the
    transposed (left-hand) operand."""
    from repro.core import spectral as sp
    r = len(spatial)
    flat = sp.fused_operand_mats(spatial, modes, "float32", adjoint, pad)
    pairs = [(flat[2 * i], flat[2 * i + 1]) for i in range(2 * r)]
    packed = sp.realified_operand_mats(spatial, modes, "float32", adjoint,
                                       pad)
    fwd_plan, inv_plan = sp.realified_plan(r)
    stages = ([(pairs[r - 1 - j], form, i == 0, False)
               for i, (j, form) in enumerate(fwd_plan)]
              + [(pairs[r + j], form, False, i == r - 1)
                 for i, (j, form) in enumerate(inv_plan)])
    assert len(packed) == len(stages) == 2 * r
    rng = np.random.default_rng(r + 10 * adjoint)
    for ((mr, mi), form, real_in, real_out), p in zip(stages, packed):
        m = mr.astype(np.float64) + 1j * mi.astype(np.float64)
        q = p.T if form == "sublane" else p
        z = rng.normal(size=(3, m.shape[0]))
        if not real_in:
            z = z + 1j * rng.normal(size=z.shape)
        want = z @ m
        want = want.real if real_out else np.concatenate(
            [want.real, want.imag], 1)
        rows = z.real if real_in else np.concatenate([z.real, z.imag], 1)
        np.testing.assert_allclose(rows @ q, want, rtol=1e-5, atol=1e-5)


def test_three_paths_agree():
    """ref == xla == pallas (the core fusion-correctness invariant)."""
    rng = np.random.default_rng(1234)
    x = _mk(rng, 2, 16, 8, 64)
    wr = _mk(rng, 16, 16, scale=1 / 16.0)
    wi = _mk(rng, 16, 16, scale=1 / 16.0)
    outs = [ops.spectral_layer_2d(x, wr, wi, (3, 17), path=p,
                                  variant=v)
            for p, v in (("ref", "full"), ("xla", "full"),
                         ("pallas", "full"), ("pallas", "partial"))]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=3e-4, atol=3e-4)
