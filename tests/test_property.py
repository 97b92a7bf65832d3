"""Hypothesis property tests on the system's invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
import hypothesis.strategies as st
from hypothesis import given, settings

from repro.core import spectral as sp
from repro.kernels import ops, ref as ref_k

hypothesis.settings.register_profile(
    "ci", deadline=None, max_examples=20,
    suppress_health_check=list(hypothesis.HealthCheck))
hypothesis.settings.load_profile("ci")

dims = st.sampled_from([16, 32, 64, 128])


@given(n=dims, frac=st.floats(0.1, 1.0), seed=st.integers(0, 2 ** 16))
def test_truncated_rdft_matches_fft(n, frac, seed):
    k = max(1, min(int(frac * (n // 2 + 1)), n // 2 + 1))
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(2, n)), jnp.float32)
    xr, xi = sp.truncated_rdft(x, k)
    ref = np.fft.rfft(np.asarray(x), axis=-1)[..., :k]
    np.testing.assert_allclose(np.asarray(xr), ref.real, rtol=1e-3,
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(xi), ref.imag, rtol=1e-3,
                               atol=1e-3)


@given(n=dims, frac=st.floats(0.1, 0.95), seed=st.integers(0, 2 ** 16))
def test_padded_irdft_matches_irfft(n, frac, seed):
    k = max(1, int(frac * (n // 2)))
    rng = np.random.default_rng(seed)
    z = rng.normal(size=(2, k)) + 1j * rng.normal(size=(2, k))
    y = sp.padded_irdft(jnp.asarray(z.real, jnp.float32),
                        jnp.asarray(z.imag, jnp.float32), n)
    ref = np.fft.irfft(np.pad(z, ((0, 0), (0, n // 2 + 1 - k))), n=n,
                       axis=-1)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-3, atol=1e-4)


@given(seed=st.integers(0, 2 ** 16))
def test_spectral_layer_linearity(seed):
    """The whole fused layer is linear in x: f(a·x1 + x2) = a·f(x1)+f(x2)."""
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    x1, x2 = mk(2, 8, 32), mk(2, 8, 32)
    wr, wi = mk(8, 8) / 8, mk(8, 8) / 8
    f = lambda x: ops.spectral_layer_nd(x, wr, wi, (9,), path="xla")
    lhs = f(1.7 * x1 + x2)
    rhs = 1.7 * f(x1) + f(x2)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), rtol=1e-3,
                               atol=1e-4)


@given(seed=st.integers(0, 2 ** 16), n=dims)
def test_truncation_contracts_energy(seed, n):
    """Truncation is an orthogonal projection: output energy of the
    identity-weight layer never exceeds input energy (Parseval)."""
    k = n // 4 + 1
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(2, 4, n)), jnp.float32)
    eye = jnp.eye(4, dtype=jnp.float32)
    y = ops.spectral_layer_nd(x, eye, jnp.zeros_like(eye), (k,), path="xla")
    e_in = float(jnp.sum(x ** 2))
    e_out = float(jnp.sum(y ** 2))
    assert e_out <= e_in * (1 + 1e-4)


@given(seed=st.integers(0, 2 ** 16))
def test_fusion_equals_staged(seed):
    """pallas fused == ref staged (the paper's central correctness claim)."""
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    x = mk(2, 8, 64)
    wr, wi = mk(8, 8) / 8, mk(8, 8) / 8
    y1 = ops.spectral_layer_nd(x, wr, wi, (17,), path="pallas")
    y0 = ref_k.ref_fno1d(x, wr, wi, 17)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=2e-4,
                               atol=2e-4)


@given(dtype=st.sampled_from(["f32", "bf16"]), seed=st.integers(0, 2 ** 16))
@settings(max_examples=12)
def test_fusion_equals_staged_both_dtypes(dtype, seed):
    """The fused layer tracks the staged oracle under BOTH precision
    presets — the PrecisionPolicy invariant: bf16 only loosens the
    tolerance (f32 accumulators), it never changes the math."""
    from repro.configs.base import PrecisionPolicy
    pol = PrecisionPolicy.from_name(dtype)
    tol = 2e-4 if dtype == "f32" else 2e-2
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    x = mk(2, 8, 64)
    wr, wi = mk(8, 8) / 8, mk(8, 8) / 8
    y1 = ops.spectral_layer_nd(x, wr, wi, (17,), path="pallas", policy=pol)
    assert jnp.dtype(y1.dtype).name == pol.compute_dtype
    y0 = ref_k.ref_fno1d(x, wr, wi, 17)
    scale = max(float(jnp.abs(np.asarray(y0)).max()), 1.0)
    np.testing.assert_allclose(np.asarray(y1, np.float32) / scale,
                               np.asarray(y0) / scale, rtol=tol, atol=tol)


@given(n=dims, frac=st.floats(0.1, 0.9), seed=st.integers(0, 2 ** 16))
def test_rdft_roundtrip_is_projection(n, frac, seed):
    """Adjoint identity of the matrix factories: irDFT(rDFT(x)) equals the
    spectral truncation of x (an orthogonal projection) — idempotent and
    energy-contracting."""
    k = max(1, int(frac * (n // 2 + 1)))
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(2, n)), jnp.float32)
    once = sp.padded_irdft(*sp.truncated_rdft(x, k), n)
    ref = np.fft.irfft(np.pad(np.fft.rfft(np.asarray(x), axis=-1)[:, :k],
                              ((0, 0), (0, n // 2 + 1 - k))), n=n, axis=-1)
    np.testing.assert_allclose(np.asarray(once), ref, rtol=1e-3, atol=1e-4)
    twice = sp.padded_irdft(*sp.truncated_rdft(once, k), n)
    np.testing.assert_allclose(np.asarray(twice), np.asarray(once),
                               rtol=1e-3, atol=1e-4)
    assert float(jnp.sum(once ** 2)) <= float(jnp.sum(x ** 2)) * (1 + 1e-4)


@given(n=dims, seed=st.integers(0, 2 ** 16))
def test_real_input_spectrum_conjugate_symmetric(n, seed):
    """Conjugate symmetry of the real-input path: the full complex DFT of
    a real signal satisfies X[m] == conj(X[(N-m) mod N]) — the invariant
    that lets the engine carry only n//2+1 rFFT bins."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(2, n)), jnp.float32)
    xr, xi = sp.truncated_cdft(x, jnp.zeros_like(x), n)
    idx = (-np.arange(n)) % n
    np.testing.assert_allclose(np.asarray(xr), np.asarray(xr)[:, idx],
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(xi), -np.asarray(xi)[:, idx],
                               rtol=1e-3, atol=1e-3)


_RANK_CASES = {
    1: ((32,), (9,)),
    2: ((16, 16), (5, 5)),
    3: ((8, 8, 8), (3, 3, 3)),
}


@given(rank=st.sampled_from([1, 2, 3]),
       weight_mode=st.sampled_from(["shared", "per_mode"]),
       seed=st.integers(0, 2 ** 16))
@settings(max_examples=12)
def test_engine_matches_ref_all_ranks(rank, weight_mode, seed):
    """One rank-generic engine == the jnp.fft staged oracle for every
    spatial rank and weight layout (the dedup-refactor invariant)."""
    spatial, modes = _RANK_CASES[rank]
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    h = o = 4
    x = mk(2, h, *spatial)
    wshape = (o, h) if weight_mode == "shared" else (o, h) + modes
    wr, wi = mk(*wshape) / h, mk(*wshape) / h
    y = ops.spectral_layer_nd(x, wr, wi, modes, path="pallas")
    yref = ref_k.ref_fnond(x, wr, wi, modes)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yref), rtol=2e-4,
                               atol=2e-4)


@given(n=st.sampled_from([4, 8, 16, 32, 64, 128, 256, 512]),
       frac=st.floats(0.05, 1.0))
def test_prune_counts_monotone(n, frac):
    """Pruned-FFT op count is monotone in k, bounded by the full FFT, and
    reproduces the paper's Fig. 5 figures."""
    k = max(1, int(frac * n))
    ops_k = sp.pruned_fft_ops(n, k)
    assert 0 < ops_k <= sp.fft_ops(n)
    if k > 1:
        assert sp.pruned_fft_ops(n, k - 1) <= ops_k
    assert sp.pruned_fft_ops(4, 1) / sp.fft_ops(4) == 0.375
    assert sp.pruned_fft_ops(4, 2) / sp.fft_ops(4) == 0.75


@given(seed=st.integers(0, 2 ** 16),
       e=st.sampled_from([2, 4, 8]), k=st.sampled_from([1, 2]))
def test_moe_gates_normalized_and_conserving(seed, e, k):
    from repro.configs import get_config
    import dataclasses
    from repro.models import moe as moe_mod
    cfg = dataclasses.replace(get_config("mixtral-8x7b", reduced=True),
                              num_experts=e, top_k=k, capacity_factor=8.0)
    key = jax.random.PRNGKey(seed)
    p = moe_mod.moe_init(key, cfg, jnp.float32)
    x = jax.random.normal(key, (2, 16, cfg.d_model))
    y, aux = moe_mod.apply_moe(p, x, cfg)
    assert y.shape == x.shape
    assert bool(jnp.isfinite(y).all())
    # E·Σ load·importance ≈ 1 at balance; can dip slightly below when the
    # top-k load distribution diverges from softmax importance
    assert 0.5 <= float(aux) < float(e)
