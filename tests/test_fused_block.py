"""The fused FNO BLOCK (PR 4): gelu(spectral(x) + 1×1 bypass + bias) as
ONE pallas_call on the full-fusion path, end-to-end differentiable.

Covers: forward parity vs the staged XLA oracle (ranks 1–3, both weight
layouts, full + partial variants, f32 ≤ 2e-4 relative), the
single-pallas_call trace guard (block forward == exactly 1; jax.grad ==
exactly 4 — fwd + gz recompute + dx adjoint + extended wgrad — so all
four cotangents stay on fused kernels), model-level integration through
``apply_fno`` with cfg.fuse_block, and a train-step convergence smoke.
bf16-policy parity lives in tests/test_precision.py; per-cotangent grad
value checks in tests/test_kernels_grad.py.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.roofline.hlo_counter import (count_pallas_calls,
                                        jaxpr_primitive_counts)

_CASES = {
    1: ((64,), (17,)),
    2: ((16, 32), (5, 9)),
    3: ((8, 8, 16), (3, 3, 5)),
}


def _mk(rng, *s, scale=1.0):
    return jnp.asarray(scale * rng.normal(size=s), jnp.float32)


def _block_args(rng, rank, weight_mode, b=2, h=8, o=6):
    spatial, modes = _CASES[rank]
    x = _mk(rng, b, h, *spatial)
    wshape = (o, h) if weight_mode == "shared" else (o, h) + modes
    wr = _mk(rng, *wshape, scale=1.0 / h)
    wi = _mk(rng, *wshape, scale=1.0 / h)
    wb = _mk(rng, o, h, scale=1.0 / h)
    bias = _mk(rng, o, scale=0.3)
    return (x, wr, wi, wb, bias), modes


def _allclose_rel(a, b, tol, **kw):
    """Tolerance scaled to the reference magnitude (sums over B·∏s terms
    make the raw values O(100+); the contract is relative)."""
    scale = max(float(jnp.abs(b).max()), 1.0)
    np.testing.assert_allclose(np.asarray(a, np.float32) / scale,
                               np.asarray(b, np.float32) / scale,
                               rtol=tol, atol=tol, **kw)


@pytest.mark.parametrize("rank", [1, 2, 3])
@pytest.mark.parametrize("weight_mode", ["shared", "per_mode"])
@pytest.mark.parametrize("variant", ["full", "partial"])
def test_block_forward_parity_f32(rank, weight_mode, variant):
    if rank == 1 and variant == "partial":
        pytest.skip("rank 1 has no partial variant")
    rng = np.random.default_rng(rank * 5 + (weight_mode == "per_mode"))
    args, modes = _block_args(rng, rank, weight_mode)
    y = ops.fno_block_nd(*args, modes, path="pallas", variant=variant)
    for oracle in ("xla", "ref"):
        yref = ops.fno_block_nd(*args, modes, path=oracle)
        _allclose_rel(y, yref, 2e-4, err_msg=oracle)


def test_block_forward_is_one_pallas_call():
    """Acceptance guard: the full-fusion block forward lowers to exactly
    ONE pallas_call — spectral, bypass GEMM, bias, and GELU all inside."""
    rng = np.random.default_rng(0)
    for rank in (1, 2, 3):
        args, modes = _block_args(rng, rank, "shared")
        fn = lambda x: ops.fno_block_nd(x, *args[1:], modes, path="pallas",
                                        variant="full")
        assert count_pallas_calls(fn, args[0]) == 1, rank


@pytest.mark.parametrize("weight_mode,want", [("shared", (6, 5)),
                                              ("per_mode", (9, 4))])
def test_block_forward_contractions(weight_mode, want):
    """One grid step of the rank-2 block forward issues 6 contractions,
    each complex one a single realified matmul: two DFT stages each way
    and the CGEMM, plus the real bypass GEMM (17 with the complex stages
    split into real halves). The per-mode CGEMM stays 4 real matmuls."""
    from repro.kernels import engine

    per_mode = weight_mode == "per_mode"
    assert engine.fwd_contractions(2, per_mode, has_wb=True) == want
    rng = np.random.default_rng(6)
    args, modes = _block_args(rng, 2, weight_mode)
    fn = lambda *a: ops.fno_block_nd(*a, modes, path="pallas",
                                     variant="full")
    inside = (jaxpr_primitive_counts(fn, *args).get("dot_general", 0)
              - jaxpr_primitive_counts(fn, *args, into_kernels=False)
              .get("dot_general", 0))
    assert inside == want[0]


def test_block_grad_stays_on_fused_kernels():
    """jax.grad of the fused block traces exactly 4 pallas_calls — the
    forward, the gz recompute (gelu_vjp epilogue), the dx adjoint, and
    the ONE extended wgrad emitting dW, dW_b, dbias — with no staged-XLA
    fallback for any of the four cotangents."""
    rng = np.random.default_rng(1)
    args, modes = _block_args(rng, 2, "shared")

    def loss(*a):
        return jnp.sum(jnp.sin(ops.fno_block_nd(*a, modes, path="pallas",
                                                variant="full")))

    g = lambda *a: jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*a)
    assert count_pallas_calls(g, *args) == 4


def test_apply_fno_fused_block_model_parity():
    """cfg.fuse_block threads through apply_fno: one pallas_call per
    layer and one per staged end, output matches the unfused pallas path
    and the XLA oracle."""
    from repro.core import fno as fno_mod

    cfg0 = get_config("fno2d", reduced=True)
    cfg = dataclasses.replace(cfg0, fuse_block=True)
    params = fno_mod.init_fno(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    x = _mk(rng, 2, cfg.in_channels, *cfg.spatial)
    y_fused = fno_mod.apply_fno(params, cfg, x, path="pallas")
    y_plain = fno_mod.apply_fno(params, cfg0, x, path="pallas")
    y_xla = fno_mod.apply_fno(params, cfg0, x, path="xla")
    _allclose_rel(y_fused, y_plain, 2e-4)
    _allclose_rel(y_fused, y_xla, 2e-4)
    # the staged paths ignore fuse_block (they stay the parity oracle)
    np.testing.assert_array_equal(
        np.asarray(fno_mod.apply_fno(params, cfg, x, path="xla")),
        np.asarray(y_xla))
    # one launch per block, plus the lift and projection end_mlp launches
    fn = lambda xx: fno_mod.apply_fno(params, cfg, xx, path="pallas")
    assert count_pallas_calls(fn, x) == cfg.num_layers + 2
    assert fno_mod.pallas_launches(cfg, "pallas") == cfg.num_layers + 2


def test_block_3d_rank_generic():
    """The block epilogue is rank-generic: 3D fused block matches the
    oracle (the engine path the fno3d config exercises)."""
    from repro.core import fno as fno_mod

    cfg = dataclasses.replace(get_config("fno3d", reduced=True),
                              fuse_block=True)
    params = fno_mod.init_fno(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(3)
    x = _mk(rng, 2, cfg.in_channels, *cfg.spatial)
    y = fno_mod.apply_fno(params, cfg, x, path="pallas")
    y_xla = fno_mod.apply_fno(params, cfg, x, path="xla")
    _allclose_rel(y, y_xla, 2e-4)


@pytest.mark.parametrize("arch", ["fno1d", "fno2d", "fno3d"])
def test_apply_fno_fused_ends_parity(arch):
    """cfg.fuse_ends folds the lifting MLP into the FIRST fused block
    kernel and the projection MLP into the LAST one (ISSUE 8): output and
    jax.grad match the staged XLA oracle, and the forward still traces
    exactly num_layers pallas_calls — the end MLPs add ZERO launches."""
    from repro.core import fno as fno_mod

    cfg0 = get_config(arch, reduced=True)
    cfg = dataclasses.replace(cfg0, fuse_block=True, fuse_ends=True)
    params = fno_mod.init_fno(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    x = _mk(rng, 2, cfg.in_channels, *cfg.spatial)

    y = fno_mod.apply_fno(params, cfg, x, path="pallas")
    y_xla = fno_mod.apply_fno(params, cfg0, x, path="xla")
    _allclose_rel(y, y_xla, 2e-4)

    loss = lambda p, path, c: jnp.sum(
        fno_mod.apply_fno(p, c, x, path=path) ** 2)
    g = jax.grad(loss)(params, "pallas", cfg)
    g_ref = jax.grad(loss)(params, "xla", cfg0)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(g_ref)):
        _allclose_rel(a, b, 2e-4)

    fn = lambda xx: fno_mod.apply_fno(params, cfg, xx, path="pallas")
    assert count_pallas_calls(fn, x) == cfg.num_layers


def test_fused_ends_one_layer_single_call():
    """The 1-layer degenerate case: lift prologue AND projection epilogue
    ride the SAME kernel — the whole model is ONE pallas_call."""
    from repro.core import fno as fno_mod

    cfg = dataclasses.replace(get_config("fno2d", reduced=True),
                              fuse_block=True, fuse_ends=True, num_layers=1)
    params = fno_mod.init_fno(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    x = _mk(rng, 2, cfg.in_channels, *cfg.spatial)
    y = fno_mod.apply_fno(params, cfg, x, path="pallas")
    y_xla = fno_mod.apply_fno(
        params, dataclasses.replace(cfg, fuse_ends=False), x, path="xla")
    _allclose_rel(y, y_xla, 2e-4)
    fn = lambda xx: fno_mod.apply_fno(params, cfg, xx, path="pallas")
    assert count_pallas_calls(fn, x) == 1


def test_fused_ends_bf16_matches_staged_pallas():
    """bf16 policy under fuse_ends: parity against the bf16 staged-ends
    pallas path (the apples-to-apples reference — both quantize the same
    boundary activations; the f32 oracle differs by inherent bf16
    rounding, covered at f32 above)."""
    from repro.configs.fno import with_precision
    from repro.core import fno as fno_mod

    cfg0 = with_precision(get_config("fno2d", reduced=True), "bf16")
    cfg0 = dataclasses.replace(cfg0, fuse_block=True)
    cfg = dataclasses.replace(cfg0, fuse_ends=True)
    params = fno_mod.init_fno(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(6)
    x = _mk(rng, 2, cfg.in_channels, *cfg.spatial)
    y = fno_mod.apply_fno(params, cfg, x, path="pallas")
    y_ref = fno_mod.apply_fno(params, cfg0, x, path="pallas")
    assert y.dtype == jnp.bfloat16
    _allclose_rel(y.astype(jnp.float32), y_ref.astype(jnp.float32), 2e-2)


def test_train_step_fuse_block_smoke():
    """Convergence smoke with fuse_block=True: the fused-block train step
    overfits one batch, and its first-step loss/grad-norm match the
    unfused pallas step (same math, one kernel per block)."""
    from repro.core import fno as fno_mod
    from repro.optim import AdamW
    from repro.optim.schedule import constant
    from repro.train.train_step import make_train_step

    rng = np.random.default_rng(0)
    cfg0 = get_config("fno2d", reduced=True)
    batch = {"x": _mk(rng, 2, cfg0.in_channels, *cfg0.spatial),
             "y": _mk(rng, 2, cfg0.out_channels, *cfg0.spatial)}
    metrics = {}
    for fuse in (False, True):
        cfg = dataclasses.replace(cfg0, fuse_block=fuse)
        params = fno_mod.init_fno(jax.random.PRNGKey(0), cfg)
        opt = AdamW(lr=constant(3e-3))
        step = jax.jit(make_train_step(cfg, opt, fno_path="pallas"))
        state = opt.init(params)
        hist = []
        for _ in range(5):
            params, state, m = step(params, state, batch)
            hist.append(float(m["loss"]))
        assert np.isfinite(hist).all()
        assert hist[-1] < hist[0], hist
        metrics[fuse] = (hist, float(m["grad_norm"]))
    np.testing.assert_allclose(metrics[True][0][0], metrics[False][0][0],
                               rtol=1e-4)


def test_dgelu_matches_jax_gelu_grad():
    """The in-kernel gelu' closed form equals jax.grad of the activation
    core/fno.py applies (tanh-approximate jax.nn.gelu)."""
    from repro.kernels.engine import _dgelu

    z = jnp.linspace(-6.0, 6.0, 301, dtype=jnp.float32)
    ref = jax.vmap(jax.grad(lambda v: jax.nn.gelu(v, approximate=True)))(z)
    np.testing.assert_allclose(np.asarray(_dgelu(z)), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_cdft_wrappers_take_operand_dtype():
    """Satellite: the complex-pair standalone DFT wrappers honor
    operand_dtype like the real-input ones (the policy's spectral dtype
    on the partial path's core stages)."""
    rng = np.random.default_rng(4)
    xr = _mk(rng, 4, 16)
    xi = _mk(rng, 4, 16)
    fr32, fi32 = ops.truncated_cdft(xr, xi, 5, path="pallas")
    fr16, fi16 = ops.truncated_cdft(xr, xi, 5, path="pallas",
                                    operand_dtype="bfloat16")
    # bf16 operands perturb the result but stay within bf16 tolerance
    assert float(jnp.abs(fr16 - fr32).max()) > 0.0
    _allclose_rel(fr16, fr32, 2e-2)
    _allclose_rel(fi16, fi32, 2e-2)
    br32, bi32 = ops.padded_icdft(fr32, fi32, 16, path="pallas")
    br16, bi16 = ops.padded_icdft(fr32, fi32, 16, path="pallas",
                                  operand_dtype="bfloat16")
    assert float(jnp.abs(br16 - br32).max()) > 0.0
    _allclose_rel(br16, br32, 2e-2)
    _allclose_rel(bi16, bi32, 2e-2)
