"""Li et al.'s FNO-3D for Navier–Stokes (``fno3d-ns``) and what it brought
into the engine: Li et al.'s ± corner truncation, ReLU blocks with an
unactivated last block, a one-layer lift, and the rank-3 kernel layout
(trailing axes merged, per-mode CGEMM on the VPU), on the CPU at small
sizes. The comparison with the benchmark's plain reference of the
published model is in ``benchmarks/onchip/tests/test_onchip_ns3d.py``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import fno, spectral
from repro.kernels import engine, ops


def test_param_count_is_the_published_one_without_batchnorm():
    # Li et al. report 6,558,537 parameters for FNO-3D; their four
    # BatchNorm layers hold 4·2·20 = 160 of them (folded away here).
    assert get_config("fno3d-ns").param_count() == 6_558_537 - 160
    # The same count reproduces their FNO-2D (width 20, 8 modes per
    # corner, ten frames and two grids in): 414,517 published.
    ns2d = dataclasses.replace(
        get_config("fno3d-ns"), ndim=2, in_channels=12, spatial=(64, 64),
        modes=(8, 8))
    assert ns2d.param_count() == 414_517 - 160


def test_kept_modes():
    assert get_config("fno3d-ns").kept_modes == (16, 16, 8)
    assert get_config("fno3d").kept_modes == (16, 16, 16)
    assert spectral.kept_modes((3, 5), "corners") == (6, 5)


@pytest.mark.parametrize("n,k", [(8, 2), (16, 6), (64, 16)])
def test_corner_operands_are_the_kept_rows_of_the_dft(n, k):
    bins = np.r_[0:k // 2, n - k // 2:n]
    dft = np.fft.fft(np.eye(n), axis=0)  # dft[m, j] = e^{-2πi mj/n}
    fr, fi = spectral.cdft_mats(n, k, False, "float64", "corners")
    np.testing.assert_allclose(fr + 1j * fi, dft[bins].T, atol=1e-12)
    er, ei = spectral.cdft_mats(n, k, True, "float64", "corners")
    idft = np.fft.ifft(np.eye(n), axis=0)  # idft[j, m] = e^{2πi mj/n}/n
    np.testing.assert_allclose(er + 1j * ei, idft[:, bins].T, atol=1e-12)


def test_merged_operands_are_the_2d_transform_of_the_trailing_axes():
    # The rank-3 kernel layout contracts (y, t) jointly: its forward
    # operand maps the flattened [Y·T] grid to the kept (k_y, k_t) bins of
    # fft over y of rfft over t, and its inverse maps them back.
    y, t, ky, kt = 8, 10, 4, 3
    sp, kept = (4, y, t), (2, ky, kt)
    x = np.random.default_rng(0).standard_normal((y, t))
    pairs = spectral._merge_trailing(spectral._fused_mat_pairs(
        sp, kept, False, "float64", "corners"))
    mr, mi = pairs[0]
    bins = np.r_[0:ky // 2, y - ky // 2:y]
    want = np.fft.fft(np.fft.rfft(x, axis=1)[:, :kt], axis=0)[bins]
    np.testing.assert_allclose(x.reshape(-1) @ (mr + 1j * mi),
                               want.reshape(-1), atol=1e-5)
    er, ei = pairs[3]
    full = np.zeros((y, t // 2 + 1), complex)
    full[bins, :kt] = want
    back = np.fft.irfft(np.fft.ifft(full, axis=0), n=t, axis=1)
    spec = want.reshape(-1)
    np.testing.assert_allclose(spec.real @ er - spec.imag @ ei,
                               back.reshape(-1), atol=1e-5)


@pytest.mark.parametrize("act", ["relu", "linear", "gelu"])
@pytest.mark.parametrize("case", ["vpu", "batched", "shared"])
def test_corner_block_matches_its_oracles_forward_and_backward(case, act):
    # "vpu": the merged trailing axis keeps 8·16 bins, the fno3d-ns form.
    sp, modes = (((8, 16, 32), (2, 4, 16)) if case == "vpu"
                 else ((8, 8, 8), (2, 2, 3)))
    per_mode = case != "shared"
    kept = spectral.kept_modes(modes, "corners")
    h = 4
    assert engine.vpu_cgemm(2, spectral.kernel_layout(sp, kept)[1]) == (
        case == "vpu")
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    w = (h, h) + (kept if per_mode else ())
    args = (jax.random.normal(ks[0], (2, h) + sp),
            0.3 * jax.random.normal(ks[1], w),
            0.3 * jax.random.normal(ks[2], w),
            0.3 * jax.random.normal(ks[3], (h, h)),
            jax.random.normal(ks[4], (h,)))

    def block(path):
        return lambda *a: ops.fno_block_nd(*a, modes, path=path, act=act,
                                           truncation="corners")

    with jax.default_matmul_precision("highest"):
        r = block("ref")(*args)
        for path in ("xla", "pallas"):
            y = block(path)(*args)
            assert float(jnp.max(jnp.abs(y - r)) / jnp.max(jnp.abs(r))) \
                < 1e-5, path
        loss = lambda path: lambda *a: jnp.sum(block(path)(*a) ** 2)  # noqa
        gx = jax.grad(loss("xla"), argnums=range(5))(*args)
        gp = jax.grad(loss("pallas"), argnums=range(5))(*args)
    for a, b in zip(gx, gp):
        assert float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(a))) < 1e-5


def test_partial_variant_refuses_corners():
    x = jnp.zeros((1, 4, 8, 8))
    w = jnp.zeros((4, 4))
    with pytest.raises(NotImplementedError, match="low corner"):
        ops.fno_block_nd(x, w, w, w, jnp.zeros(4), (2, 2), path="pallas",
                         variant="partial", truncation="corners")


def test_fused_ends_refuse_the_published_ends():
    with pytest.raises(AssertionError, match="fuse_ends"):
        dataclasses.replace(get_config("fno3d-ns"), fuse_ends=True
                            ).validate()


@pytest.mark.parametrize("path", ["pallas", "xla"])
def test_reduced_model_runs_and_trains_like_the_staged_path(path):
    # The whole model — one-layer lift, ReLU blocks, a linear last block,
    # the ReLU projection — on the fused pallas path (interpret mode) and
    # the XLA path against the staged jnp.fft reference path ("ref"),
    # forward, and the gradient of the XLA path (the fused backward is
    # held to it block by block above).
    cfg = dataclasses.replace(get_config("fno3d-ns", reduced=True),
                              path=path, fuse_block=True)
    p = fno.init_fno(jax.random.PRNGKey(3), cfg)
    assert "lift2" not in p and p["lift1"]["w"].shape == (13, cfg.hidden)
    x = jax.random.normal(jax.random.PRNGKey(4),
                          (2, 13) + tuple(cfg.spatial))
    y = jax.random.normal(jax.random.PRNGKey(5), (2, 1) + tuple(cfg.spatial))
    ref_cfg = dataclasses.replace(cfg, path="ref")
    with jax.default_matmul_precision("highest"):
        out, r = fno.apply_fno(p, cfg, x), fno.apply_fno(p, ref_cfg, x)
        assert out.shape == (2, 1) + tuple(cfg.spatial)
        assert float(jnp.max(jnp.abs(out - r)) / jnp.max(jnp.abs(r))) < 1e-5
        if path == "pallas":
            return
        batch = {"x": x, "y": y}
        g = jax.grad(fno.fno_loss)(p, cfg, batch)
        gr = jax.grad(fno.fno_loss)(p, ref_cfg, batch)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(gr)):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        assert float(jnp.max(jnp.abs(a - b))) / scale < 1e-4


def test_fused_model_launches_one_block_kernel_per_layer():
    from repro.train.serve_fno_step import make_fno_serve_step
    cfg = dataclasses.replace(get_config("fno3d-ns", reduced=True),
                              path="pallas", fuse_block=True)
    p = jax.eval_shape(lambda: fno.init_fno(jax.random.PRNGKey(0), cfg))
    x = jax.ShapeDtypeStruct((2, 13) + tuple(cfg.spatial), jnp.float32)
    jaxpr = jax.make_jaxpr(make_fno_serve_step(cfg))(p, {"x": x})
    names = [e.params["name"] for e in _eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert names == (["end_mlp"] + ["block_fwd"] * cfg.num_layers
                     + ["end_mlp"])


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


def test_forward_kernel_counts_its_contractions():
    # The rank-3 layout of fno3d-ns: 2·2 realified DFT stages, the bypass,
    # and a per-mode CGEMM on the VPU with no matmul.
    kmodes = spectral.kernel_layout((64, 64, 40), (16, 16, 8))[1]
    assert kmodes == (16, 128) and engine.vpu_cgemm(2, kmodes)
    assert engine.fwd_contractions(2, True, has_wb=True, vpu=True) == (5, 4)
    # fno2d-large keeps the batched MXU form.
    assert not engine.vpu_cgemm(2, (32, 32))
    assert engine.fwd_contractions(2, True, has_wb=True) == (9, 4)


def test_server_sends_host_inputs_in_the_kernel_layout():
    # A numpy request crosses to the device as [B, C, s_1, s_2·s_3] and is
    # served as the same answers as the device-array (grid-shaped) path.
    from repro.train.serve_fno_step import FNOServer
    cfg = dataclasses.replace(get_config("fno3d-ns", reduced=True),
                              path="pallas", fuse_block=True)
    p = fno.init_fno(jax.random.PRNGKey(2), cfg)
    server = FNOServer(cfg, p, max_batch=8)
    sp = tuple(cfg.spatial)
    assert server.host_layout == (sp[0], sp[1] * sp[2])
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(6), (8, 13) + sp))
    with jax.default_matmul_precision("highest"):
        host = server(x)
        dev = server(jnp.asarray(x))
    assert host.shape == dev.shape == (8, 1) + sp
    np.testing.assert_allclose(np.asarray(host), np.asarray(dev),
                               rtol=1e-5, atol=1e-5 * float(
                                   jnp.max(jnp.abs(dev))))
