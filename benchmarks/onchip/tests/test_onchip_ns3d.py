"""The ``fno3d-ns.serve.bulk`` cell at a tiny size on the CPU: the plain
reference of Li et al.'s FNO-3D against an independent numpy.fft forward
and against the program, the request inputs, the cell's two readers on
made-up views, its traffic module's refusal of a configuration the
program cannot run, and a whole run of the harness with its control and
its fault."""
import dataclasses
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import onchip_testkit
import harness
import inputs_ns3d
import ns3d_work
import peaks
import reference_ns3d as ref3
import work
from repro.configs import get_config
from repro.configs.base import FNOConfig
from repro.core import fno

CELL = "fno3d-ns.serve.bulk"


def _tiny_cfg():
    """The cell's configuration file at the CPU size of fno3d-ns."""
    spec = harness.load_spec(CELL)
    red = get_config("fno3d-ns", reduced=True)
    return dict(spec.config, hidden=red.hidden, num_layers=red.num_layers,
                spatial=list(red.spatial), modes=list(red.modes),
                lifting_dim=red.lifting_dim)


def _tiny_spec():
    spec = harness.load_spec(CELL)
    spec.config = _tiny_cfg()
    spec.mix = dict(spec.mix, request_samples=4, max_batch=4,
                    pool_samples=12, ref_rows=4)
    return spec


def _numpy_forward(p, cfg, x):
    """The published forward in float64 numpy: rfftn, the four corners
    written out by hand, irfftn."""
    kx, ky, kt = cfg["modes"]
    dense = lambda q, v: (np.einsum("bc...,cd->bd...", v, q["w"])  # noqa
                          + q["b"].reshape((1, -1) + (1,) * (v.ndim - 2)))
    h = dense(p["lift1"], x)
    n = len(p["blocks"])
    for i, blk in enumerate(p["blocks"]):
        w = blk["spectral"]["wr"] + 1j * blk["spectral"]["wi"]
        hf = np.fft.rfftn(h, axes=(2, 3, 4))
        of = np.zeros((h.shape[0], w.shape[0]) + hf.shape[2:], complex)
        for xs, wx in ((slice(0, kx), slice(0, kx)),
                       (slice(-kx, None), slice(kx, 2 * kx))):
            for ys, wy in ((slice(0, ky), slice(0, ky)),
                           (slice(-ky, None), slice(ky, 2 * ky))):
                of[:, :, xs, ys, :kt] = np.einsum(
                    "bhxyt,ohxyt->boxyt", hf[:, :, xs, ys, :kt],
                    w[:, :, wx, wy])
        h = (np.fft.irfftn(of, s=h.shape[2:], axes=(2, 3, 4))
             + dense(blk["bypass"], h))
        if i < n - 1:
            h = np.maximum(h, 0)
    return dense(p["proj2"], np.maximum(dense(p["proj1"], h), 0))


def _inputs(cfg, n=2, seed=3):
    return inputs_ns3d.ns_inputs(np.random.default_rng(seed), n,
                                 cfg["spatial"][0], cfg["spatial"][2])


def test_reference_matches_an_independent_numpy_forward():
    cfg = dict(_tiny_cfg(), spatial=[8, 8, 6], modes=[2, 2, 3],
               hidden=4, num_layers=3)
    p = ref3.init_params(jax.random.PRNGKey(1), cfg)
    x = _inputs(cfg)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref3.forward(p, cfg, jnp.asarray(x)))
    want = _numpy_forward(jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float64), p), cfg, x.astype(np.float64))
    assert got.shape == (2, 1, 8, 8, 6)
    np.testing.assert_allclose(got, want, atol=1e-5 * np.abs(want).max())


def test_inputs_hold_ten_frames_then_the_grids():
    x = inputs_ns3d.ns_inputs(np.random.default_rng(0), 3, 16, 5)
    assert x.shape == (3, 13, 16, 16, 5) and x.dtype == np.float32
    # Frames are constant along t, differ from each other, and rotate
    # between two fields: w_t = cos(πt/20) g1 + sin(πt/20) g2.
    assert np.all(x[:, :10] == x[:, :10, :, :, :1])
    assert not np.allclose(x[:, 0], x[:, 9])
    g1 = (x[:, 1, ..., 0] * np.sin(np.pi / 20)
          - x[:, 0, ..., 0] * np.sin(np.pi / 10)) / np.sin(-np.pi / 20)
    assert np.abs(x[:, :10, ..., 0]).mean() > 0.01
    assert np.isfinite(g1).all()
    lin = lambda n: np.linspace(0, 1, n, dtype=np.float32)  # noqa: E731
    np.testing.assert_array_equal(x[0, 10, :, 0, 0], lin(16))
    np.testing.assert_array_equal(x[0, 11, 0, :, 0], lin(16))
    np.testing.assert_array_equal(x[0, 12, 0, 0, :], lin(5))
    np.testing.assert_array_equal(
        x, inputs_ns3d.ns_inputs(np.random.default_rng(0), 3, 16, 5))


@pytest.mark.parametrize("path", ["pallas", "xla"])
def test_program_matches_the_reference(path):
    d = _tiny_cfg()
    fields = {f.name for f in dataclasses.fields(FNOConfig)}
    cfg = FNOConfig(**{k: (tuple(v) if isinstance(v, list) else v)
                       for k, v in d.items() if k in fields})
    cfg = dataclasses.replace(cfg, path=path)
    key = jax.random.PRNGKey(7)
    p, pr = fno.init_fno(key, cfg), ref3.init_params(key, d)
    for name in ("lift1", "proj1", "proj2"):
        np.testing.assert_array_equal(p[name]["w"], pr[name]["w"])
    for b, br in zip(p["blocks"], pr["blocks"]):
        np.testing.assert_array_equal(b["spectral"]["wr"],
                                      br["spectral"]["wr"])
        np.testing.assert_array_equal(b["bypass"]["w"], br["bypass"]["w"])
    x = jnp.asarray(_inputs(d))
    with jax.default_matmul_precision("highest"):
        y, r = fno.apply_fno(p, cfg, x), ref3.forward(pr, d, x)
    assert float(jnp.max(jnp.abs(y - r)) / jnp.max(jnp.abs(r))) < 1e-5


def _view(cfg, trace, samples=64):
    return harness.View(run=types.SimpleNamespace(cfg=cfg), trace=trace,
                        counters={"samples": samples, "batch": 32,
                                  "requests": samples // 32,
                                  "window_s": 1.0, "training": False},
                        peak=peaks.peaks("TPU v5 lite"), chips=1)


def test_readers_count_the_kept_bins():
    import devtrace
    cfg = harness.load_spec(CELL).config
    # 16·16·8 = 2,048 kept modes; one block 0.596 GFLOP per sample, the
    # model 3.35; a 32-sample request's blocks are memory-bound at 3.38 GB.
    assert ns3d_work.kept_modes(cfg) == 2048
    assert work.block_flops(ns3d_work.kept(cfg)) == pytest.approx(0.596e9,
                                                                 rel=2e-3)
    assert ns3d_work.model_flops(cfg, 1) == pytest.approx(3.35e9, rel=2e-3)
    v5e = peaks.peaks("TPU v5 lite")
    least = ns3d_work.least_seconds(cfg, 32, v5e)
    assert least["bound"] == "memory"
    assert least["seconds"] == pytest.approx(3.38e9 / 819e9, rel=2e-3)
    mods = {n: harness.load_module(harness.HERE / "metrics" / f"{n}.py")
            for n in ("engine_roofline.serve.ns3d", "mfu.serve.ns3d")}
    assert mods["engine_roofline.serve.ns3d"].read(_view(cfg, None)) is None
    trace = devtrace.Reduced(window_s=1.0, busy_s=0.9, chips=1,
                             kind_s={"kernel": 8 * least["seconds"],
                                     "xla": 0.0, "collective": 0.0},
                             op_s={}, idle_by_span={}, n_events=3)
    # Two requests' least time over eight's worth of kernel time.
    assert mods["engine_roofline.serve.ns3d"].read(_view(cfg, trace)) \
        == pytest.approx(25.0)
    mfu = 64 * ns3d_work.model_flops(cfg, 1) / 197e12 * 100
    assert mods["mfu.serve.ns3d"].read(_view(cfg, trace)) \
        == pytest.approx(mfu)
    assert mods["mfu.serve.ns3d"].read(_view(cfg, trace, 0)) is None


def test_traffic_refuses_a_config_the_program_lacks():
    spec = _tiny_spec()
    spec.config = dict(spec.config, spectral_norm="layer")
    traffic = harness.load_module(harness.HERE / "traffic" /
                                 "closed_loop_ns3d.py")
    run = harness.Run(spec, 1, 0.5, False, jax.devices()[:1])
    with pytest.raises(ValueError, match="spectral_norm"):
        traffic.setup(run)


@pytest.fixture(scope="module")
def window():
    spec = _tiny_spec()
    traffic = harness.load_module(harness.HERE / "traffic" /
                                 "closed_loop_ns3d.py")
    run = harness.Run(spec, 2 ** 33 + 6, 0.5, False, jax.devices()[:1])
    with jax.default_matmul_precision("highest"):
        state = traffic.setup(run)
        win = traffic.window(run, state)
    return spec, traffic, run, win


def test_window_counts_the_kernel_and_the_modes(window):
    _, _, _, win = window
    assert win.counters["fwd_contractions"] == [5, 4]
    assert win.counters["kept_modes"] == 8 * 8 * 16
    assert win.metrics["serve_samples_per_s"] > 0


@pytest.mark.parametrize("what", ["high", "altered"])
def test_the_control_and_the_fault_are_not_correct(window, what):
    spec, traffic, run, win = window
    with jax.default_matmul_precision("highest"):
        got = traffic.control(run, win.record, what)
        sound = traffic.check(run, win.record)
    assert all(sound[k] <= v["limit"] for k, v in spec.limits.items()), \
        sound
    assert any(got[k] > v["limit"] for k, v in spec.limits.items()), got


def test_sound_run_is_correct():
    out = onchip_testkit.run_tiny(_tiny_spec(), seed=2 ** 32 + 9)
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == {"out_gap", "out_l2_gap"}
