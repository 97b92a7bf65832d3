"""The benchmark's yardstick: operations and least bytes from shapes, and
the table of peaks."""
import dataclasses

import pytest

import onchip_testkit  # noqa: F401  (puts the benchmark on the path)
import peaks
import work
from repro.configs import fno as fno_cfgs
from repro.roofline.analysis import fno_model_flops


def _as_file(cfg):
    d = dataclasses.asdict(cfg)
    d.pop("policy")
    return {k: (list(v) if isinstance(v, tuple) else v) for k, v in d.items()}


@pytest.mark.parametrize("make", [fno_cfgs.fno1d, fno_cfgs.fno2d,
                                  fno_cfgs.fno2d_large, fno_cfgs.fno3d])
@pytest.mark.parametrize("training", [True, False])
def test_ops_agree_with_the_roofline_algebra(make, training):
    cfg = make()
    for batch in (1, 20):
        assert work.model_flops(_as_file(cfg), batch, training=training) \
            == pytest.approx(fno_model_flops(cfg, batch, training=training),
                             rel=1e-12)


def test_least_bytes_count_each_operand_once():
    cfg = _as_file(fno_cfgs.fno2d())
    sp, h = 128 * 128, 64
    act = 8 * h * sp * 4
    w = (2 * h * h + h * h + h) * 4
    assert work.blocks_bytes(cfg, 8, training=False) == 4 * (2 * act + w)
    assert work.blocks_bytes(cfg, 8, training=True) == 4 * (5 * act + 3 * w)
    large = _as_file(fno_cfgs.fno2d_large())
    assert work.block_weight_elems(large) == 2 * 128 * 128 * 32 * 32 \
        + 128 * 128 + 128


def test_least_time_names_the_side_that_binds():
    cfg = _as_file(fno_cfgs.fno2d())
    v5e = peaks.peaks("TPU v5 lite")
    t = work.least_seconds(cfg, 64, v5e, training=False)
    assert t["bound"] == "memory"
    assert t["seconds"] == t["bytes_s"] > t["ops_s"] > 0
    fast_memory = dict(v5e, hbm_bytes_per_s=1e18)
    t = work.least_seconds(cfg, 64, fast_memory, training=False)
    assert t["bound"] == "compute" and t["seconds"] == t["ops_s"]


def test_unknown_device_kind_raises():
    assert peaks.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("cpu")


def test_readers_count_each_pass_and_never_pass_a_peak():
    import types

    import devtrace
    import harness
    cfg = _as_file(fno_cfgs.fno2d())
    v5e = peaks.peaks("TPU v5 lite")
    req = work.least_seconds(cfg, 64, v5e, training=False)["seconds"]
    trace = devtrace.Reduced(window_s=1.0, busy_s=0.9, chips=1,
                             kind_s={"kernel": 10 * req, "xla": 0.0,
                                     "collective": 0.0},
                             op_s={}, idle_by_span={}, n_events=3)
    view = harness.View(run=types.SimpleNamespace(cfg=cfg), trace=trace,
                        counters={"samples": 192, "batch": 64,
                                  "requests": 3, "window_s": 1.0,
                                  "training": False},
                        peak=v5e, chips=1)
    mods = {n: harness.load_module(harness.HERE / "metrics" / f"{n}.py")
            for n in ("engine_roofline.serve", "mfu.serve",
                      "idle_share.serve")}
    # Three requests' least time (weights read in each) over ten's worth.
    assert mods["engine_roofline.serve"].read(view) == pytest.approx(30.0)
    assert mods["idle_share.serve"].read(view) == pytest.approx(10.0)
    mfu = 192 * work.model_flops(cfg, 1, training=False) / 197e12 * 100
    assert mods["mfu.serve"].read(view) == pytest.approx(mfu)
    view.counters["training"] = True
    assert mods["engine_roofline.serve"].read(view) is None
