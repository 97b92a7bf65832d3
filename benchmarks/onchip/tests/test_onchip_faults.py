"""The comparison that decides ``correct`` fails what it must: each cell's
control (the reference at the next lower precision, put in the program's
place) and each fault the cell can have, planted under the timed path of
a whole run of the harness. At a tiny size on the CPU, where the program
computes in float32 and every sound reading is float32 rounding."""
import jax.numpy as jnp
import pytest

import onchip_testkit
import harness

SERVE = ("fno2d.serve.bulk",)


def _spec(cell):
    return onchip_testkit.tiny_spec(cell)


# What each cell's limits catch, with the reference put in the program's
# place: the control (three bfloat16 passes) and an altered answer.
CAUGHT = [("fno2d.serve.bulk", "high"), ("fno2d.serve.bulk", "altered")]


@pytest.mark.parametrize("cell", SERVE)
def test_sound_run_is_correct(cell):
    spec = _spec(cell)
    out = onchip_testkit.run_tiny(spec)
    assert out["correct"], out["checks"]
    assert set(out["checks"]) == set(spec.limits)


@pytest.mark.parametrize("cell,what", CAUGHT)
def test_the_control_in_the_programs_place_is_not_correct(cell, what):
    import jax
    spec = _spec(cell)
    driver = harness.load_module(harness.HERE / "traffic" /
                                 f"{spec.mix['kind']}.py")
    run = harness.Run(spec, 6, 0.5, False, jax.devices()[:1])
    state = driver.setup(run)
    win = driver.window(run, state)
    del state
    got = driver.control(run, win.record, what)
    assert any(got[k] > v["limit"] for k, v in spec.limits.items()), got


@pytest.mark.parametrize("cell", SERVE)
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        monkeypatch, cell):
    from repro.train import serve_fno_step as sfs
    real = sfs.make_fno_serve_step

    def make(cfg, **kw):
        step = real(cfg, **kw)

        def altered(params, batch):
            y = step(params, batch)
            return y.at[:, :, 0, 0].add(0.5 * jnp.max(jnp.abs(y)))
        return altered

    monkeypatch.setattr(sfs, "make_fno_serve_step", make)
    out = onchip_testkit.run_tiny(_spec(cell))
    assert not out["correct"], out["checks"]
