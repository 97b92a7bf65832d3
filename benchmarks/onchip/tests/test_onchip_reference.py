"""The plain reference against the program at a reduced size on the CPU,
where both compute in float32: weights and forward must agree to float32
rounding."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import onchip_testkit
import reference as ref
from repro.configs.base import FNOConfig
from repro.core import fno


def _pair(weight_mode, path):
    d = dict(onchip_testkit.TINY, weight_mode=weight_mode)
    fields = {f.name for f in dataclasses.fields(FNOConfig)}
    cfg = FNOConfig(**{k: (tuple(v) if isinstance(v, list) else v)
                       for k, v in d.items() if k in fields})
    return dataclasses.replace(cfg, path=path), d


def _data(b=3):
    x = jax.random.normal(jax.random.PRNGKey(1), (b, 3, 32, 32))
    y = jax.random.normal(jax.random.PRNGKey(2), (b, 1, 32, 32))
    return x, y


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("weight_mode", ["shared", "per_mode"])
@pytest.mark.parametrize("path", ["pallas", "xla"])
def test_forward_matches_the_program(weight_mode, path):
    cfg, d = _pair(weight_mode, path)
    key = jax.random.PRNGKey(7)
    p, pr = fno.init_fno(key, cfg), ref.init_params(key, d)
    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(pr)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    x, _ = _data()
    assert _rel(fno.apply_fno(p, cfg, x), ref.forward(pr, d, x)) < 1e-5


def test_lower_precisions_read_as_they_should():
    """The controls' forward errors, relative to the largest output:
    three bfloat16 passes about 2^-16, one pass and bfloat16 storage
    about 2^-7."""
    _, d = _pair("shared", "xla")
    p = ref.init_params(jax.random.PRNGKey(0), d)
    x, _ = _data()
    r = ref.forward(p, d, x)
    high = _rel(ref.forward(p, d, x, precision="high"), r)
    one = _rel(ref.forward(p, d, x, precision="default"), r)
    bf16 = _rel(ref.forward(p, d, x, jnp.bfloat16, "default")
                .astype(jnp.float32), r)
    assert 1e-7 < high < 1e-4 < 1e-3 < one < 5e-2 and 1e-3 < bf16 < 5e-2
