"""The model's staged ends as one launch (``ops.end_mlp``), on the CPU in
interpret mode: parity with the staged ``_dense`` chain at the end shapes
of fno2d and fno3d-ns (reduced grids) at each precision the launch
resolves, its gradients, the passes the packed contraction takes, and the
layout the lift hands to the first block. The described-v5e compile of
the launch is in ``tests/test_tpu_compile.py``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import fno, spectral
from repro.kernels import engine, ops

# (C_in, D_1, D_2, activation, kernel-layout grid) of each model end; D_2
# = 0 is a one-Linear lift. The grids are the benchmark cells' layouts cut
# in their leading axis: fno2d 128x128, fno3d-ns [64, 64·40].
ENDS = {
    "fno2d.lift": (3, 128, 64, "gelu", (16, 128)),
    "fno2d.projection": (64, 128, 1, "gelu", (16, 128)),
    "fno3d-ns.lift": (13, 20, 0, "relu", (8, 2560)),
    "fno3d-ns.projection": (20, 128, 1, "relu", (8, 2560)),
}


def _end(name, dtype=jnp.float32, batch=2):
    c, d1, d2, act, grid = ENDS[name]
    keys = jax.random.split(jax.random.PRNGKey(hash(name) % 1000), 5)
    x = jax.random.normal(keys[0], (batch, c) + grid).astype(dtype)
    ps = [fno._dense_init(keys[1], c, d1)]
    if d2:
        ps.append(fno._dense_init(keys[2], d1, d2))
    for i, p in enumerate(ps):  # nonzero biases
        p["b"] = 0.1 * jax.random.normal(keys[3 + i], p["b"].shape)
    return x, ps, act


def _staged(x, ps, act, rnd=lambda a: a):
    """The chain core.fno runs on the staged path: ``_dense``, the
    activation, ``_dense``; `rnd` rounds the operands of each Linear the
    MXU runs (a Linear to one channel runs on the VPU in f32)."""
    for i, p in enumerate(ps):
        if i:
            x = ops.activate(x, act)
        r = rnd if p["w"].shape[1] > 1 else (lambda a: a)
        x = fno._dense({"w": r(p["w"]), "b": p["b"]}, r(x))
    return x


def _layers(ps):
    return tuple((p["w"], p["b"]) for p in ps)


def _rel(y, r):
    y, r = np.asarray(y, np.float64), np.asarray(r, np.float64)
    return float(np.max(np.abs(y - r)) / np.max(np.abs(r)))


@pytest.fixture
def on_tpu(monkeypatch):
    """The precision rule of a launch compiled for the TPU (the kernel
    still interpreted)."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)


@pytest.mark.parametrize("name", list(ENDS))
def test_forward_at_highest_matches_the_staged_chain(name, on_tpu):
    x, ps, act = _end(name)
    with jax.default_matmul_precision("highest"):
        assert ops.end_mlp_terms(x.dtype) == 6
        y = ops.end_mlp(x, _layers(ps), act, interpret=True)
        r = _staged(x, ps, act)
    assert y.shape == r.shape and y.dtype == r.dtype
    assert _rel(y, r) <= 2e-6, _rel(y, r)


@pytest.mark.parametrize("name", list(ENDS))
def test_forward_at_default_is_one_bf16_pass(name, on_tpu):
    # One term: each MXU Linear contracts its operands rounded to bf16, as
    # the staged einsum does in one MXU pass, with f32 accumulation.
    x, ps, act = _end(name)
    bf16 = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa
    with jax.default_matmul_precision("default"):
        assert ops.end_mlp_terms(x.dtype) == 1
        y = ops.end_mlp(x, _layers(ps), act, interpret=True)
    with jax.default_matmul_precision("highest"):
        one_pass = _staged(x, ps, act, rnd=bf16)
        exact = _staged(x, ps, act)
    assert _rel(y, one_pass) <= 1e-5, _rel(y, one_pass)
    assert _rel(y, exact) > 1e-4, _rel(y, exact)


@pytest.mark.parametrize("name", list(ENDS))
def test_forward_bf16_policy(name):
    # bf16 operands take one term whatever the precision; the kernel keeps
    # the inner activation in f32 where the staged chain rounds it.
    x, ps, act = _end(name, dtype=jnp.bfloat16)
    assert ops.end_mlp_terms(x.dtype) == 1
    y = ops.end_mlp(x, _layers(ps), act)
    r = _staged(x, ps, act)
    assert y.dtype == jnp.bfloat16
    assert _rel(y, r) <= 2e-2, _rel(y, r)


@pytest.mark.parametrize("name", list(ENDS))
def test_gradients_are_the_staged_chains(name):
    # The custom_vjp's backward is the staged chain's VJP.
    x, ps, act = _end(name)
    loss = lambda f: lambda x_, ps_: jnp.sum(f(x_, ps_) ** 2)  # noqa
    kern = lambda x_, ps_: ops.end_mlp(x_, _layers(ps_), act)  # noqa
    stag = lambda x_, ps_: _staged(x_, ps_, act)  # noqa
    g = jax.grad(loss(kern), argnums=(0, 1))(x, ps)
    gr = jax.grad(loss(stag), argnums=(0, 1))(x, ps)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(gr)):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert _rel(a, b) <= 2e-6, _rel(a, b)


@pytest.mark.parametrize("cin,cout,terms,passes", [
    (20, 128, 6, 1),   # fno3d-ns fc1: 120 rows of K
    (13, 20, 6, 1),    # fno3d-ns fc0
    (3, 128, 6, 1),    # fno2d lift1
    (64, 128, 6, 3),   # fno2d proj1
    (128, 64, 6, 6),   # fno2d lift2: no fewer passes than XLA's six
    (128, 1, 6, 0),    # proj2 and fc2 run on the VPU
    (64, 128, 3, 2),   # "high"
    (128, 64, 1, 1),   # the default, and bf16 operands
])
def test_end_mlp_passes(cin, cout, terms, passes):
    assert engine.end_mlp_passes(cin, cout, terms) == passes


def test_terms_follow_the_operands_and_the_precision(monkeypatch):
    # Off the TPU XLA computes an f32 dot in f32 at any precision.
    assert ops.end_mlp_terms(jnp.float32) == 6
    assert ops.end_mlp_terms(jnp.bfloat16) == 1
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    for prec, terms in (("default", 1), ("bfloat16", 1), ("high", 3),
                        ("highest", 6), ("float32", 6)):
        with jax.default_matmul_precision(prec):
            assert ops.end_mlp_terms(jnp.float32) == terms, prec
            assert ops.end_mlp_terms(jnp.bfloat16) == 1, prec


def _subjaxprs(eqn):
    return [getattr(j, "jaxpr", j) for j in jax.core.jaxprs_in_params(
        eqn.params)]


def _launch(eqn):
    """The names of the pallas_calls an eqn holds (itself included)."""
    if eqn.primitive.name == "pallas_call":
        return [eqn.params["name"]]
    return [n for j in _subjaxprs(eqn) for e in j.eqns for n in _launch(e)]


def _flows_into(jaxpr, var, launch):
    """Whether `var` reaches a `launch` pallas_call's operands through
    reshapes and the call boundaries alone, and nothing else reads it."""
    hit = False
    for eqn in jaxpr.eqns:
        if not any(v is var for v in eqn.invars):
            continue
        if eqn.primitive.name == "pallas_call":
            hit = hit or eqn.params["name"] == launch
            assert eqn.params["name"] == launch, eqn.params["name"]
        elif eqn.primitive.name == "reshape":
            hit = _flows_into(jaxpr, eqn.outvars[0], launch) or hit
        else:
            subs = [j for j in _subjaxprs(eqn)
                    if len(j.invars) == len(eqn.invars)]
            assert subs, eqn.primitive.name  # a relayout or a copy
            i = next(i for i, v in enumerate(eqn.invars) if v is var)
            hit = any(_flows_into(j, j.invars[i], launch)
                      for j in subs) or hit
    return hit


@pytest.mark.parametrize("arch", ["fno2d", "fno3d-ns"])
def test_lift_feeds_the_first_block_in_the_kernel_layout(arch):
    # The lift's end_mlp writes [B, H, kernel layout], and the first
    # block kernel reads that array through reshapes only: no transpose,
    # pad or copy between them. The projection reads the last block's
    # output the same way.
    cfg = dataclasses.replace(get_config(arch, reduced=True),
                              path="pallas", fuse_block=True)
    p = jax.eval_shape(lambda: fno.init_fno(jax.random.PRNGKey(0), cfg))
    b = 8  # a whole batch block of the tuned plans: the block pads none
    x = jax.ShapeDtypeStruct((b, cfg.in_channels) + tuple(cfg.spatial),
                             jnp.float32)
    jaxpr = jax.make_jaxpr(lambda p_, x_: fno.apply_fno(p_, cfg, x_))(
        p, x).jaxpr
    launches = [(e, _launch(e)) for e in jaxpr.eqns if _launch(e)]
    assert [n for _, ns in launches for n in ns] == (
        ["end_mlp"] + ["block_fwd"] * cfg.num_layers + ["end_mlp"])
    lift, last = launches[0][0], launches[-2][0]
    ksp = spectral.kernel_layout(cfg.spatial, cfg.kept_modes)[0]
    assert lift.outvars[0].aval.shape == (b, cfg.hidden) + ksp
    assert _flows_into(jaxpr, lift.outvars[0], "block_fwd")
    assert _flows_into(jaxpr, last.outvars[0], "end_mlp")
