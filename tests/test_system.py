"""End-to-end system behaviour: train a reduced model until the loss
drops, serve it, and check the public API surface holds together."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ALL_IDS, get_config
from repro.core import fno as fno_mod
from repro.data import pde
from repro.models import transformer as tf
from repro.optim import AdamW
from repro.optim.schedule import constant
from repro.train import serve_step
from repro.train.train_step import make_train_step


def test_fno2d_end_to_end_darcy():
    """Lifting -> spectral blocks -> projection learns Darcy on synthetic
    data (few steps, reduced size)."""
    cfg = get_config("fno2d", reduced=True)
    key = jax.random.PRNGKey(0)
    params = fno_mod.init_fno(key, cfg)
    opt = AdamW(lr=constant(1e-2), weight_decay=0.0)
    step = jax.jit(make_train_step(cfg, opt, fno_path="xla"))
    state = opt.init(params)
    losses = []
    for i in range(25):
        batch = pde.darcy_batch(0, i, 4, cfg.spatial[0], iters=120)
        params, state, m = step(params, state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < 0.5 * losses[0], losses[::6]


def test_lm_generation_loop():
    cfg = get_config("qwen2-1.5b", reduced=True)
    key = jax.random.PRNGKey(0)
    params = tf.init_lm(key, cfg, jnp.float32)
    prompts = jax.random.randint(key, (2, 12), 0, cfg.vocab_size)
    prefill = jax.jit(serve_step.make_prefill_step(cfg, max_len=20))
    decode = jax.jit(serve_step.make_decode_step(cfg))
    logits, cache = prefill(params, {"tokens": prompts})
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    toks = [tok]
    for _ in range(7):
        tok, lg, cache = decode(params, cache, tok)
        toks.append(tok)
    gen = jnp.stack(toks, 1)
    assert gen.shape == (2, 8)
    assert int(cache["len"]) == 19  # 12 prompt + 7 decoded inputs
    # greedy decode is deterministic
    logits2, cache2 = prefill(params, {"tokens": prompts})
    tok2 = jnp.argmax(logits2, -1).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(toks[0]), np.asarray(tok2))


def test_all_configs_resolve():
    for arch in ALL_IDS:
        cfg = get_config(arch)
        red = get_config(arch, reduced=True)
        assert red.param_count() < cfg.param_count()


def test_compile_cache_dir(monkeypatch):
    """The persistent compile cache: $JAX_COMPILATION_CACHE_DIR wins and
    nothing is set over it; otherwise the fixed .jax_cache/ at the
    checkout root."""
    import os

    from repro.launch import compile_cache

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(root, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]
