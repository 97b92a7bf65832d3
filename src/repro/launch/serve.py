"""Serving driver: batched prefill + autoregressive decode for the LM zoo,
batched bucketed inference for the FNO archs.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
        --batch 4 --prompt-len 32 --new-tokens 16
    PYTHONPATH=src python -m repro.launch.serve --arch fno2d --reduced \
        --requests 8 --max-batch 8

``--arch fno{1,2,3}d`` (any FNO id) delegates to ``launch.serve_fno`` —
request bucketing, padding to the fused kernel's batch blocks, and the
DP×TP pallas placement (docs/DESIGN.md §6).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import FNO_IDS, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tf
from repro.models.frontend import fake_frontend_arrays
from repro.train import serve_step


def main() -> None:
    enable_compile_cache()
    peek = argparse.ArgumentParser(add_help=False)
    peek.add_argument("--arch", default="qwen2-1.5b")
    known, _ = peek.parse_known_args()
    if known.arch in FNO_IDS:
        from repro.launch import serve_fno
        serve_fno.main()
        return

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()

    cfg = get_config(args.arch, reduced=args.reduced)
    assert cfg.is_decoder, "encoder-only archs have no decode loop"
    key = jax.random.PRNGKey(0)
    params = tf.init_lm(key, cfg, jnp.float32)
    max_len = args.prompt_len + args.new_tokens

    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    extra = fake_frontend_arrays(cfg, args.batch, args.prompt_len, key)

    prefill = jax.jit(serve_step.make_prefill_step(cfg, max_len=max_len))
    decode = jax.jit(serve_step.make_decode_step(cfg))

    t0 = time.time()
    logits, cache = prefill(params, {"tokens": prompts, **extra})
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    jax.block_until_ready(tok)
    t_prefill = time.time() - t0

    out = [tok]
    t0 = time.time()
    for _ in range(args.new_tokens - 1):
        tok, _, cache = decode(params, cache, tok)
        out.append(tok)
    jax.block_until_ready(tok)
    t_dec = time.time() - t0
    gen = jnp.stack(out, 1)
    print(f"arch={args.arch} batch={args.batch} "
          f"prefill({args.prompt_len} toks)={t_prefill*1e3:.0f}ms "
          f"decode={t_dec/max(args.new_tokens-1,1)*1e3:.1f}ms/tok")
    print("generated tokens[0]:", gen[0].tolist())


if __name__ == "__main__":
    main()
