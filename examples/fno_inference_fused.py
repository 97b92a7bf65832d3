"""Serve an FNO with the fused TurboFNO kernel and compare the three
execution paths on identical inputs — parity + per-path wall time + the
modeled HBM traffic of a staged and a whole-block-fused forward.

    PYTHONPATH=src python examples/fno_inference_fused.py
"""
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import fno
from repro.roofline.analysis import fno_model_bytes

cfg = get_config("fno2d", reduced=True)
key = jax.random.PRNGKey(1)
params = fno.init_fno(key, cfg)
x = jax.random.normal(key, (4, cfg.in_channels, *cfg.spatial))

apply = {p: jax.jit(lambda pr, xx, p=p: fno.apply_fno(pr, cfg, xx, path=p))
         for p in ("ref", "xla", "pallas")}

ref = None
for name, fn in apply.items():
    y = jax.block_until_ready(fn(params, x))
    t0 = time.time()
    for _ in range(5):
        y = jax.block_until_ready(fn(params, x))
    dt = (time.time() - t0) / 5
    if ref is None:
        ref = y
    err = float(jnp.abs(y - ref).max())
    note = "(interpret mode on CPU — Pallas timing is not meaningful here)" \
        if name == "pallas" else ""
    print(f"path={name:7s}  {dt*1e3:8.1f} ms/call  max|Δ|={err:.2e} {note}")

staged = fno_model_bytes(cfg, 4, training=False, fuse_block=False)
fused = fno_model_bytes(cfg, 4, training=False, fuse_block=True)
print(f"\nmodeled HBM traffic per forward: staged blocks {staged/2**20:.1f}"
      f" MiB vs fused blocks {fused/2**20:.1f} MiB — {staged/fused:.1f}x"
      f" less (a model, not a measurement).")
