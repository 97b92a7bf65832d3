"""End-to-end FNO training driver (deliverable b): data generation →
sharded train step → checkpointing → restart-safe loop.

Reduced demo (runs in ~a minute on this CPU container):

    PYTHONPATH=src python examples/train_fno.py --steps 60

--full trains the chosen --arch at its full width (default --arch fno2d);
without it the arch runs at its reduced CPU-smoke size. Full-width fno2d
on the fused kernels, fwd and bwd, on a TPU:

    PYTHONPATH=src python examples/train_fno.py --arch fno2d --full \
        --path pallas --fuse-block --steps 300 --batch 8 --lr 3e-4

The ~100M-parameter per-mode configuration is its own arch:
--arch fno2d-large --full.

Rank sweep: --arch fno1d / fno2d / fno3d trains the matching PDE task
(Burgers / Darcy / 3D diffusion-reaction) through the same rank-generic
fused engine.

Mixed precision: --dtype bf16 selects the bf16 PrecisionPolicy — bf16
compute/spectral operands through the fused kernels (halving the
memory-bound layer's HBM traffic) with f32 master params, accumulators,
and AdamW update. --dtype f32 (default) is the pure-f32 policy.
"""
import argparse
import tempfile

import jax

from repro.configs import get_config
from repro.configs.fno import with_fuse_block, with_precision
from repro.core import fno
from repro.data import pde
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import AdamW
from repro.optim.schedule import cosine_warmup
from repro.train.train_step import make_train_step
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--arch", default="fno2d",
                    choices=["fno1d", "fno2d", "fno2d-large", "fno3d"],
                    help="architecture/rank; picks the matching PDE task "
                         "(Burgers 1D / Darcy 2D / diffusion-reaction 3D)")
    ap.add_argument("--full", action="store_true",
                    help="train --arch at its full width (default: the "
                         "reduced smoke size)")
    ap.add_argument("--path", default="xla", choices=["ref", "xla", "pallas"],
                    help="pallas = fused kernels fwd AND bwd (custom_vjp); "
                         "no staged-XLA fallback")
    ap.add_argument("--variant", default="full", choices=["full", "partial"],
                    help="2D/3D pallas fusion: full (beyond-paper) or "
                         "partial (paper-faithful)")
    ap.add_argument("--dtype", default="f32", choices=["f32", "bf16"],
                    help="precision policy: bf16 = bf16 compute/spectral "
                         "operands with f32 master params + accumulators "
                         "(mixed precision); f32 = pure f32")
    ap.add_argument("--fuse-block", action="store_true",
                    help="pallas path: fuse each whole FNO block "
                         "(spectral + 1x1 bypass + bias + GELU) into ONE "
                         "pallas_call per layer, fwd and bwd")
    args = ap.parse_args()

    if args.fuse_block and args.path != "pallas":
        ap.error("--fuse-block requires --path pallas (the staged paths "
                 "stay the parity oracle)")
    enable_compile_cache()
    cfg = with_precision(get_config(args.arch, reduced=not args.full),
                         args.dtype)
    if args.fuse_block:
        cfg = with_fuse_block(cfg)
    key = jax.random.PRNGKey(0)
    params = fno.init_fno(key, cfg)
    n = cfg.spatial[0]
    print(f"{cfg.name}: {cfg.param_count()/1e6:.1f}M params, "
          f"grid {cfg.spatial}, modes {cfg.modes}, "
          f"weights={cfg.weight_mode}, path={args.path}, "
          f"variant={args.variant}, dtype={args.dtype} "
          f"(compute={cfg.precision.compute_dtype}, "
          f"params={cfg.precision.param_dtype}), "
          f"fuse_block={cfg.fuse_block}")

    opt = AdamW(lr=cosine_warmup(args.lr, args.steps // 10 + 1, args.steps),
                weight_decay=0.0)
    step = jax.jit(make_train_step(cfg, opt, fno_path=args.path,
                                   fno_variant=args.variant))
    if cfg.ndim == 1:
        batch_fn = lambda i: pde.burgers_batch(0, i, args.batch, n)
    elif cfg.ndim == 2:
        batch_fn = lambda i: pde.darcy_batch(0, i, args.batch, n,
                                             iters=150 if args.full else 100)
    else:
        batch_fn = lambda i: pde.diffusion3d_batch(0, i, args.batch, n)

    with tempfile.TemporaryDirectory() as ckpt_dir:
        tcfg = TrainerConfig(total_steps=args.steps, ckpt_every=50,
                             ckpt_dir=ckpt_dir, log_every=10)
        trainer = Trainer(tcfg, step, batch_fn, params, opt.init(params))
        out = trainer.run()
    for m in out["metrics"]:
        print(f"  step {m['step']:4d}  loss {m['loss']:.4f}  "
              f"gnorm {m['grad_norm']:.2f}  {m['dt']*1e3:.0f} ms")
    print(f"finished {out['final_step']} steps; "
          f"stragglers flagged: {out['stragglers']}")


if __name__ == "__main__":
    main()
