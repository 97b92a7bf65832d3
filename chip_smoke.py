#!/usr/bin/env python3
"""Smoke test of the fused FNO path on a TPU: serve and train full-width
fno2d through the normal entry points, and check what comes out.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four chips: the DP×TP serving mesh

One chip (the default) runs three phases in this one process:

* serve f32 and serve bf16 — ``FNOServer`` (built the way
  ``python -m repro.launch.serve --arch fno2d`` builds it) answers four
  request batches of mixed size at full fno2d width with ``max_batch`` 8.
  The compiled serve step must hold ``fno.pallas_launches`` Mosaic kernels
  (``tpu_custom_call``), every output must be finite, and the outputs must
  match the ``path="ref"`` oracle computed in f32 under
  ``jax.default_matmul_precision("highest")``.
* train — three steps of ``make_train_step(cfg, opt, fno_path="pallas")``
  (fused block forward and backward) at batch 8, f32, on seeded Darcy
  data, through ``Trainer``: finite losses and grad norms, no step skipped
  by the trainer's non-finite budget, and a step-0 loss that matches the
  ``fno_path="xla"`` step.

``--chips 4`` runs only the serving mesh: full-width fno2d on a dp2×tp2
mesh with the default scattered TP layout, the same requests on one device
of the host, a parity check between the two, and the collective plan.

Each phase prints its timings (warm-up = compile + first call), errors and
peak device memory. Any failure raises and exits non-zero; a run without a
TPU exits 2 at once. The last line of a passing run is the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
import argparse
import dataclasses
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "fno2d"
MAX_BATCH = 8
REQUEST_SIZES = (3, 8, 1, 5)  # buckets 4, 8, 1 and 8 of the ladder
TRAIN_STEPS = 3
TRAIN_BATCH = 8

# Served output vs the f32 "highest"-precision ref oracle, as
# max|y - ref| / max|ref|. XLA's default matmul precision on a TPU rounds
# f32 operands to bf16 (2^-9 relative) in the lifting/projection einsums
# around the kernels, about ten matmuls deep: f32 gets 2e-2. bf16 adds a
# bf16 rounding of the activations at every layer boundary and bf16 DFT
# operands inside the kernels: 5e-2.
SERVE_TOL = {"f32": 2e-2, "bf16": 5e-2}
# Step-0 loss, fused pallas vs the XLA path at "highest" precision,
# relative: the same bf16-operand rounding as the f32 serve tolerance,
# averaged over the batch by the relative-L2 loss.
TRAIN_LOSS_RTOL = 1e-2
# dp2×tp2 mesh vs one device. Each path is also held to the f32 serve
# tolerance against the ref oracle; between them the roundings differ in
# place: on the mesh each shard's kernel contracts half the hidden width
# and the bias + GELU epilogue runs in XLA after the reduce-scatter, on
# one device all of it runs in the kernel (3.1e-3 apart on a TPU v5e
# host). A missed reduction or a misplaced shard is an O(1) error.
MESH_TOL = 1e-2


def _log(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def _peak_memory(jax) -> str:
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"


def _rel_err(ys, refs) -> float:
    """max|y - ref| / max|ref| over request batches, on the host."""
    import numpy as np

    y = np.concatenate([np.asarray(a, np.float32) for a in ys])
    ref = np.concatenate([np.asarray(a, np.float32) for a in refs])
    return float(np.max(np.abs(y - ref)) / np.max(np.abs(ref)))


def _requests(jax, cfg, seed: int = 0):
    key = jax.random.PRNGKey(seed)
    return [jax.random.normal(jax.random.fold_in(key, i),
                              (n, cfg.in_channels) + tuple(cfg.spatial))
            for i, n in enumerate(REQUEST_SIZES)]


def _server(dtype: str, mesh_args=()):
    """A FNOServer built as ``launch.serve --arch fno2d`` builds it."""
    from repro.launch import serve_fno
    from repro.train.serve_fno_step import FNOServer

    args = serve_fno.build_parser().parse_args(
        ["--arch", ARCH, "--dtype", dtype, "--max-batch", str(MAX_BATCH),
         *mesh_args])
    cfg, ctx, params, dp, tp = serve_fno.build(args)
    server = FNOServer(cfg, params, ctx=ctx, path=args.path,
                       variant=args.variant, max_batch=args.max_batch)
    return cfg, server, dp, tp


def _serve(jax, cfg, server, tag: str):
    """Warm every bucket, assert the kernel count, serve the requests."""
    import numpy as np

    from repro.core import fno as fno_mod

    warm = {}
    for b in server.buckets:
        xb = jax.numpy.zeros((b, cfg.in_channels) + tuple(cfg.spatial))
        t0 = time.perf_counter()
        jax.block_until_ready(server(xb))
        warm[b] = time.perf_counter() - t0
    xb = jax.numpy.zeros((server.buckets[0], cfg.in_channels)
                         + tuple(cfg.spatial))
    text = jax.jit(server.step_fn).lower(server.params,
                                         {"x": xb}).compile().as_text()
    kernels = text.count("tpu_custom_call")
    want = fno_mod.pallas_launches(cfg, ctx=server.ctx)
    assert kernels == want, (
        f"{tag}: compiled serve step holds {kernels} tpu_custom_call(s), "
        f"want one per layer and one per staged end ({want})")
    reqs = _requests(jax, cfg)
    jax.block_until_ready(reqs)
    t0 = time.perf_counter()
    ys = [server(x) for x in reqs]
    jax.block_until_ready(ys)
    dt = time.perf_counter() - t0
    for y in ys:
        assert np.isfinite(np.asarray(y, np.float32)).all(), \
            f"{tag}: non-finite serve output"
    _log(f"{tag}: buckets={server.buckets} warm-up s (compile + first "
         f"call) per bucket={ {b: round(t, 3) for b, t in warm.items()} } "
         f"tpu_custom_calls={kernels} served {len(reqs)} requests / "
         f"{sum(REQUEST_SIZES)} samples in {dt * 1e3:.3f} ms")
    return reqs, ys, text


def _check_vs_ref(jax, server, reqs, ys, dtype: str, tag: str) -> None:
    """Served outputs vs the f32 ``path="ref"`` oracle at "highest"."""
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core import fno as fno_mod

    ref_cfg = dataclasses.replace(get_config(ARCH), path="ref")
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, x: fno_mod.apply_fno(p, ref_cfg, x,
                                                     path="ref"))(
            server.params, jnp.concatenate(reqs))
    err = _rel_err(ys, [ref])
    _log(f"{tag}: max|y-ref|/max|ref| = {err!r} (tol {SERVE_TOL[dtype]}) "
         f"peak memory {_peak_memory(jax)}")
    assert err <= SERVE_TOL[dtype], (
        f"{tag}: error {err} vs the highest-precision ref oracle exceeds "
        f"{SERVE_TOL[dtype]}")


def serve_phase(jax, dtype: str) -> None:
    cfg, server, dp, tp = _server(dtype)
    assert dp * tp == 1, (dp, tp)
    tag = f"serve {ARCH} {dtype}"
    reqs, ys, _ = _serve(jax, cfg, server, tag)
    _check_vs_ref(jax, server, reqs, ys, dtype, tag)


def train_phase(jax) -> None:
    import math

    from repro.configs import get_config
    from repro.configs.fno import with_fuse_block, with_precision
    from repro.core import fno as fno_mod
    from repro.data import pde
    from repro.optim import AdamW
    from repro.optim.schedule import cosine_warmup
    from repro.train.train_step import make_train_step
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = with_fuse_block(with_precision(get_config(ARCH), "f32"))
    n = cfg.spatial[0]
    params = fno_mod.init_fno(jax.random.PRNGKey(0), cfg)
    opt = AdamW(lr=cosine_warmup(1e-3, 1, TRAIN_STEPS), weight_decay=0.0)

    def batch_fn(i):
        return pde.darcy_batch(0, i, TRAIN_BATCH, n, iters=150)

    t0 = time.perf_counter()
    b0 = jax.block_until_ready(batch_fn(0))
    t_data = time.perf_counter() - t0
    step = jax.jit(make_train_step(cfg, opt, fno_path="pallas"))
    t0 = time.perf_counter()
    _, _, m_p = jax.block_until_ready(step(params, opt.init(params), b0))
    t_pallas = time.perf_counter() - t0
    xla_step = jax.jit(make_train_step(cfg, opt, fno_path="xla"))
    with jax.default_matmul_precision("highest"):
        _, _, m_x = jax.block_until_ready(
            xla_step(params, opt.init(params), b0))
    lp, lx = float(m_p["loss"]), float(m_x["loss"])
    rel = abs(lp - lx) / abs(lx)
    _log(f"train {ARCH} f32: darcy batch {t_data:.3f} s, pallas step "
         f"warm-up (compile + first call) {t_pallas:.3f} s; step-0 loss "
         f"pallas={lp!r} xla(highest)={lx!r} rel diff={rel!r} "
         f"(tol {TRAIN_LOSS_RTOL})")
    assert rel <= TRAIN_LOSS_RTOL, (
        f"train: step-0 loss {lp} vs XLA {lx} (rel {rel}) exceeds "
        f"{TRAIN_LOSS_RTOL}")

    with tempfile.TemporaryDirectory() as ckpt_dir:
        tcfg = TrainerConfig(total_steps=TRAIN_STEPS,
                             ckpt_every=TRAIN_STEPS, ckpt_dir=ckpt_dir,
                             log_every=1)
        out = Trainer(tcfg, step, batch_fn, params, opt.init(params)).run()
    for m in out["metrics"]:
        _log(f"train step {m['step']}: loss={m['loss']!r} "
             f"grad_norm={m['grad_norm']!r} {m['dt'] * 1e3:.3f} ms")
        assert math.isfinite(m["loss"]) and math.isfinite(m["grad_norm"])
    assert out["nan_skipped"] == 0, f"{out['nan_skipped']} steps skipped"
    assert out["final_step"] == TRAIN_STEPS and \
        len(out["metrics"]) == TRAIN_STEPS, out
    _log(f"train {ARCH} f32: {TRAIN_STEPS} steps, none skipped; peak "
         f"memory {_peak_memory(jax)}")


def mesh_phase(jax) -> None:
    import jax.numpy as jnp

    cfg, mesh_server, dp, tp = _server("f32")
    assert (dp, tp) == (2, 2), f"want the dp2×tp2 auto mesh, got {dp}×{tp}"
    assert cfg.tp_layout == "scatter", cfg.tp_layout
    plan = mesh_server.collective_plan()
    _log(f"mesh dp{dp}×tp{tp} collective plan: {plan}")
    tag = f"serve {ARCH} dp2×tp2"
    reqs, ys, text = _serve(jax, cfg, mesh_server, tag)
    _check_vs_ref(jax, mesh_server, reqs, ys, "f32", tag)
    assert "reduce-scatter" in text or "all-reduce" in text, \
        "the TP serve step compiled without a cross-shard collective"
    xb = jnp.zeros((mesh_server.buckets[-1], cfg.in_channels)
                   + tuple(cfg.spatial))
    placed = jax.jit(mesh_server.step_fn)(mesh_server.params, {"x": xb})
    n_dev = len(placed.sharding.device_set)
    assert n_dev == 4, f"mesh serve output lives on {n_dev} device(s)"

    cfg1, one_server, dp1, tp1 = _server("f32", ("--dp", "1", "--tp", "1"))
    assert (dp1, tp1) == (1, 1)
    tag1 = f"serve {ARCH} one device"
    _, ys1, _ = _serve(jax, cfg1, one_server, tag1)
    _check_vs_ref(jax, one_server, reqs, ys1, "f32", tag1)
    err = _rel_err(ys, ys1)
    _log(f"mesh vs one device: max|y_mesh-y_one|/max|y_one| = {err!r} "
         f"(tol {MESH_TOL}); output spread over {n_dev} devices; peak "
         f"memory (device 0) {_peak_memory(jax)}")
    assert err <= MESH_TOL, f"mesh vs one device: {err} > {MESH_TOL}"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the dp2×tp2 serving mesh and its "
                         "one-device comparison")
    args = ap.parse_args()

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s) visible", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    kind = devices[0].device_kind
    _log(f"jax {jax.__version__} platform={platform} device_kind={kind!r} "
         f"count={len(devices)} compile cache={cache}")
    t0 = time.perf_counter()
    if args.chips == 4:
        mesh_phase(jax)
    else:
        serve_phase(jax, "f32")
        serve_phase(jax, "bf16")
        train_phase(jax)
    _log(f"all phases passed in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
