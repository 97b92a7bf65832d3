"""Paper Fig. 14 / 19 — end-to-end TurboFNO vs PyTorch-style baseline over a
(K, BS) grid, 1D and 2D. derived = speedup (the paper's heatmap cell) —
paper reports avg 44% (1D) / 67% (2D), max 150-250%.

Plus the PR-4 fused-BLOCK row pair: one whole FNO block
gelu(spectral + bypass + bias) unfused (fused spectral kernel + XLA tail)
vs fully fused (ONE pallas_call) — wall time, modeled HBM bytes, and
kernel-call count (pallas_calls + total traced primitives).

Plus the PR-5 SERVING row pair: the batched FNO serve step (fused vs
unfused block) on a DP×TP mesh over the local devices — throughput in
samples/s. Row schema and the committed BENCH_*.json baselines are
documented in benchmarks/README.md."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import pipelines as pl
from benchmarks.common import row, time_fn
from repro.kernels import ops, ref as ref_k


# ---- 2D pipelines ----------------------------------------------------------
@jax.jit
def _rfft2(x):
    xf = jnp.fft.rfft2(x, axes=(-2, -1))
    return xf.real, xf.imag


@functools.partial(jax.jit, static_argnames=("kx", "ky"))
def _trunc2(xr, xi, kx, ky):
    return xr[..., :kx, :ky].copy(), xi[..., :kx, :ky].copy()


@jax.jit
def _cgemm2(wr, wi, xr, xi):
    yr = jnp.einsum("oh,bhxy->boxy", wr, xr) - jnp.einsum("oh,bhxy->boxy", wi, xi)
    yi = jnp.einsum("oh,bhxy->boxy", wr, xi) + jnp.einsum("oh,bhxy->boxy", wi, xr)
    return yr, yi


@functools.partial(jax.jit, static_argnames=("nx", "ny"))
def _pad_irfft2(yr, yi, nx, ny):
    kx, ky = yr.shape[-2:]
    pad = [(0, 0), (0, 0), (0, nx - kx), (0, ny // 2 + 1 - ky)]
    yf = jnp.pad(yr + 1j * yi, pad)
    return jnp.fft.irfft2(yf, s=(nx, ny), axes=(-2, -1))


def baseline2d(x, wr, wi, kx, ky):
    nx, ny = x.shape[-2:]
    fr, fi = _rfft2(x)
    tr, ti = _trunc2(fr, fi, kx, ky)
    yr, yi = _cgemm2(wr, wi, tr, ti)
    return _pad_irfft2(yr, yi, nx, ny)


@functools.partial(jax.jit, static_argnames=("kx", "ky"))
def turbo2d(x, wr, wi, kx, ky):
    return ops.spectral_layer_2d(x, wr, wi, (kx, ky), path="xla")


def run(quick: bool = False):
    print("# bench_e2e (paper Fig.14/19): name,us_per_call,derived")
    rng = np.random.default_rng(0)
    # --- 1D grid ---
    n = 256
    grid = [(16, 512), (32, 2048), (64, 8192), (128, 8192)]
    if quick:
        grid = grid[:2]
    speedups = []
    for h, bs in grid:
        k = n // 4
        b = max(1, bs // h)
        x = jnp.asarray(rng.normal(size=(b, h, n)), jnp.float32)
        wr = jnp.asarray(rng.normal(size=(h, h)) / h, jnp.float32)
        wi = jnp.asarray(rng.normal(size=(h, h)) / h, jnp.float32)
        t_base = time_fn(pl.baseline_staged, x, wr, wi, k)
        t_turbo = time_fn(pl.fused_full, x, wr, wi, k)
        s = t_base / t_turbo
        speedups.append(s)
        row(f"e2e1d_K{h}_BS{bs}", t_turbo, f"speedup={s:.2f}x")
    row("e2e1d_avg", 0.0,
        f"avg_speedup={np.mean(speedups):.2f}x max={np.max(speedups):.2f}x")

    # --- 2D grid ---
    nx = ny = 64 if quick else 128
    grid2 = [(16, 8), (32, 8), (64, 4)]
    if quick:
        grid2 = grid2[:1]
    speedups2 = []
    for h, b in grid2:
        kx, ky = nx // 4, ny // 4
        x = jnp.asarray(rng.normal(size=(b, h, nx, ny)), jnp.float32)
        wr = jnp.asarray(rng.normal(size=(h, h)) / h, jnp.float32)
        wi = jnp.asarray(rng.normal(size=(h, h)) / h, jnp.float32)
        t_base = time_fn(baseline2d, x, wr, wi, kx, ky)
        t_turbo = time_fn(turbo2d, x, wr, wi, kx, ky)
        s = t_base / t_turbo
        speedups2.append(s)
        row(f"e2e2d_K{h}_B{b}", t_turbo, f"speedup={s:.2f}x")
    row("e2e2d_avg", 0.0,
        f"avg_speedup={np.mean(speedups2):.2f}x max={np.max(speedups2):.2f}x")

    run_block(quick)
    run_tuned(quick)
    run_serve(quick)


def run_block(quick: bool = False):
    """Fused-block vs unfused-block row pair (PR 4): one whole 2D FNO
    block on the pallas path — the staged composition (fused spectral
    kernel + XLA bypass/bias/GELU tail) vs the single-pallas_call block.
    derived = modeled HBM bytes per forward + kernel-call counts; NOTE
    off-TPU the pallas kernels run in interpret mode so the wall-time
    ratio only validates the harness (the byte model carries the claim).
    """
    import dataclasses

    from repro.configs import get_config
    from repro.roofline.analysis import fno_model_bytes
    from repro.roofline.hlo_counter import (count_pallas_calls,
                                            jaxpr_primitive_counts)

    print("# bench_e2e fused-block rows: name,us_per_call,derived")
    rng = np.random.default_rng(1)
    b, h, n, k = (1, 16, 32, 8) if quick else (2, 32, 64, 16)
    x = jnp.asarray(rng.normal(size=(b, h, n, n)), jnp.float32)
    wr = jnp.asarray(rng.normal(size=(h, h)) / h, jnp.float32)
    wi = jnp.asarray(rng.normal(size=(h, h)) / h, jnp.float32)
    wb = jnp.asarray(rng.normal(size=(h, h)) / h, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(h,)) * 0.1, jnp.float32)

    @jax.jit
    def unfused(x, wr, wi, wb, bias):
        s = ops.spectral_layer_2d(x, wr, wi, (k, k), path="pallas")
        byp = jnp.einsum("oh,bhxy->boxy", wb, x)
        return jax.nn.gelu(s + byp + bias[None, :, None, None])

    @jax.jit
    def fused(x, wr, wi, wb, bias):
        return ops.fno_block_nd(x, wr, wi, wb, bias, (k, k), path="pallas",
                                variant="full")

    cfg = dataclasses.replace(
        get_config("fno2d", reduced=quick), hidden=h, spatial=(n, n),
        modes=(k, k), num_layers=1)
    # fno_model_bytes models a whole step; the benchmarked functions are
    # ONE bare block, so subtract the layer-independent io + lift/proj
    # traffic (the num_layers=0 evaluation) to get block-only bytes.
    overhead = fno_model_bytes(dataclasses.replace(cfg, num_layers=0), b,
                               training=False)
    times, bts = {}, {}
    for name, fn, fb in (("unfused", unfused, False), ("fused", fused, True)):
        times[name] = time_fn(fn, x, wr, wi, wb, bias, iters=5)
        bts[name] = fno_model_bytes(cfg, b, fuse_block=fb,
                                    training=False) - overhead
        n_pallas = count_pallas_calls(fn, x, wr, wi, wb, bias)
        # launch-level op count: pallas_call bodies NOT expanded, so the
        # unfused row carries the XLA tail (bypass GEMM/bias/sum/GELU)
        # the fused row folds into its single kernel
        n_ops = sum(jaxpr_primitive_counts(
            fn, x, wr, wi, wb, bias, into_kernels=False).values())
        row(f"block2d_{name}_H{h}N{n}", times[name],
            f"bytes={bts[name] / 2 ** 20:.2f}MiB pallas_calls={n_pallas} "
            f"launch_ops={n_ops}")
    row("block2d_fusion_gain", times["fused"],
        f"bytes_ratio={bts['fused'] / bts['unfused']:.3f}x "
        f"speedup={times['unfused'] / times['fused']:.2f}x")


def run_tuned(quick: bool = False):
    """Tuned vs default launch-plan row trios (ISSUE 7), ranks 1-3: the
    whole fused FNO block forward at the committed autotuned plan
    (``repro.tuning`` cache resolution, block_plan=None) against the
    static ``ops._BLOCK_DEFAULTS`` triple forced via ``block_plan=``.
    derived = the effective plans, each plan's VMEM launch estimate, the
    plan-invariant modeled HBM bytes, and the tuned/default parity
    max-|Δ| (must be float-noise). Off-TPU the kernels run in interpret
    mode, so wall time tracks grid-step count rather than MXU behavior —
    the VMEM estimates carry the feasibility claim (full-size 2D/3D fit
    the budget ONLY under tuned plans; the defaults are 2-9x over)."""
    import dataclasses

    from repro.analysis.vmem import launch_estimate
    from repro.configs import get_config
    from repro.kernels.ops import _BLOCK_DEFAULTS, legal_blocks
    from repro.roofline.analysis import fno_model_bytes
    from repro.tuning import resolve_block_plan

    print("# bench_e2e tuned-plan rows: name,us_per_call,derived")
    rng = np.random.default_rng(3)
    b = 4 if quick else 8
    for arch in ("fno1d", "fno2d", "fno3d"):
        cfg = get_config(arch, reduced=True)
        r, h = cfg.ndim, cfg.hidden
        modes = tuple(cfg.modes)
        x = jnp.asarray(rng.normal(size=(b, h) + tuple(cfg.spatial)),
                        jnp.float32)
        wr = jnp.asarray(rng.normal(size=(h, h)) / h, jnp.float32)
        wi = jnp.asarray(rng.normal(size=(h, h)) / h, jnp.float32)
        wb = jnp.asarray(rng.normal(size=(h, h)) / h, jnp.float32)
        bias = jnp.asarray(rng.normal(size=(h,)) * 0.1, jnp.float32)

        tuned = resolve_block_plan(cfg, "block_fwd").triple
        dflt = _BLOCK_DEFAULTS[r]
        shapes = (h, tuple(cfg.spatial), modes,
                  cfg.weight_mode == "per_mode")
        hbm = fno_model_bytes(
            dataclasses.replace(cfg, num_layers=1), b, fuse_block=True,
            training=False)
        outs, times = {}, {}
        for name, plan in (("tuned", None), ("default", dflt)):
            fn = jax.jit(functools.partial(
                ops.fno_block_nd, modes=modes, path="pallas",
                variant="full", block_plan=plan))
            times[name] = time_fn(fn, x, wr, wi, wb, bias, iters=5)
            outs[name] = fn(x, wr, wi, wb, bias)
            triple = tuned if plan is None else plan
            eff = legal_blocks(r, b, h, h, *triple)
            est = launch_estimate(shapes, "block_fwd", triple, batch=b)
            row(f"tuned_r{r}_{name}", times[name],
                f"plan={eff} vmem_est={est.total_bytes / 2**20:.2f}MiB "
                f"hbm_model={hbm / 2**20:.2f}MiB")
        err = float(jnp.max(jnp.abs(outs["tuned"] - outs["default"])))
        row(f"tuned_r{r}_gain", times["tuned"],
            f"speedup={times['default'] / times['tuned']:.2f}x "
            f"parity_max_err={err:.2e}")
        assert err < 1e-4, f"tuned/default parity broke at rank {r}: {err}"


def run_serve(quick: bool = False):
    """FNO serving throughput rows (ISSUE 5 + ISSUE 8): the batched serve
    step with the whole-block fusion on vs off, placed DP×TP over the
    local devices (DP shards the request batch, TP the hidden k-loop axis
    when it divides — docs/DESIGN.md §6), then the TP collective-layout
    pair — the scattered layout (interior psum_scatter emitting the next
    layer's hidden shard) vs the all-reduce-every-layer psum layout.
    derived = samples/s, the mesh grid, and `coll_bytes` — the modeled
    per-device ICI wire bytes of the TP collectives per forward
    (`roofline.analysis.fno_collective_bytes`); off-TPU the pallas kernels
    run in interpret mode and the collectives cross no real ICI, so the
    byte model carries the traffic claim (exactly 0.5x per interior layer)
    while the wall ratio only validates the harness."""
    import dataclasses

    from repro.configs import get_config
    from repro.core import fno as fno_mod
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.launch.serve_fno import _pick_tp
    from repro.roofline.analysis import fno_collective_bytes
    from repro.train import serve_fno_step as sfs

    print("# bench_e2e serving rows: name,us_per_call,derived")
    n_dev = jax.device_count()
    cfg0 = get_config("fno2d", reduced=True)
    tp = _pick_tp(n_dev, cfg0.hidden)  # the serving driver's own auto-pick
    dp = n_dev // tp
    mesh = make_mesh((dp, tp), ("data", "model"))
    b = 4 if quick else 8
    x = jnp.asarray(np.random.default_rng(2).normal(
        size=(b, cfg0.in_channels) + tuple(cfg0.spatial)), jnp.float32)

    def serve_time(cfg):
        ctx = shd.make_context(cfg, mesh, kind="serve")
        params = fno_mod.init_fno(jax.random.PRNGKey(0), cfg)
        # one full-bucket request per call — the server's own jit cache
        server = sfs.FNOServer(cfg, params, ctx=ctx, max_batch=b)
        return time_fn(server, x, iters=5)

    times = {}
    for name, fuse in (("unfused", False), ("fused", True)):
        cfg = dataclasses.replace(cfg0, path="pallas", fuse_block=fuse)
        times[name] = serve_time(cfg)
        cb = fno_collective_bytes(cfg, dp, tp, batch=b)
        row(f"serve2d_{name}_dp{dp}tp{tp}", times[name],
            f"samples_per_s={b / (times[name] * 1e-6):.1f} "
            f"coll_bytes={cb['total'] / 2**10:.1f}KiB")
    row("serve2d_fusion_gain", times["fused"],
        f"speedup={times['unfused'] / times['fused']:.2f}x grid=dp{dp}xtp{tp}")

    # TP collective-layout pair (ISSUE 8): fused serve step under the
    # scattered layout vs the legacy psum layout, same mesh. The modeled
    # interior-layer wire bytes halve under scatter; the final layer
    # always psums (the projection consumes the full hidden vector).
    lt, lb = {}, {}
    for layout in ("scatter", "psum"):
        cfg = dataclasses.replace(cfg0, path="pallas", fuse_block=True,
                                  tp_layout=layout)
        lt[layout] = serve_time(cfg)
        lb[layout] = fno_collective_bytes(cfg, dp, tp,
                                          scattered=layout == "scatter",
                                          batch=b)
        row(f"serve2d_fused_{layout}_dp{dp}tp{tp}", lt[layout],
            f"samples_per_s={b / (lt[layout] * 1e-6):.1f} "
            f"coll_bytes={lb[layout]['total'] / 2**10:.1f}KiB "
            f"interior_per_layer={lb[layout]['interior_per_layer'] / 2**10:.1f}KiB")
    ratio = (lb["scatter"]["interior_per_layer"]
             / lb["psum"]["interior_per_layer"]) if tp > 1 else 0.0
    row("serve2d_layout_gain", lt["scatter"],
        f"speedup={lt['psum'] / lt['scatter']:.2f}x "
        f"interior_bytes_ratio={ratio:.3f}x grid=dp{dp}xtp{tp}")

    run_replay(quick)


def run_replay(quick: bool = False):
    """Traffic-replay serving rows (ISSUE 10): the async continuous-
    batching tier (``train/serve_queue``) under a seeded Poisson-ish
    arrival schedule — p50/p99 enqueue→complete latency and queue-depth
    rows next to the throughput rows. The schedule is a pure function of
    its seed (no wall-clock randomness); the event loop runs on a virtual
    clock whose per-bucket service model is CALIBRATED from this host's
    measured fused serve step, and the arrival rate is set to ~1.2x the
    calibrated capacity so the queue actually builds depth on any
    machine. Admission/coalescing decisions are therefore deterministic
    given the calibration; absolute latencies are host latencies, same
    caveat as every wall-time row. Row schema: benchmarks/README.md."""
    import dataclasses

    from repro.configs import get_config
    from repro.core import fno as fno_mod
    from repro.train import serve_fno_step as sfs
    from repro.train import serve_queue as sq

    print("# bench_e2e replay rows: name,us_per_call,derived")
    cfg = dataclasses.replace(get_config("fno2d", reduced=True),
                              path="pallas", fuse_block=True)
    params = fno_mod.init_fno(jax.random.PRNGKey(0), cfg)
    max_batch = 4 if quick else 8
    server = sfs.FNOServer(cfg, params, max_batch=max_batch)
    steps = 2  # every request asks a 2-step device-resident rollout
    base = {}
    for b in server.buckets:
        xb = jnp.zeros((b, cfg.in_channels) + tuple(cfg.spatial),
                       jnp.float32)
        base[b] = time_fn(
            lambda xb=xb: server(xb, rollout_steps=steps), iters=3) * 1e-6
    service_model = lambda bucket, k: base[bucket]  # noqa: E731

    top = server.buckets[-1]
    mean_n = (1 + max_batch) / 2
    rate_hz = 1.2 * (top / base[top]) / mean_n  # ~1.2x calibrated capacity
    deadline_s = 20 * base[top]
    requests = 24 if quick else 64
    cbs = sq.ContinuousBatchingServer(
        server, queue_limit=2 * max_batch, coalesce_s=1.0 / rate_hz,
        clock=sq.VirtualClock(), service_model=service_model)
    sched = sq.poisson_schedule(7, requests, rate_hz=rate_hz,
                                max_n=max_batch, rollout_steps=steps,
                                deadline_s=deadline_s)
    rng = np.random.default_rng(7)
    xs = [jnp.asarray(rng.normal(
        size=(a.n, cfg.in_channels) + tuple(cfg.spatial)), jnp.float32)
        for a in sched]
    rep = cbs.replay(sched, lambda a, i: xs[i])
    s, lat, qd = rep["stats"], rep["latency"], rep["queue_depth"]
    row("serve2d_replay_lat", lat["p50"] * 1e6,
        f"p50_ms={lat['p50']*1e3:.2f} p99_ms={lat['p99']*1e3:.2f} "
        f"deadline_ms={deadline_s*1e3:.2f} completed={s['completed']} "
        f"rollout_steps={steps}")
    row("serve2d_replay_queue", 0.0,
        f"qdepth_p50={qd['p50']:.1f} qdepth_p99={qd['p99']:.1f} "
        f"qdepth_max={qd['max']:.0f} batches={s['batches']} "
        f"coalesced={s['coalesced']} shed={s['shed']} "
        f"deadline_exceeded={s['deadline_exceeded']}")
    tput = rep["served_samples"] / max(rep["makespan_s"], 1e-9)
    row("serve2d_replay_tput", 0.0,
        f"samples_per_s={tput:.1f} makespan_ms={rep['makespan_s']*1e3:.0f} "
        f"offered={s['offered']} accepted={s['accepted']}")


if __name__ == "__main__":
    run()
