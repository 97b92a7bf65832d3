"""Batched FNO serving driver — the production inference path (ISSUE 5).

    PYTHONPATH=src python -m repro.launch.serve --arch fno2d --reduced \
        --requests 8 --max-batch 8

Request batches of random sizes are bucketed and padded to the fused
kernel's batch blocks (``train.serve_fno_step``), each bucket gets one jit
cache entry, and the forward runs on a (data × model) mesh over the local
devices: DP shards the batch, TP shards the hidden k-loop axis when it
divides (docs/DESIGN.md §6). On the default pallas path the driver also
asserts the fusion contract — one pallas_call per FNO layer — and that
every served output is finite, so it doubles as the CI serving smoke
(scripts/check.sh).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import FNO_IDS, get_config
from repro.configs.fno import with_precision
from repro.core import fno as fno_mod
from repro.distributed import sharding as shd
from repro.launch.mesh import make_mesh
from repro.train import serve_fno_step as sfs


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="fno2d", choices=list(FNO_IDS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8,
                    help="number of synthetic request batches to serve")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="largest request batch (and bucket ceiling)")
    ap.add_argument("--path", default="pallas",
                    choices=["ref", "xla", "pallas"])
    ap.add_argument("--variant", default="full", choices=["full", "partial"])
    ap.add_argument("--dtype", default="f32", choices=["f32", "bf16"])
    ap.add_argument("--no-fuse-block", action="store_true",
                    help="serve the staged (unfused-block) pallas path")
    ap.add_argument("--rollout-steps", type=int, default=1,
                    help="serve K-step autoregressive rollouts: step t+1 "
                         "runs on step t's output inside ONE jitted "
                         "lax.scan (device-resident — the carry never "
                         "leaves HBM; docs/DESIGN.md §10)")
    ap.add_argument("--replay", action="store_true",
                    help="traffic replay through the async continuous-"
                         "batching tier: a seeded Poisson-ish arrival "
                         "schedule (no wall-clock randomness) coalesced "
                         "into kernel-block buckets on a virtual clock, "
                         "printing p50/p99 latency and queue-depth next "
                         "to throughput (docs/DESIGN.md §10)")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="--replay arrival rate in requests/s")
    ap.add_argument("--deadline-ms", type=float, default=100.0,
                    help="--replay per-request deadline (milliseconds)")
    ap.add_argument("--chaos", action="store_true",
                    help="replay the standard fault plan (kernel fault, "
                         "NaN injection, replica kill, corrupt checkpoint) "
                         "through the resilient runtime and print pool/"
                         "degradation stats (docs/DESIGN.md §9)")
    ap.add_argument("--replicas", type=int, default=2,
                    help="replica-pool size for --chaos")
    ap.add_argument("--dp", type=int, default=0,
                    help="data-parallel shards (0 = devices // tp)")
    ap.add_argument("--tp", type=int, default=0,
                    help="tensor-parallel shards over hidden (0 = auto: "
                         "the largest divisor of both the device count and "
                         "hidden that keeps dp >= tp — FNO serving is "
                         "batch-throughput-bound, so DP gets the devices "
                         "TP can't use)")
    return ap


def _pick_tp(n_dev: int, hidden: int) -> int:
    best = 1
    for tp in range(2, n_dev + 1):
        if n_dev % tp == 0 and hidden % tp == 0 and n_dev // tp >= tp:
            best = tp
    return best


def build(args):
    """(cfg, ctx, params, dp, tp): the configured model, its DP×TP
    sharding context over the local devices, and its seeded params —
    what every serving mode (and ``chip_smoke.py``) starts from."""
    cfg = with_precision(get_config(args.arch, reduced=args.reduced),
                         args.dtype)
    fuse = args.path == "pallas" and not args.no_fuse_block
    cfg = dataclasses.replace(cfg, path=args.path, fuse_block=fuse)

    n_dev = jax.device_count()
    tp = args.tp or _pick_tp(n_dev, cfg.hidden)
    dp = args.dp or max(n_dev // tp, 1)
    if dp * tp > n_dev:
        raise SystemExit(
            f"serve_fno: requested mesh dp{dp}xtp{tp} needs {dp * tp} "
            f"devices but only {n_dev} are visible — pass --dp/--tp whose "
            f"product fits the host (or omit them for the auto grid)")
    mesh = make_mesh((dp, tp), ("data", "model"))
    ctx = shd.make_context(cfg, mesh, kind="serve")
    params = fno_mod.init_fno(jax.random.PRNGKey(0), cfg)
    return cfg, ctx, params, dp, tp


def run(args) -> dict:
    cfg, ctx, params, dp, tp = build(args)
    fuse = cfg.fuse_block
    key = jax.random.PRNGKey(0)
    if args.chaos:
        return _run_chaos(args, cfg, ctx, params, key, dp, tp)
    if args.replay:
        return _run_replay(args, cfg, ctx, params, key, dp, tp)
    server = sfs.FNOServer(cfg, params, ctx=ctx, path=args.path,
                           variant=args.variant, max_batch=args.max_batch)

    # Fusion contract (trace-level, robust to interpret mode): ONE
    # pallas_call per FNO layer on the fused-block path, even through the
    # shard_map dispatch. Only the full-fusion variant makes this promise —
    # the paper-faithful partial variant legitimately runs a multi-kernel
    # spectral pipeline per layer. Checked through the contract-linter
    # framework (the same checker scripts/lint.py --trace sweeps). A
    # K-step rollout makes the SAME promise for any K (the scan body
    # traces once — docs/DESIGN.md §10).
    if fuse and args.variant == "full":
        import functools

        from repro.analysis import format_findings
        from repro.analysis.jaxpr_lint import (check_pallas_count,
                                               serve_step_contract)
        findings = serve_step_contract(server, cfg)
        if args.rollout_steps > 1:
            xb = jnp.zeros((server.buckets[0], cfg.in_channels)
                           + tuple(cfg.spatial), jnp.float32)
            findings += check_pallas_count(
                functools.partial(server.rollout_step_fn,
                                  steps=args.rollout_steps),
                (params, {"x": xb}), cfg.num_layers,
                target=f"{cfg.name} rollout K={args.rollout_steps}")
        assert not findings, format_findings(findings)

    rng = np.random.default_rng(0)
    sizes = rng.integers(1, args.max_batch + 1, size=args.requests)
    # Warm the jit cache (one compile per bucket) outside the timed loop.
    for b in server.buckets:
        jax.block_until_ready(server(jnp.zeros(
            (b, cfg.in_channels) + tuple(cfg.spatial), jnp.float32),
            rollout_steps=args.rollout_steps))

    # Pre-build the request batches and validate outputs after the clock
    # stops, so samples_per_s measures the serve steps — not input
    # generation or the device->host transfer of the finite check.
    reqs = [jax.random.normal(jax.random.fold_in(key, i),
                              (int(n), cfg.in_channels) + tuple(cfg.spatial))
            for i, n in enumerate(sizes)]
    jax.block_until_ready(reqs)
    t0 = time.time()
    ys = [server(x, rollout_steps=args.rollout_steps) for x in reqs]
    jax.block_until_ready(ys)
    dt = time.time() - t0
    for y in ys:
        assert np.isfinite(np.asarray(y)).all(), "non-finite serve output"

    samples = int(sizes.sum())
    plan = server.collective_plan()
    out = {
        "arch": args.arch, "path": args.path, "fuse_block": fuse,
        "dp": dp, "tp": tp, "buckets": list(server.buckets),
        "rollout_steps": args.rollout_steps,
        "requests": args.requests, "samples": samples,
        "padded": server.stats["padded"],
        "samples_per_s": samples / max(dt, 1e-9),
        "collective_plan": plan,
    }
    print(f"serve_fno arch={args.arch} mesh=dp{dp}xtp{tp} path={args.path} "
          f"fuse_block={fuse} dtype={args.dtype} "
          f"buckets={list(server.buckets)}")
    print(f"  collective plan: interior={plan['interior_collective']} "
          f"final={plan['final_collective']} "
          f"layout={plan['tp_layout']} overlap={plan['tp_overlap']} "
          f"wire={plan['wire_bytes_per_fwd'] / 2**10:.1f}KiB/fwd")
    print(f"  served {args.requests} requests / {samples} samples "
          f"(rollout K={args.rollout_steps}) in "
          f"{dt*1e3:.0f} ms ({out['samples_per_s']:.1f} samples/s, "
          f"{server.stats['padded']} padded), all outputs finite")
    return out


def _run_replay(args, cfg, ctx, params, key, dp, tp) -> dict:
    """--replay: the async continuous-batching tier under a seeded
    Poisson-ish traffic replay (docs/DESIGN.md §10). The arrival schedule
    is a pure function of the seed; the event loop runs on a virtual
    clock with a per-bucket service model CALIBRATED from this host's
    measured step times, so the p50/p99 rows reflect the machine while
    the admission/coalescing decisions stay deterministic given the
    calibration. scripts/serve_replay_smoke.py is the stricter CI gate
    (fixed synthetic service model → machine-independent exact counts)."""
    from repro.train import serve_queue as sq
    from repro.train import serve_runtime as srt

    rs = srt.ResilientServer(cfg, params, replicas=args.replicas, ctx=ctx,
                             variant=args.variant,
                             max_batch=args.max_batch,
                             queue_limit=max(args.requests, 1), seed=0)
    buckets = rs.primary.buckets
    steps = args.rollout_steps
    # Calibrate the virtual-time service model: median of 3 measured
    # calls per (bucket, steps) after a warmup compile.
    base = {}
    for b in buckets:
        xb = jnp.zeros((b, cfg.in_channels) + tuple(cfg.spatial),
                       jnp.float32)
        jax.block_until_ready(rs.primary(xb, rollout_steps=steps))
        ts = []
        for _ in range(3):
            t0 = time.time()
            jax.block_until_ready(rs.primary(xb, rollout_steps=steps))
            ts.append(time.time() - t0)
        base[b] = float(np.median(ts))
    service_model = lambda bucket, k: base[bucket]  # noqa: E731

    cbs = sq.ContinuousBatchingServer(
        rs, queue_limit=args.max_batch * 2, coalesce_s=2.0 / args.rate,
        clock=sq.VirtualClock(), service_model=service_model)
    sched = sq.poisson_schedule(
        0, args.requests, rate_hz=args.rate, max_n=args.max_batch,
        rollout_steps=steps, deadline_s=args.deadline_ms * 1e-3)

    def input_fn(a, i):
        return np.asarray(jax.random.normal(
            jax.random.fold_in(key, i),
            (a.n, cfg.in_channels) + tuple(cfg.spatial)))

    rep = cbs.replay(sched, input_fn)
    for r in cbs.requests.values():
        if r.status == "done":
            assert np.isfinite(np.asarray(r.y)).all(), \
                "non-finite replay output"
    s, lat, qd = rep["stats"], rep["latency"], rep["queue_depth"]
    print(f"serve_fno --replay arch={args.arch} mesh=dp{dp}xtp{tp} "
          f"rate={args.rate:.0f}req/s deadline={args.deadline_ms:.0f}ms "
          f"rollout K={steps} buckets={list(buckets)}")
    print(f"  admission: offered={s['offered']} accepted={s['accepted']} "
          f"shed={s['shed']} deadline_exceeded={s['deadline_exceeded']} "
          f"completed={s['completed']}")
    print(f"  batching: batches={s['batches']} coalesced={s['coalesced']} "
          f"queue_depth p50={qd['p50']:.1f} p99={qd['p99']:.1f} "
          f"max={qd['max']:.0f}")
    print(f"  latency: p50={lat['p50']*1e3:.2f}ms p99={lat['p99']*1e3:.2f}ms "
          f"mean={lat['mean']*1e3:.2f}ms over {lat['count']} completed "
          f"({rep['served_samples']} samples, "
          f"makespan {rep['makespan_s']*1e3:.0f}ms virtual)")
    return {"arch": args.arch, "dp": dp, "tp": tp, **rep}


def _run_chaos(args, cfg, ctx, params, key, dp, tp) -> dict:
    """--chaos: replay the standard deterministic fault plan through the
    resilient runtime (ResilientServer), asserting every accepted request
    is answered finite, then print the pool/degradation stats next to the
    collective plan. scripts/chaos_smoke.py is the stricter CI gate; this
    mode is the operator-facing replay."""
    import tempfile

    from repro.checkpoint import Checkpointer
    from repro.distributed import faults as flt
    from repro.train import serve_runtime as srt

    plan = flt.standard_chaos_plan()
    with tempfile.TemporaryDirectory() as ckdir:
        ck = Checkpointer(ckdir)
        rs = srt.ResilientServer(
            cfg, params, replicas=args.replicas, ctx=ctx,
            variant=args.variant, max_batch=args.max_batch,
            queue_limit=max(args.requests, 1), fault_plan=plan,
            checkpointer=ck, seed=0, backoff_base_s=1e-3)
        rng = np.random.default_rng(0)
        sizes = rng.integers(1, args.max_batch + 1, size=args.requests)
        t0 = time.time()
        ys = []
        for i, n in enumerate(sizes):
            x = jax.random.normal(
                jax.random.fold_in(key, i),
                (int(n), cfg.in_channels) + tuple(cfg.spatial))
            ys.append(rs(x))
        dt = time.time() - t0
        for y in ys:
            assert np.isfinite(y).all(), "non-finite chaos serve output"
        # The corrupt-checkpoint leg: a corrupted-on-disk step must make
        # the hot reload roll back (old params keep serving).
        ck.save(1, params)
        flt.corrupt_checkpoint(ckdir, 1)
        assert rs.reload() is False, "reload of a corrupt ckpt must roll back"

        report = rs.pool_report()
        plan_srv = rs.primary.collective_plan()
        samples = int(sizes.sum())
        print(f"serve_fno --chaos arch={args.arch} mesh=dp{dp}xtp{tp} "
              f"replicas={args.replicas} requests={args.requests}")
        print(f"  collective plan: interior={plan_srv['interior_collective']} "
              f"final={plan_srv['final_collective']} "
              f"layout={plan_srv['tp_layout']} overlap={plan_srv['tp_overlap']} "
              f"wire={plan_srv['wire_bytes_per_fwd'] / 2**10:.1f}KiB/fwd")
        print(f"  pool: {report['replicas']}")
        print(f"  stats: accepted={report['accepted']} "
              f"served={report['served']} degraded={report['degraded']} "
              f"shed={report['shed']} failovers={report['failovers']} "
              f"quarantined={report['quarantined']} "
              f"reinstated={report['reinstated']} "
              f"rollbacks={report['rollbacks']}")
        print(f"  served {samples} samples in {dt*1e3:.0f} ms under the "
              f"fault plan; all outputs finite")
        return {"arch": args.arch, "dp": dp, "tp": tp, **report}


def main() -> None:
    run(build_parser().parse_args())


if __name__ == "__main__":
    main()
