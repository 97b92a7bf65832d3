"""Closed loop: one client sends a request of ``request_samples`` fields,
waits until its answer is on the host, and sends the next.

Mix keys: ``request_samples``, ``max_batch`` (the server's bucket
ceiling), ``pool_samples`` (distinct host inputs the requests cycle
through), ``mesh`` (``[dp, tp]`` over the cell's chips, or null for one
chip), ``compare_requests`` (answers kept, drawn from the seed, for the
comparison) and ``ref_rows``.

The server is ``FNOServer`` as ``launch/serve.py`` builds it: the
configuration's path, a bucket ladder up to ``max_batch``, and on a mesh
the DP×TP sharding context of ``launch/serve_fno.build``.
"""
from __future__ import annotations

import time

import jax
import numpy as np

import compare
import harness
import inputs


def _context(run, cfg):
    mesh_shape = run.mix.get("mesh")
    if not mesh_shape:
        return None
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_mesh
    mesh = make_mesh(tuple(mesh_shape), ("data", "model"))
    return shd.make_context(cfg, mesh, kind="serve")


def setup(run):
    from repro.core import fno
    from repro.train.serve_fno_step import FNOServer

    cfg, mix = run.fno_config(), run.mix
    ctx = _context(run, cfg)
    params = jax.jit(lambda k: fno.init_fno(k, cfg))(run.key())
    server = FNOServer(cfg, params, ctx=ctx, path=cfg.path,
                       variant=run.cfg.get("variant", "full"),
                       max_batch=mix["max_batch"])
    n, req = mix["pool_samples"], mix["request_samples"]
    pool = inputs.darcy_inputs(run.rng(1), n, cfg.spatial[0])
    np.asarray(server(pool[:req]))  # warm the one bucket the window uses
    server.stats = {k: 0 for k in server.stats}
    program = jax.jit(server.step_fn).lower(params,
                                            {"x": pool[:req]}).compile()
    return {"server": server, "pool": pool, "compiled": program}


def window(run, st):
    server, pool = st.pop("server"), st["pool"]
    req, n = run.mix["request_samples"], len(st["pool"])
    keep_n = run.mix["compare_requests"]
    rng = run.rng(2)
    kept = []  # reservoir of (pool offset, answer)
    k = 0
    t0 = time.perf_counter()
    while True:
        with run.span("request generation"):
            off = (k * req) % (n - req + 1)
            x = pool[off:off + req]
        with run.span("server call"):
            y = server(x)
        with run.span("output fetch"):
            y = np.asarray(y)
        if len(kept) < keep_n:
            kept.append((off, y))
        else:
            j = int(rng.integers(0, k + 1))
            if j < keep_n:
                kept[j] = (off, y)
        k += 1
        if time.perf_counter() - t0 >= run.seconds:
            break
    dt = time.perf_counter() - t0
    stats = dict(server.stats)
    return harness.Window(
        metrics={"serve_samples_per_s": k * req / dt},
        attempted=k, failed=0,
        counters={"requests": k, "samples": k * req, "window_s": dt,
                  "batch": req, "training": False, "server": stats},
        record={"kept": kept, "pool": pool, "req": req},
        programs=[st["compiled"]])


def _compared(record):
    req, pool = record["req"], record["pool"]
    xs = np.concatenate([pool[off:off + req] for off, _ in record["kept"]])
    ys = np.concatenate([y for _, y in record["kept"]])
    return xs, ys


def check(run, record):
    xs, ys = _compared(record)
    return compare.readings(run, xs, ys)


def control(run, record, what):
    return compare.control_readings(run, _compared(record)[0], what)
