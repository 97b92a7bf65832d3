"""Closed loop over Li et al.'s FNO-3D for Navier–Stokes: one client sends
a request of ``request_samples`` observed histories
(``inputs_ns3d``: ten vorticity frames and the x, y, t grids per sample),
waits until the 40 predicted frames are on the host, and sends the next.

Mix keys as ``closed_loop``, whose window this module runs. The server is
``FNOServer`` on the configuration's path; the answers are compared with
``reference_ns3d``, the plain forward pass of the published model.

The configuration file's architecture keys must all be ``FNOConfig``
fields: a program that lacks one would serve another model, so set-up
refuses it at once.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

import closed_loop
import compare
import inputs_ns3d
import ns3d_work
import reference_ns3d

# Keys of a configuration file that describe the run, not the model.
RUN_KEYS = ("source", "assumed", "variant", "matmul_precision",
            "param_dtype")


def fno_config(run):
    """The configuration file as the program's ``FNOConfig``; raises
    ``ValueError`` for an architecture key the program does not have."""
    from repro.configs.base import FNOConfig
    fields = {f.name for f in dataclasses.fields(FNOConfig)}
    missing = sorted(k for k in run.cfg if k not in fields
                     and k not in RUN_KEYS)
    if missing:
        raise ValueError(
            f"{run.spec.config_name}: this program's FNOConfig has no "
            f"{missing}; it cannot run this configuration")
    return run.fno_config()


def _inputs(run, n):
    cfg = run.cfg
    return inputs_ns3d.ns_inputs(run.rng(1), n, cfg["spatial"][0],
                                 cfg["spatial"][2])


def setup(run):
    from repro.core import fno, spectral
    from repro.kernels import engine
    from repro.train.serve_fno_step import FNOServer

    cfg, mix = fno_config(run), run.mix
    params = jax.jit(lambda k: fno.init_fno(k, cfg))(run.key())
    server = FNOServer(cfg, params, path=cfg.path,
                       variant=run.cfg.get("variant", "full"),
                       max_batch=mix["max_batch"])
    req = mix["request_samples"]
    pool = _inputs(run, mix["pool_samples"])
    np.asarray(server(pool[:req]))  # warm the one bucket the window uses
    server.stats = {k: 0 for k in server.stats}
    x = pool[:req]  # as the server sends it to the device
    x = x.reshape(x.shape[:2] + server.host_layout)
    program = jax.jit(server.step_fn).lower(params, {"x": x}).compile()
    # The matmuls one grid step of the forward block kernel runs, in the
    # kernel's layout (rank 3 runs as rank 2 over merged trailing axes).
    _, kmodes = spectral.kernel_layout(cfg.spatial, cfg.kept_modes)
    counts = engine.fwd_contractions(
        len(kmodes), cfg.weight_mode == "per_mode", has_wb=True,
        vpu=engine.vpu_cgemm(len(kmodes), kmodes))
    return {"server": server, "pool": pool, "compiled": program,
            "counters": {"fwd_contractions": list(counts),
                         "kept_modes": ns3d_work.kept_modes(run.cfg)}}


def window(run, st):
    """``closed_loop.window``, with the kernel's counts in the counters."""
    extra = st["counters"]
    win = closed_loop.window(run, st)
    win.counters.update(extra)
    return win


def check(run, record):
    xs, ys = closed_loop._compared(record)
    rs = reference_ns3d.reference_outputs(run.key(), run.cfg, xs,
                                          rows=run.mix.get("ref_rows", 8))
    return compare.gaps(ys, rs)


def control(run, record, what):
    """The comparison with the reference put in the program's place: at a
    lower precision (``high``, ``bf16``), or with every answer altered at
    one grid point (``altered``)."""
    xs = closed_loop._compared(record)[0]
    rows = run.mix.get("ref_rows", 8)
    out = lambda **kw: reference_ns3d.reference_outputs(  # noqa: E731
        run.key(), run.cfg, xs, rows=rows, **kw)
    rs = out()
    if what == "high":
        cs = out(precision="high")
    elif what == "bf16":
        cs = out(dtype=jnp.bfloat16, precision="default")
    elif what == "altered":
        cs = rs.copy()
        flat = cs.reshape(len(cs), -1)
        flat[:, 0] += 0.5 * np.max(np.abs(flat), axis=1)
    else:
        raise ValueError(f"unknown control {what!r}")
    return compare.gaps(cs, rs)
