"""The comparison of served answers with the plain reference.

For each compared sample s, with y the served field and r the
reference's: ``out_gap`` is the largest over samples of
max|y_s - r_s| / max|r_s|, and ``out_l2_gap`` the largest of
||y_s - r_s|| / ||r_s||. The reference runs once the window has closed,
in blocks of ``rows`` samples, from weights it makes from the seed.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

import reference as ref


def reference_outputs(key, cfg, xs: np.ndarray, *, dtype=jnp.float32,
                      precision: str = "highest",
                      rows: int = 16) -> np.ndarray:
    params = ref.init_params(key, cfg)
    fwd = jax.jit(lambda p, x: ref.forward(p, cfg, x, dtype, precision)
                  .astype(jnp.float32))
    return np.concatenate([np.asarray(fwd(params, jnp.asarray(xs[s:s + rows])))
                           for s in range(0, len(xs), rows)])


def gaps(ys: np.ndarray, rs: np.ndarray) -> Dict[str, float]:
    ys = np.asarray(ys, np.float64).reshape(len(ys), -1)
    rs = np.asarray(rs, np.float64).reshape(len(rs), -1)
    d = ys - rs
    peak = np.maximum(np.max(np.abs(rs), axis=1), 1e-30)
    l2 = np.maximum(np.linalg.norm(rs, axis=1), 1e-30)
    return {"out_gap": float(np.max(np.max(np.abs(d), axis=1) / peak)),
            "out_l2_gap": float(np.max(np.linalg.norm(d, axis=1) / l2))}


def readings(run, xs: np.ndarray, ys: np.ndarray) -> Dict[str, float]:
    """The numbers compared for a serving cell."""
    rs = reference_outputs(run.key(), run.cfg, xs,
                           rows=run.mix.get("ref_rows", 16))
    return gaps(ys, rs)


def control_readings(run, xs: np.ndarray, what: str) -> Dict[str, float]:
    """The same numbers with the reference put in the program's place:
    at a lower precision (``high``: three bf16 passes; ``bf16``: bfloat16
    throughout), or with every answer altered at one grid point
    (``altered``)."""
    rows = run.mix.get("ref_rows", 16)
    rs = reference_outputs(run.key(), run.cfg, xs, rows=rows)
    if what == "high":
        cs = reference_outputs(run.key(), run.cfg, xs, precision="high",
                               rows=rows)
    elif what == "bf16":
        cs = reference_outputs(run.key(), run.cfg, xs, dtype=jnp.bfloat16,
                               precision="default", rows=rows)
    elif what == "altered":
        cs = rs.copy()
        flat = cs.reshape(len(cs), -1)
        flat[:, 0] += 0.5 * np.max(np.abs(flat), axis=1)
    else:
        raise ValueError(f"unknown control {what!r}")
    return gaps(cs, rs)
