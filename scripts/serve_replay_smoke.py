#!/usr/bin/env python
"""Continuous-batching replay smoke (ISSUE 10, docs/DESIGN.md §10).

Replays a seeded Poisson-ish arrival schedule through the async
continuous-batching tier (``train/serve_queue``) over the REAL fused
serving engine, on a virtual clock with a FIXED synthetic service model —
so every count below is machine-independent and asserted exactly:

  * determinism — two replays of the same schedule produce identical
    reports (stats, latencies, queue depths);
  * exact admission/coalescing counts — shed, batches, coalesced,
    deadline_exceeded are pinned to the schedule's known-good values;
  * conservation — offered == accepted + shed and
    accepted == completed + deadline_exceeded + failed;
  * the deadline contract — no request is served past its deadline
    (every completed request's t_complete <= its deadline), so completed
    p99 <= the deadline by construction;
  * the rollout trace contract — a K-step device-resident rollout traces
    exactly one forward's pallas_calls for K in {1, 4}
    (``analysis.jaxpr_lint.lint_rollout``);
  * every served output is finite (the engine really ran).

Wired into scripts/check.sh and a named CI step. Pure CPU, seconds.

Usage: PYTHONPATH=src python scripts/serve_replay_smoke.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import dataclasses  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import fno as fno_mod  # noqa: E402
from repro.train import serve_fno_step as sfs  # noqa: E402
from repro.train import serve_queue as sq  # noqa: E402

# The schedule and its exact expected outcome. The counts are a pure
# function of (SEED, REQUESTS, RATE_HZ, MAX_N, DEADLINE_S, QUEUE_LIMIT,
# COALESCE_S, the synthetic service model, and the bucket ladder) — if a
# change to the batch-formation policy moves them, that is a behavior
# change to review, not noise to re-bake silently.
SEED = 0
REQUESTS = 24
RATE_HZ = 600.0
MAX_N = 4
ROLLOUT_STEPS = 2
DEADLINE_S = 0.015
QUEUE_LIMIT = 6
COALESCE_S = 0.004
SERVICE_MODEL = lambda bucket, steps: 1e-3 * steps + 2.5e-4 * bucket  # noqa: E731

# This schedule exercises EVERY admission outcome: sheds (bounded queue),
# a deadline miss (failed with DeadlineExceeded, never served late), and
# real coalescing (10 requests ride along in another request's batch).
EXPECTED = {"offered": 24, "accepted": 20, "shed": 4, "completed": 19,
            "deadline_exceeded": 1, "failed": 0, "batches": 9,
            "coalesced": 10}


def run_once(server):
    cbs = sq.ContinuousBatchingServer(
        server, queue_limit=QUEUE_LIMIT, coalesce_s=COALESCE_S,
        clock=sq.VirtualClock(), service_model=SERVICE_MODEL)
    sched = sq.poisson_schedule(SEED, REQUESTS, rate_hz=RATE_HZ,
                                max_n=MAX_N, rollout_steps=ROLLOUT_STEPS,
                                deadline_s=DEADLINE_S)
    cfg = server.cfg
    key = jax.random.PRNGKey(SEED)

    def input_fn(a, i):
        return np.asarray(jax.random.normal(
            jax.random.fold_in(key, i),
            (a.n, cfg.in_channels) + tuple(cfg.spatial)))

    return cbs, cbs.replay(sched, input_fn)


def main() -> int:
    cfg = dataclasses.replace(get_config("fno2d", reduced=True),
                              path="pallas", fuse_block=True)
    params = fno_mod.init_fno(jax.random.PRNGKey(0), cfg)
    server = sfs.FNOServer(cfg, params, max_batch=MAX_N)

    cbs, rep = run_once(server)
    s = rep["stats"]
    print(f"replay: stats={s}")
    print(f"        latency p50={rep['latency']['p50']*1e3:.2f}ms "
          f"p99={rep['latency']['p99']*1e3:.2f}ms  "
          f"queue p50={rep['queue_depth']['p50']:.1f} "
          f"p99={rep['queue_depth']['p99']:.1f} "
          f"max={rep['queue_depth']['max']:.0f}")

    # Exact counts (machine-independent: virtual clock + fixed model).
    for k, v in EXPECTED.items():
        assert s[k] == v, f"{k}: got {s[k]}, expected exactly {v}"
    # Conservation.
    assert s["offered"] == s["accepted"] + s["shed"]
    assert s["accepted"] == (s["completed"] + s["deadline_exceeded"]
                             + s["failed"])
    assert cbs.queue_depth() == 0, "drained replay left queued requests"
    # Deadline contract: nothing served late; completed p99 <= deadline.
    for r in cbs.requests.values():
        if r.status == "done" and r.deadline_t is not None:
            assert r.t_complete <= r.deadline_t + 1e-12, \
                f"request {r.idx} served {r.t_complete - r.deadline_t:.4f}s " \
                f"past its deadline without DeadlineExceeded"
        if r.status == "done":
            assert np.isfinite(np.asarray(r.y)).all(), \
                f"request {r.idx}: non-finite served output"
    assert rep["latency"]["p99"] <= DEADLINE_S, \
        f"completed p99 {rep['latency']['p99']:.4f}s > deadline {DEADLINE_S}s"
    print("exact counts, conservation, deadline contract, finiteness: OK")

    # Determinism: the identical schedule replays to the identical report.
    _, rep2 = run_once(server)
    assert rep2 == rep, "replay is not deterministic"
    print("replay determinism: OK")

    # Rollout trace contract: K-step rollout == one forward's pallas_calls
    # for K in {1, 4} (the acceptance-criteria pin), clean casts.
    from repro.analysis import format_findings
    from repro.analysis.jaxpr_lint import lint_rollout
    findings = lint_rollout(archs=("fno2d",), dtypes=("f32",), ks=(1, 4))
    assert not findings, format_findings(findings)
    print(f"rollout trace contract: {cfg.num_layers} blocks + 2 ends "
          f"pallas_calls for K in (1, 4): OK")

    # Rollout parity through the tier: a K-step continuous-batched answer
    # matches the engine's own device-resident rollout bit-for-bit (the
    # tier only batches — it never changes math).
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1),
                                     (2, cfg.in_channels)
                                     + tuple(cfg.spatial)))
    direct = np.asarray(server(jnp.asarray(x),
                               rollout_steps=ROLLOUT_STEPS))
    cbs3 = sq.ContinuousBatchingServer(server, queue_limit=4)
    idx = cbs3.submit(x, rollout_steps=ROLLOUT_STEPS)
    cbs3.drain()
    got = np.asarray(cbs3.result(idx).y)
    assert np.array_equal(got, direct), "tier changed the rollout answer"
    print("tier-vs-engine rollout parity: OK")
    print("serve_replay_smoke: all gates passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
