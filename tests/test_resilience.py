"""Resilience suite (ISSUE 9, docs/DESIGN.md §9): the deterministic
fault-injection harness, the guarded serving runtime's failure matrix
(degrade / failover / quarantine / reload-rollback / shed / deadline),
the hardened trainer (NaN budget, watchdog restart, ckpt save retry),
and the satellite fixes (pipeline timeout semantics, checkpointer
integrity sweep, watchdog one-shot)."""
import dataclasses
import os
import tempfile
import threading
import time

import jax
import numpy as np
import pytest

from repro.checkpoint import Checkpointer
from repro.configs import get_config
from repro.core import fno as fno_mod
from repro.data.pipeline import PrefetchPipeline
from repro.distributed import faults as flt
from repro.distributed.fault_tolerance import StragglerMonitor, Watchdog
from repro.train import serve_runtime as srt

PARITY_TOL = 2e-4


# ---------------------------------------------------------------------------
# FaultPlan: explicit, deterministic, fire-once
# ---------------------------------------------------------------------------
def test_fault_plan_take_fires_each_fault_once():
    plan = flt.FaultPlan([flt.Fault("kernel", at=3),
                          flt.Fault("nan", at=3)])
    got = plan.take("serve", 3, kind="kernel")
    assert [f.kind for f in got] == ["kernel"]
    assert plan.take("serve", 3, kind="kernel") == []  # fired = gone
    assert [f.kind for f in plan.pending()] == ["nan"]
    assert plan.take("train", 3, kind="nan") == []  # scope filter
    assert [f.kind for f in plan.take("serve", 3, kind="nan")] == ["nan"]
    assert plan.pending() == []


def test_fault_plan_replica_narrowing_and_count():
    plan = flt.FaultPlan([flt.Fault("kill", at=0, replica=1),
                          flt.Fault("kernel", at=0)])
    # A replica-pinned fault does not fire on a different replica...
    assert plan.take("serve", 0, kind="kill", replica=0) == []
    # ...but a replica-agnostic fault fires on whichever replica serves.
    assert len(plan.take("serve", 0, kind="kernel", replica=0)) == 1
    assert len(plan.take("serve", 0, kind="kill", replica=1)) == 1
    assert plan.count(kinds=("kill",)) == 1  # planned, not remaining
    assert plan.count() == 2


def test_fault_rejects_unknown_kind_and_scope():
    with pytest.raises(AssertionError):
        flt.Fault("meteor", at=0)
    with pytest.raises(AssertionError):
        flt.Fault("nan", at=0, scope="orbit")


def test_corrupt_checkpoint_defeats_verify_not_load():
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        ck.save(1, {"w": np.arange(6.0)})
        assert ck.verify(1)
        key = flt.corrupt_checkpoint(d, 1)
        assert key == "w"
        assert not ck.verify(1)  # checksum catches the flipped payload
        with pytest.raises(IOError):
            ck.restore(1, {"w": np.zeros(6)})


# ---------------------------------------------------------------------------
# satellite: PrefetchPipeline timeout semantics + terminal producer death
# ---------------------------------------------------------------------------
def test_pipeline_zero_timeout_is_a_timeout():
    # A slow producer + timeout=0 must poll (zero-second timeout), count
    # the misses as skips, and still return the batch once it lands —
    # the old code treated 0 as falsy "no timeout" and blocked.
    def slow(i):
        time.sleep(0.05)
        return {"x": i}

    pipe = PrefetchPipeline(slow, depth=1)
    try:
        idx, batch = pipe.get(timeout=0)
        assert batch == {"x": idx}
        # the 50ms producer latency showed up as Empty polls -> skips
        assert pipe.skipped >= 1
    finally:
        pipe.stop()


def test_pipeline_dead_producer_is_terminal():
    def dies(i):
        if i >= 2:
            raise ValueError("disk ate the shard")
        return {"x": i}

    pipe = PrefetchPipeline(dies, depth=1)
    try:
        assert pipe.get(timeout=1.0)[0] == 0
        assert pipe.get(timeout=1.0)[0] == 1
        with pytest.raises(RuntimeError, match="failed at index 2"):
            pipe.get(timeout=1.0)
        # Death is terminal: every later get raises IMMEDIATELY (the old
        # code spun on the empty queue counting skips forever).
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="failed at index 2"):
            pipe.get(timeout=None)  # would hang forever pre-fix
        assert time.monotonic() - t0 < 0.5
    finally:
        pipe.stop()


# ---------------------------------------------------------------------------
# satellite: Checkpointer integrity — stale tmp sweep + latest_valid_step
# ---------------------------------------------------------------------------
def test_checkpointer_sweeps_stale_tmp_dirs():
    with tempfile.TemporaryDirectory() as d:
        stale = os.path.join(d, ".tmp_step_7")
        os.makedirs(stale)
        with open(os.path.join(stale, "arrays.npz"), "wb") as f:
            f.write(b"half-written garbage")
        Checkpointer(d)  # init sweeps crash leftovers
        assert not os.path.exists(stale)


def test_latest_valid_step_skips_corrupt_steps():
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        ck.save(1, {"w": np.ones(3)})
        ck.save(2, {"w": np.full(3, 2.0)})
        assert ck.latest_valid_step() == 2
        flt.corrupt_checkpoint(d, 2)
        assert ck.latest_step() == 2      # newest on disk...
        assert ck.latest_valid_step() == 1  # ...newest that verifies
        flt.corrupt_checkpoint(d, 1)
        assert ck.latest_valid_step() is None


# ---------------------------------------------------------------------------
# satellite: watchdog one-shot + straggler reset
# ---------------------------------------------------------------------------
def test_watchdog_fires_once_per_stall():
    fired = []
    wd = Watchdog(0.1, lambda: fired.append(time.monotonic()))
    try:
        time.sleep(0.6)  # one long stall, several checker periods
        assert len(fired) == 1, (
            f"one stall must fire exactly once, got {len(fired)}")
        wd.beat()  # re-arm
        time.sleep(0.4)
        assert len(fired) == 2
    finally:
        wd.stop()


def test_watchdog_beat_prevents_fire():
    fired = []
    wd = Watchdog(0.3, lambda: fired.append(1))
    try:
        for _ in range(6):
            time.sleep(0.05)
            wd.beat()
        assert fired == []
    finally:
        wd.stop()


def test_watchdog_callback_runs_outside_lock():
    # A callback that beats (like a self-restarting trainer might) must
    # not deadlock against the checker's lock.
    wd = None
    done = threading.Event()

    def cb():
        wd.beat()
        done.set()

    wd = Watchdog(0.1, cb)
    try:
        assert done.wait(2.0), "callback deadlocked on the watchdog lock"
    finally:
        wd.stop()


def test_straggler_monitor_reset():
    m = StragglerMonitor(ratio=2.0, decay=0.5)
    for s in range(5):
        m.record(s, 0.1)
    assert m.record(5, 0.5) is True
    m.reset()
    assert m.ema is None and m.flagged == []
    # Post-reset, a slow first step is baseline, not a straggler.
    assert m.record(6, 0.5) is False


# ---------------------------------------------------------------------------
# ResilientServer failure matrix (reduced fno2d, pallas interpret on CPU)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def serve_setup():
    cfg = dataclasses.replace(get_config("fno2d", reduced=True),
                              path="pallas", fuse_block=True)
    key = jax.random.PRNGKey(0)
    params = fno_mod.init_fno(key, cfg)
    x = jax.random.normal(jax.random.fold_in(key, 1),
                          (2, cfg.in_channels) + tuple(cfg.spatial))
    oracle = np.asarray(fno_mod.apply_fno(params, cfg, x, path="xla"))
    return cfg, params, x, oracle


def _server(serve_setup, plan=None, **kw):
    cfg, params, _, _ = serve_setup
    kw.setdefault("replicas", 2)
    kw.setdefault("max_batch", 2)
    kw.setdefault("backoff_base_s", 1e-3)
    return srt.ResilientServer(cfg, params, fault_plan=plan, **kw)


def test_kernel_fault_degrades_to_xla_with_parity(serve_setup):
    _, _, x, oracle = serve_setup
    plan = flt.FaultPlan([flt.Fault("kernel", at=0)])
    rs = _server(serve_setup, plan)
    y = rs(x)
    assert np.isfinite(y).all()
    assert float(np.max(np.abs(y - oracle))) <= PARITY_TOL
    assert rs.stats["degraded"] == 1 and rs.stats["quarantined"] == 1
    # drain's health sweep gave the quarantined replica its canary back
    assert rs.stats["reinstated"] == 1
    assert rs.pool.states() == {"healthy": 2, "quarantined": 0, "dead": 0}


def test_nan_output_quarantines_and_reserves(serve_setup):
    _, _, x, oracle = serve_setup
    plan = flt.FaultPlan([flt.Fault("nan", at=0)])
    rs = _server(serve_setup, plan)
    y = rs(x)  # the poisoned reply is caught, re-served on XLA
    assert np.isfinite(y).all()
    assert float(np.max(np.abs(y - oracle))) <= PARITY_TOL
    assert rs.stats["degraded"] == 1
    assert rs.stats["served"] == 1 and rs.stats["accepted"] == 1


def test_replica_kill_fails_over_with_zero_drops(serve_setup):
    _, _, x, _ = serve_setup
    plan = flt.FaultPlan([flt.Fault("kill", at=0)])
    rs = _server(serve_setup, plan)
    for _ in range(3):
        rs.submit(x)
    ys = rs.drain()
    assert len(ys) == 3 and all(np.isfinite(y).all() for y in ys)
    assert rs.stats["killed"] == 1 and rs.stats["failovers"] == 1
    assert rs.stats["retries"] == 1
    assert rs.stats["degraded"] == 0  # failover is not degradation
    assert rs.pool.states()["dead"] == 1  # kills are terminal


def test_all_replicas_dead_raises_no_healthy(serve_setup):
    _, _, x, _ = serve_setup
    # Pin one kill to each replica id: whichever replica the failover
    # retries onto dies too, exhausting the pool.
    plan = flt.FaultPlan([flt.Fault("kill", at=0, replica=0),
                          flt.Fault("kill", at=0, replica=1)])
    rs = _server(serve_setup, plan)
    with pytest.raises(srt.NoHealthyReplica):
        rs(x)
    assert rs.pool.states()["dead"] == 2
    assert rs.stats["killed"] == 2


def test_admission_overflow_sheds_explicitly(serve_setup):
    _, _, x, _ = serve_setup
    rs = _server(serve_setup, queue_limit=2)
    rs.submit(x)
    rs.submit(x)
    with pytest.raises(srt.RequestRejected):
        rs.submit(x)
    assert rs.stats["accepted"] == 2 and rs.stats["shed"] == 1
    ys = rs.drain()  # the admitted two still get answers
    assert len(ys) == 2 and all(np.isfinite(y).all() for y in ys)


def test_deadline_exceeded_on_injected_delay(serve_setup):
    _, _, x, _ = serve_setup
    plan = flt.FaultPlan([flt.Fault("delay", at=0, delay_s=0.3)])
    rs = _server(serve_setup, plan, deadline_s=0.05)
    with pytest.raises(srt.DeadlineExceeded):
        rs(x)
    assert rs.stats["deadline_exceeded"] == 1
    assert rs.stats["served"] == 0


def test_reload_rolls_back_on_corrupt_checkpoint(serve_setup):
    cfg, params, x, _ = serve_setup
    params2 = fno_mod.init_fno(jax.random.PRNGKey(7), cfg)
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        rs = _server(serve_setup, checkpointer=ck)
        before = rs(x)
        ck.save(1, params2)
        flt.corrupt_checkpoint(d, 1)
        assert rs.reload() is False  # latest_valid_step finds nothing
        assert rs.stats["rollbacks"] == 1 and rs.stats["reloads"] == 0
        after = rs(x)  # old params keep serving, bit-identical
        np.testing.assert_array_equal(before, after)


def test_reload_swaps_on_valid_checkpoint(serve_setup):
    cfg, params, x, _ = serve_setup
    params2 = fno_mod.init_fno(jax.random.PRNGKey(7), cfg)
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        rs = _server(serve_setup, checkpointer=ck)
        ck.save(1, params2)  # corrupt step...
        flt.corrupt_checkpoint(d, 1)
        ck.save(2, params2)  # ...shadowed by a newer valid one
        assert rs.reload() is True
        assert rs.stats["reloads"] == 1
        want = np.asarray(fno_mod.apply_fno(params2, cfg, x, path="xla"))
        assert float(np.max(np.abs(rs(x) - want))) <= PARITY_TOL


def test_standard_chaos_plan_end_to_end(serve_setup):
    # The CI gate's plan, compressed: kernel + nan + kill across the
    # first three requests — every accepted request answered finite,
    # degradations exactly the planned count.
    _, _, x, _ = serve_setup
    plan = flt.standard_chaos_plan()
    rs = _server(serve_setup, plan)
    for _ in range(4):
        rs.submit(x)
    ys = rs.drain()
    assert len(ys) == 4 and all(np.isfinite(y).all() for y in ys)
    assert rs.stats["degraded"] == plan.count(kinds=("kernel", "nan"))
    assert rs.stats["killed"] == 1
    # the corrupt_ckpt record is a driver fault, never consumed in-band
    assert [f.kind for f in plan.pending()] == ["corrupt_ckpt"]


@pytest.mark.parametrize("name", sorted(flt.canned_chaos_plans()))
def test_stat_keys_conserve_under_every_canned_plan(serve_setup, name):
    # ISSUE 10 satellite: whatever a canned chaos plan injects, the
    # counter ledger must balance — no request may vanish from the stats,
    # and the fault-class counters must match the plan EXACTLY (counts
    # are injected deterministically, so anything else is an accounting
    # bug, not noise).
    _, _, x, _ = serve_setup
    plan = flt.canned_chaos_plans()[name]
    planned_degrade = plan.count(kinds=("kernel", "nan"))
    planned_kill = plan.count(kinds=("kill",))
    rs = _server(serve_setup, plan)
    offered = 5
    for _ in range(offered):
        rs.submit(x)
    ys = rs.drain()
    s = rs.stats
    assert set(s) == set(srt.ResilientServer.STAT_KEYS)
    # admission ledger: every offered request is accepted (no shed here)
    # and every accepted request reached exactly one terminal outcome.
    assert s["accepted"] == offered and s["shed"] == 0
    assert s["served"] + s["deadline_exceeded"] == s["accepted"]
    assert len(ys) == offered and all(np.isfinite(y).all() for y in ys)
    # fault-class counters match the plan exactly.
    assert s["degraded"] == planned_degrade, (name, dict(s))
    assert s["killed"] == planned_kill, (name, dict(s))
    assert s["failovers"] == planned_kill  # every kill failed over
    # quarantine is a cycle: drain's health sweep reinstates whatever the
    # faults quarantined, so the pool ends with no quarantined replica
    # and the two counters agree.
    assert s["quarantined"] == s["reinstated"], (name, dict(s))
    assert rs.pool.states()["quarantined"] == 0
    assert rs.pool.states()["dead"] == planned_kill
    # nothing in these plans touches checkpoints in-band.
    assert s["reloads"] == 0 and s["rollbacks"] == 0


def test_stat_keys_conserve_under_forced_shed(serve_setup):
    # The shed path joins the same ledger: offered == accepted + shed,
    # with the exact shed count forced by the admission bound.
    _, _, x, _ = serve_setup
    rs = _server(serve_setup, queue_limit=3)
    offered, shed = 5, 2
    for i in range(offered):
        if i < 3:
            rs.submit(x)
        else:
            with pytest.raises(srt.RequestRejected):
                rs.submit(x)
    ys = rs.drain()
    s = rs.stats
    assert s["accepted"] + s["shed"] == offered
    assert s["shed"] == shed
    assert s["served"] == s["accepted"] == len(ys) == 3


# ---------------------------------------------------------------------------
# hardened trainer: NaN budget, ckpt save retry, watchdog restart
# ---------------------------------------------------------------------------
def _mk_trainer(d, steps=8, plan=None, **cfg_kw):
    from repro.data import pde
    from repro.optim import AdamW
    from repro.optim.schedule import constant
    from repro.train.train_step import make_train_step
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = get_config("fno1d", reduced=True)
    key = jax.random.PRNGKey(0)
    params = fno_mod.init_fno(key, cfg)
    opt = AdamW(lr=constant(1e-3))
    step = jax.jit(make_train_step(cfg, opt, fno_path="xla"))
    batch_fn = lambda i: pde.burgers_batch(0, i, 4, cfg.spatial[0])
    tc = TrainerConfig(total_steps=steps, ckpt_every=4, ckpt_dir=d,
                       log_every=2, ckpt_async=False, **cfg_kw)
    return Trainer(tc, step, batch_fn, params, opt_state=opt.init(params),
                   fault_plan=plan)


def test_trainer_skips_nan_steps_within_budget():
    from repro.train.trainer import NaNBudgetExceeded  # noqa: F401

    plan = flt.FaultPlan([flt.Fault("nan", at=2, scope="train")])
    with tempfile.TemporaryDirectory() as d:
        tr = _mk_trainer(d, steps=8, plan=plan, nan_skip_budget=2)
        before = jax.tree_util.tree_map(np.asarray, tr.params)
        out = tr.run()
        assert out["final_step"] == 8
        assert out["nan_skipped"] == 1
        # the poisoned update was DISCARDED: params kept evolving from
        # clean steps only (they must differ from init — training ran)
        leaves = jax.tree_util.tree_leaves(
            jax.tree_util.tree_map(np.asarray, tr.params))
        assert all(np.isfinite(l).all() for l in leaves)
        init_leaves = jax.tree_util.tree_leaves(before)
        assert any(not np.array_equal(a, b)
                   for a, b in zip(leaves, init_leaves))


def test_trainer_nan_budget_exceeded_raises_not_restarts():
    from repro.train.trainer import NaNBudgetExceeded

    plan = flt.FaultPlan([flt.Fault("nan", at=s, scope="train")
                          for s in (1, 2, 3)])
    with tempfile.TemporaryDirectory() as d:
        tr = _mk_trainer(d, steps=8, plan=plan, nan_skip_budget=2)
        # run_with_restarts must surface it, NOT restart (deterministic
        # data would replay the poison forever)
        with pytest.raises(NaNBudgetExceeded):
            tr.run_with_restarts()
        assert tr.restarts == 0
        assert tr.nan_skipped == 3


def test_trainer_ckpt_save_retries_on_injected_io_fault():
    plan = flt.FaultPlan([flt.Fault("ckpt_io", at=4, scope="train")])
    with tempfile.TemporaryDirectory() as d:
        tr = _mk_trainer(d, steps=8, plan=plan, ckpt_retries=2,
                         ckpt_backoff_s=0.01)
        out = tr.run()
        assert out["final_step"] == 8
        assert out["ckpt_save_retries"] == 1  # one fault, one retry
        assert tr.ckpt.latest_valid_step() == 8


def test_trainer_watchdog_timeout_triggers_restart():
    from repro.train.trainer import WatchdogTimeout  # noqa: F401

    plan = flt.FaultPlan([flt.Fault("delay", at=5, scope="train",
                                    delay_s=1.5)])
    with tempfile.TemporaryDirectory() as d:
        tr = _mk_trainer(d, steps=8, plan=plan, step_timeout_s=0.3)
        # Warm the jit cache first: compile time must not read as a stall
        # (in production step_timeout_s is sized well above compile).
        b = tr.batch_fn(0)
        jax.block_until_ready(
            tr.train_step(tr.params, tr.opt_state, b)[2]["loss"])
        out = tr.run_with_restarts()
        # the stalled step fired the watchdog -> WatchdogTimeout -> one
        # restart from the step-4 checkpoint -> run completes
        assert tr.restarts == 1
        assert out["final_step"] == 8
        assert tr.ckpt.latest_valid_step() == 8


def test_trainer_restores_through_corrupt_checkpoint():
    with tempfile.TemporaryDirectory() as d:
        tr = _mk_trainer(d, steps=8)
        tr._fail_at = {6: RuntimeError("node died")}
        # corrupt the step-4 checkpoint as soon as it lands: the restart
        # must skip it (latest_valid_step) and fall back to the newest
        # valid state — here from scratch — instead of crashing mid-restore
        orig_save = tr._save_ckpt

        def save_and_corrupt(step):
            orig_save(step)
            if step == 4:
                flt.corrupt_checkpoint(d, 4)

        tr._save_ckpt = save_and_corrupt
        out = tr.run_with_restarts()
        assert tr.restarts == 1
        assert out["final_step"] == 8


# ---------------------------------------------------------------------------
# DP-sharded resilient serving on the forced-8-device mesh
# ---------------------------------------------------------------------------
def test_resilient_server_on_dp_mesh(subproc):
    subproc("""
    import sys
    sys.path.insert(0, {src!r})
    import dataclasses
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.core import fno as fno_mod
    from repro.distributed import faults as flt
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_mesh
    from repro.train import serve_runtime as srt

    assert jax.device_count() == 8
    cfg = dataclasses.replace(get_config("fno2d", reduced=True),
                              path="pallas", fuse_block=True)
    mesh = make_mesh((4, 2), ("data", "model"))
    ctx = shd.make_context(cfg, mesh, kind="serve")
    params = fno_mod.init_fno(jax.random.PRNGKey(0), cfg)
    plan = flt.FaultPlan([flt.Fault("kernel", at=0),
                          flt.Fault("kill", at=1)])
    rs = srt.ResilientServer(cfg, params, ctx=ctx, replicas=2,
                             max_batch=4, fault_plan=plan,
                             backoff_base_s=1e-3)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (3, cfg.in_channels) + tuple(cfg.spatial))
    oracle = np.asarray(fno_mod.apply_fno(params, cfg, x, path="xla"))
    for _ in range(3):
        rs.submit(x)
    ys = rs.drain()
    assert len(ys) == 3
    for y in ys:
        assert np.isfinite(y).all()
        assert float(np.max(np.abs(y - oracle))) <= 2e-4
    assert rs.stats["degraded"] == 1 and rs.stats["killed"] == 1
    assert rs.stats["failovers"] == 1
    print("dp-mesh resilient serve OK:", rs.pool_report())
    """.format(src=os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")))
