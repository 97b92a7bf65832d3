"""Serving-primitive and continuous-batching property suite (ISSUE 10).

Hypothesis properties over the bucket ladder (``serve_fno_step``:
smallest-fit, padding masks, oversize chunk-and-tail reassembly) and the
coalescing queue (``serve_queue``: deadline contract, FIFO within a
bucket, conservation), plus deterministic unit tests of the tier over a
fake executor and one live pass over the real fused engine."""
import dataclasses

import numpy as np
import pytest

from repro.train import serve_queue as sq
from repro.train.serve_fno_step import (bucket_sizes, pad_to_bucket,
                                        pick_bucket)
from repro.train.serve_runtime import RequestRejected

# hypothesis is optional (requirements-dev.txt installs it in CI; the
# runtime image may lack it). Unlike test_property.py, only the @given
# properties skip without it — the deterministic queue tests in this
# module still run everywhere.
try:
    import hypothesis
    import hypothesis.strategies as st
    from hypothesis import given

    hypothesis.settings.register_profile(
        "ci", deadline=None, max_examples=20,
        suppress_health_check=list(hypothesis.HealthCheck))
    hypothesis.settings.load_profile("ci")
except ImportError:  # pragma: no cover - exercised on hypothesis-less images
    hypothesis = None

    class st:  # minimal stand-ins so the decorators below still parse
        @staticmethod
        def _stub(*a, **k):
            return None
        integers = floats = sampled_from = tuples = lists = _stub

    def given(**kw):
        return pytest.mark.skip(reason="hypothesis not installed")


# ---------------------------------------------------------------------------
# bucket-ladder primitives
# ---------------------------------------------------------------------------
@given(quantum=st.integers(1, 8), max_batch=st.integers(1, 64))
def test_bucket_ladder_geometric_and_quantized(quantum, max_batch):
    buckets = bucket_sizes(max_batch, quantum=quantum)
    assert buckets[0] == quantum
    assert buckets[-1] >= max_batch
    for a, b in zip(buckets, buckets[1:]):
        assert b == 2 * a  # geometric: one jit entry per doubling
    assert all(b % quantum == 0 for b in buckets)
    # minimal: dropping the top bucket would no longer cover max_batch
    if len(buckets) > 1:
        assert buckets[-2] < max_batch


@given(quantum=st.integers(1, 8), max_batch=st.integers(1, 64),
       n=st.integers(1, 96))
def test_pick_bucket_is_smallest_fit(quantum, max_batch, n):
    buckets = bucket_sizes(max_batch, quantum=quantum)
    b = pick_bucket(n, buckets)
    assert b in buckets
    if n <= buckets[-1]:
        assert b >= n
        smaller = [x for x in buckets if x < b]
        assert all(x < n for x in smaller)  # nothing smaller would fit
    else:
        assert b == buckets[-1]  # oversize: caller chunks at the top


@given(n=st.integers(1, 16), extra=st.integers(0, 16),
       seed=st.integers(0, 2 ** 16))
def test_pad_to_bucket_masks_and_preserves(n, extra, seed):
    bucket = n + extra
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 3, 4)).astype(np.float32)
    xp, m = pad_to_bucket(x, bucket)
    assert m == n and xp.shape[0] == bucket
    assert np.array_equal(np.asarray(xp)[:n], x)  # payload bit-exact
    assert not np.asarray(xp)[n:].any()  # padding is zeros


@given(quantum=st.integers(1, 4), max_batch=st.integers(1, 16),
       n=st.integers(1, 80), seed=st.integers(0, 2 ** 16))
def test_oversize_chunk_and_tail_reassembles_bit_exactly(quantum, max_batch,
                                                         n, seed):
    # Mirror FNOServer.__call__'s oversize loop with an identity step:
    # chunk at the largest bucket, pad each chunk to its own bucket,
    # unpad, concatenate — the round trip must be bit-exact.
    buckets = bucket_sizes(max_batch, quantum=quantum)
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 2, 3)).astype(np.float32)
    top = buckets[-1]
    ys = []
    for s in range(0, n, top):
        chunk = x[s:s + top]
        b = pick_bucket(chunk.shape[0], buckets)
        xp, m = pad_to_bucket(chunk, b)
        assert xp.shape[0] == b and m == chunk.shape[0]
        ys.append(np.asarray(xp)[:m])
    out = np.concatenate(ys, 0)
    assert out.shape == x.shape
    assert np.array_equal(out, x)


# ---------------------------------------------------------------------------
# the coalescing queue (fake executor — no jax)
# ---------------------------------------------------------------------------
class FakeEngine:
    """Identity executor that records every dispatched batch."""

    def __init__(self, buckets=(2, 4, 8), fail=False):
        self.buckets = buckets
        self.calls = []
        self.fail = fail

    def __call__(self, x, rollout_steps=1):
        self.calls.append((int(x.shape[0]), int(rollout_steps)))
        if self.fail:
            raise RuntimeError("injected engine failure")
        return np.asarray(x)


def _payload(a, i):
    # Each request's samples carry its schedule index, so output routing
    # is checkable per request.
    return np.full((a.n, 1), float(i), np.float32)


schedules = st.lists(
    st.tuples(st.floats(1e-4, 0.02),  # inter-arrival gap
              st.integers(1, 5),  # samples
              st.sampled_from([1, 2]),  # rollout depth
              st.sampled_from([None, 0.01, 0.05])),  # deadline
    min_size=1, max_size=30)


def _mk_schedule(raw):
    t, out = 0.0, []
    for gap, n, steps, dl in raw:
        t += gap
        out.append(sq.Arrival(t, n, steps, dl))
    return out


def _replay(raw, queue_limit=4, coalesce_s=0.004):
    sched = _mk_schedule(raw)
    eng = FakeEngine()
    cbs = sq.ContinuousBatchingServer(
        eng, buckets=eng.buckets, queue_limit=queue_limit,
        coalesce_s=coalesce_s, clock=sq.VirtualClock(),
        service_model=lambda bucket, steps: 1e-3 * steps + 2e-4 * bucket)
    rep = cbs.replay(sched, _payload)
    return cbs, eng, rep, sched


@given(raw=schedules)
def test_queue_conservation(raw):
    cbs, _, rep, _ = _replay(raw)
    s = rep["stats"]
    assert s["offered"] == len(raw)
    assert s["offered"] == s["accepted"] + s["shed"]
    # replay drains fully: every accepted request reached a terminal state
    assert s["accepted"] == (s["completed"] + s["deadline_exceeded"]
                             + s["failed"])
    assert cbs.queue_depth() == 0
    # per-request statuses agree with the counters
    by_status = {}
    for r in cbs.requests.values():
        by_status[r.status] = by_status.get(r.status, 0) + 1
    assert by_status.get("done", 0) == s["completed"]
    assert by_status.get("deadline", 0) == s["deadline_exceeded"]
    assert by_status.get("failed", 0) == s["failed"]


@given(raw=schedules)
def test_no_request_served_past_deadline(raw):
    cbs, _, _, _ = _replay(raw)
    for r in cbs.requests.values():
        if r.status == "done":
            assert r.t_complete >= r.t_dispatch >= r.t_enqueue
            if r.deadline_t is not None:
                # served => on time; late == DeadlineExceeded, never both
                assert r.t_complete <= r.deadline_t + 1e-12
        if r.status == "deadline":
            assert r.y is None and "deadline" in r.error


@given(raw=schedules)
def test_fifo_within_bucket_and_payload_routing(raw):
    cbs, eng, _, _ = _replay(raw)
    # Every dispatched batch is uniform in rollout depth and within the
    # ladder's largest bucket unless a single oversize request rode alone.
    done = [r for r in cbs.requests.values() if r.status == "done"]
    for n, steps in eng.calls:
        assert steps in (1, 2)
    batches = {}
    for r in done:
        batches.setdefault(r.t_dispatch, []).append(r)
    for members in batches.values():
        sizes = [m.n for m in members]
        assert len({m.rollout_steps for m in members}) == 1
        assert sum(sizes) <= eng.buckets[-1] or len(members) == 1
        # FIFO within the bucket: coalesced members in admission order
        idxs = [m.idx for m in members]
        assert idxs == sorted(idxs)
    # payload routing: each request got back exactly its own samples
    for r in done:
        assert r.y.shape[0] == r.n
        assert (np.asarray(r.y) == np.asarray(r.y).flat[0]).all()
    # identity engine: request i's payload is the schedule index it was
    # admitted with — cross-request mixups would show here
    accepted = sorted(done, key=lambda r: r.idx)
    vals = [float(np.asarray(r.y).flat[0]) for r in accepted]
    assert vals == sorted(vals)


def test_submit_sheds_at_queue_limit_without_enqueue():
    eng = FakeEngine()
    cbs = sq.ContinuousBatchingServer(eng, buckets=eng.buckets,
                                      queue_limit=2,
                                      clock=sq.VirtualClock())
    x = np.zeros((1, 1), np.float32)
    assert cbs.submit(x) == 0 and cbs.submit(x) == 1
    with pytest.raises(RequestRejected):
        cbs.submit(x)
    assert cbs.stats["shed"] == 1 and cbs.stats["offered"] == 3
    assert cbs.queue_depth() == 2  # the shed request never enqueued
    handled = cbs.drain()
    assert len(handled) == 2
    assert cbs.stats["completed"] == 2


def test_mixed_rollout_depths_never_share_a_batch():
    eng = FakeEngine()
    cbs = sq.ContinuousBatchingServer(eng, buckets=eng.buckets,
                                      queue_limit=8,
                                      clock=sq.VirtualClock())
    x = np.zeros((1, 1), np.float32)
    for steps in (1, 1, 2, 2, 1):
        cbs.submit(x, rollout_steps=steps)
    cbs.drain()
    # FIFO forces the depth runs to dispatch as [1,1], [2,2], [1]
    assert eng.calls == [(2, 1), (2, 2), (1, 1)]
    assert cbs.stats["batches"] == 3 and cbs.stats["coalesced"] == 2


def test_engine_failure_marks_batch_failed_not_lost():
    eng = FakeEngine(fail=True)
    cbs = sq.ContinuousBatchingServer(eng, buckets=eng.buckets,
                                      queue_limit=4,
                                      clock=sq.VirtualClock())
    x = np.zeros((1, 1), np.float32)
    i0, i1 = cbs.submit(x), cbs.submit(x)
    handled = cbs.drain()
    assert {r.status for r in handled} == {"failed"}
    assert cbs.stats["failed"] == 2 and cbs.stats["completed"] == 0
    assert "injected engine failure" in cbs.result(i0).error
    assert cbs.result(i1).t_complete is not None  # terminal, accounted
    # conservation still holds with every request in a terminal state
    s = cbs.stats
    assert s["accepted"] == s["completed"] + s["deadline_exceeded"] + s["failed"]


def test_replay_requires_virtual_clock_and_model():
    eng = FakeEngine()
    cbs = sq.ContinuousBatchingServer(eng, buckets=eng.buckets)
    with pytest.raises(ValueError, match="VirtualClock"):
        cbs.replay([sq.Arrival(0.0, 1)], _payload)
    cbs = sq.ContinuousBatchingServer(eng, buckets=eng.buckets,
                                      clock=sq.VirtualClock())
    with pytest.raises(ValueError, match="service_model"):
        cbs.replay([sq.Arrival(0.0, 1)], _payload)


def test_poisson_schedule_is_seed_deterministic():
    a = sq.poisson_schedule(3, 16, rate_hz=100.0, max_n=4,
                            deadline_s=0.1)
    b = sq.poisson_schedule(3, 16, rate_hz=100.0, max_n=4,
                            deadline_s=0.1)
    assert a == b
    c = sq.poisson_schedule(4, 16, rate_hz=100.0, max_n=4, deadline_s=0.1)
    assert a != c
    assert all(x.t < y.t for x, y in zip(a, a[1:]))  # arrivals ordered


# ---------------------------------------------------------------------------
# the tier over the real fused engine (one small live pass)
# ---------------------------------------------------------------------------
def test_tier_over_real_server_matches_direct_calls():
    jax = pytest.importorskip("jax")
    from repro.configs import get_config
    from repro.core import fno as fno_mod
    from repro.train import serve_fno_step as sfs

    cfg = dataclasses.replace(get_config("fno2d", reduced=True),
                              path="pallas", fuse_block=True)
    params = fno_mod.init_fno(jax.random.PRNGKey(0), cfg)
    server = sfs.FNOServer(cfg, params, max_batch=2)
    cbs = sq.ContinuousBatchingServer(server, queue_limit=4)
    assert cbs.buckets == server.buckets  # ladder discovered, not guessed
    key = jax.random.PRNGKey(1)
    xs = [np.asarray(jax.random.normal(
        jax.random.fold_in(key, i),
        (1 + i % 2, cfg.in_channels) + tuple(cfg.spatial)))
        for i in range(3)]
    idxs = [cbs.submit(x, rollout_steps=2) for x in xs]
    cbs.drain()
    # The tier batches but never changes math: each answer equals the
    # engine's own device-resident rollout on that request alone.
    for x, i in zip(xs, idxs):
        direct = np.asarray(server(np.asarray(x), rollout_steps=2))
        got = np.asarray(cbs.result(i).y)
        np.testing.assert_allclose(got, direct, rtol=0, atol=1e-6)
        assert np.isfinite(got).all()


def test_server_phases_are_host_spans_in_a_profiler_trace(tmp_path,
                                                          monkeypatch):
    """FNOServer records its phases as host spans on the profiler's clock:
    per chunk a pad and a step, then one slice that joins the chunks."""
    jax = pytest.importorskip("jax")
    from repro.configs import get_config
    from repro.core import fno as fno_mod
    from repro.train import serve_fno_step as sfs

    cfg = dataclasses.replace(get_config("fno2d", reduced=True), path="xla")
    params = fno_mod.init_fno(jax.random.PRNGKey(0), cfg)
    server = sfs.FNOServer(cfg, params, max_batch=16)
    top = server.buckets[-1]
    x = np.zeros((top + 1, cfg.in_channels) + tuple(cfg.spatial),
                 np.float32)
    want = np.asarray(server(x))  # compiles both buckets outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        got = np.asarray(server(x))
    finally:
        jax.profiler.stop_trace()
    np.testing.assert_array_equal(got, want)
    path = next(tmp_path.rglob("*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    spans = sorted((e.start_ns, e.name) for plane in data.planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith("fno_server."))
    # One sample past the top bucket: two chunks.
    assert [name for _, name in spans] == [
        "fno_server.pad", "fno_server.step", "fno_server.pad",
        "fno_server.step", "fno_server.slice"]

    # A host request of one top bucket, above the pipeline's chunk bytes:
    # one pad/step pair per transfer-sized chunk (the smallest bucket
    # here, two chunks), then one slice.
    x = x[:top]
    monkeypatch.setattr(sfs, "_PIPELINE_CHUNK_BYTES", x[0].nbytes)
    chunks = top // server.buckets[0]
    want = np.asarray(server(x))
    trace_dir = tmp_path / "pipelined"
    jax.profiler.start_trace(str(trace_dir))
    try:
        got = np.asarray(server(x))
    finally:
        jax.profiler.stop_trace()
    np.testing.assert_array_equal(got, want)
    path = next(trace_dir.rglob("*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    spans = sorted((e.start_ns, e.name) for plane in data.planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith("fno_server."))
    assert [name for _, name in spans] == (
        ["fno_server.pad", "fno_server.step"] * chunks
        + ["fno_server.slice"])


# ---------------------------------------------------------------------------
# a large host request pipelined in transfer-sized chunks
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def xla_server():
    """The reduced fno2d on the XLA path behind a (8, 16, 32) bucket ladder
    (its tuned batch block is 8), and a full-bucket host request of
    distinct samples."""
    jax = pytest.importorskip("jax")
    from repro.configs import get_config
    from repro.core import fno as fno_mod
    from repro.train import serve_fno_step as sfs

    cfg = dataclasses.replace(get_config("fno2d", reduced=True), path="xla")
    params = fno_mod.init_fno(jax.random.PRNGKey(0), cfg)
    server = sfs.FNOServer(cfg, params, max_batch=32)
    assert server.buckets == (8, 16, 32)
    x = np.random.default_rng(0).normal(
        size=(32, cfg.in_channels) + tuple(cfg.spatial)).astype(np.float32)
    return server, x


def _pipelined(monkeypatch, server, x, samples):
    """Set the chunk bytes to ``samples`` samples of ``x``; returns the
    server's chunk counter before the call that follows."""
    from repro.train import serve_fno_step as sfs
    monkeypatch.setattr(sfs, "_PIPELINE_CHUNK_BYTES", samples * x[0].nbytes)
    return server.stats["chunks"]


@pytest.mark.parametrize("samples, chunks", [(1, 4), (8, 4), (9, 2),
                                             (32, 1), (33, 1)])
def test_large_host_request_splits_into_transfer_sized_chunks(
        xla_server, monkeypatch, samples, chunks):
    # The chunk is the smallest bucket carrying the chunk bytes (1 or 8
    # samples → bucket 8, 9 → bucket 16); a request at or under them
    # stays one chunk.
    server, x = xla_server
    before = _pipelined(monkeypatch, server, x, samples)
    server(x)
    assert server.stats["chunks"] - before == chunks


@pytest.mark.parametrize("n, chunks", [(32, 4), (20, 3)])
def test_pipelined_request_matches_the_unsplit_step(xla_server, monkeypatch,
                                                    n, chunks):
    # 20 samples: two chunks of 8 and a tail of 4 padded to bucket 8.
    server, x = xla_server
    x = x[:n]
    whole = np.asarray(server(x))  # one chunk at the default chunk bytes
    before = _pipelined(monkeypatch, server, x, 8)
    got = np.asarray(server(x))
    assert server.stats["chunks"] - before == chunks
    # Each sample's forward pass is independent of its batch.
    np.testing.assert_allclose(got, whole, rtol=0, atol=1e-6)


def test_device_request_is_not_split(xla_server, monkeypatch):
    jax = pytest.importorskip("jax")
    server, x = xla_server
    before = _pipelined(monkeypatch, server, x, 8)
    y = server(jax.device_put(x))
    assert server.stats["chunks"] - before == 1
    assert y.shape[0] == len(x)


def test_repeated_pipelined_request_compiles_nothing(xla_server,
                                                     monkeypatch):
    server, x = xla_server
    _pipelined(monkeypatch, server, x, 8)
    np.asarray(server(x))
    entries = server._step._cache_size()
    np.asarray(server(x))
    assert server._step._cache_size() == entries
