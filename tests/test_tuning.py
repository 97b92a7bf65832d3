"""Block-size autotuner tests (ISSUE 7): key schema, cache round-trip and
staleness lint, resolver precedence, VMEM feasibility of every runnable
cell under the committed cache, block-clamp regressions, and numerical
parity of tuned vs default launch plans (forward AND grads, ranks 1-3)."""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import vmem
from repro.configs import FNO_IDS, get_config
from repro.configs.base import PrecisionPolicy
from repro.configs.fno import with_block_plan
from repro.kernels import ops
from repro.tuning import (autotune, plans, resolve_block_plan,
                          resolve_launch_plans, store)
from repro.tuning.plans import LaunchPlans


# ---------------------------------------------------------------------------
# key schema
# ---------------------------------------------------------------------------
def test_plan_key_roundtrip_and_variant_normalization():
    klass = plans.shape_class(64, 64, (128, 128), (32, 32))
    assert klass == "h64-s128x128-m32x32"
    for launch in plans.LAUNCH_KINDS:
        key = plans.plan_key(2, klass, "shared", "bf16", launch)
        parsed = plans.parse_key(key)
        assert parsed["launch"] == launch
        # backward launches key as "full"; core is the only "partial"
        assert parsed["variant"] == ("partial" if launch == "core"
                                     else "full")


def test_shape_class_distinguishes_cells():
    # distinct (hidden | spatial | modes | out) => distinct keys; batch
    # never participates
    a = plans.shape_class(64, 64, (128,), (32,))
    assert plans.shape_class(128, 128, (128,), (32,)) != a
    assert plans.shape_class(64, 64, (256,), (32,)) != a
    assert plans.shape_class(64, 64, (128,), (64,)) != a
    assert "o32" in plans.shape_class(64, 32, (128,), (32,))
    # pow2 bucketing: nearby shapes transfer
    assert plans.shape_class(60, 60, (100,), (30,)) == a


def test_parse_key_rejects_defects():
    ok = plans.plan_key(2, "h64-s128x128-m32x32", "shared", "f32", "wgrad")
    plans.parse_key(ok)
    for bad in ("r2/only/four/segs",
                "r4/h64-s128-m32/shared/full/f32/block_fwd",
                "r2/h64-s128-m32/diag/full/f32/block_fwd",
                "r2/h64-s128-m32/shared/full/f32/warp",
                "r2/h64-s128-m32/shared/partial/f32/block_fwd"):
        with pytest.raises(ValueError):
            plans.parse_key(bad)


# ---------------------------------------------------------------------------
# cache store
# ---------------------------------------------------------------------------
def _entry(bb, bo, bh, probe=None):
    return {"bb": bb, "bo": bo, "bh": bh,
            "probe": probe or {"batch": 8, "hidden": 16, "spatial": [64],
                               "modes": [16]}}


def test_cache_roundtrip_and_lint_clean(tmp_path):
    path = str(tmp_path / "blocks.json")
    key = plans.plan_key(1, plans.shape_class(16, 16, (64,), (16,)),
                         "shared", "f32", "block_fwd")
    store.save_cache({key: _entry(8, 16, 16)}, path=path)
    assert store.lookup(key, path) == (8, 16, 16)
    assert store.lookup("r1/h16-s64-m16/shared/full/bf16/block_fwd",
                        path) is None  # distinct dtype key: miss
    assert [f for f in store.check_tuning_cache(path)
            if f.severity == "error"] == []


def test_cache_staleness_lint_fires(tmp_path):
    key = plans.plan_key(1, plans.shape_class(16, 16, (64,), (16,)),
                         "shared", "f32", "block_fwd")

    # engine signature mismatch
    p1 = str(tmp_path / "stale_sig.json")
    store.save_cache({key: _entry(8, 16, 16)},
                     meta={"engine_signature": "fnond-v0:obsolete"}, path=p1)
    fs = store.check_tuning_cache(p1)
    assert any("signature mismatch" in f.message for f in fs)

    # unparseable key + non-positive triple + missing probe
    p2 = str(tmp_path / "broken.json")
    store.save_cache({
        "not/a/key": _entry(1, 1, 1),
        key: {"bb": 0, "bo": 16, "bh": 16, "probe": {}},
    }, path=p2)
    msgs = " | ".join(f.message for f in store.check_tuning_cache(p2))
    assert "unparseable key" in msgs and "positive integer" in msgs

    # stale winner: recorded probe no longer fits under the estimator
    p3 = str(tmp_path / "stale_win.json")
    big = plans.plan_key(3, plans.shape_class(32, 32, (64, 64, 64),
                                              (16, 16, 16)),
                         "shared", "f32", "block_fwd")
    store.save_cache({big: _entry(8, 128, 128, probe={
        "batch": 8, "hidden": 32, "spatial": [64, 64, 64],
        "modes": [16, 16, 16]})}, path=p3)
    fs = store.check_tuning_cache(p3)
    assert any("stale winner" in f.message for f in fs)

    # absent file: warn, not error
    fs = store.check_tuning_cache(str(tmp_path / "nope.json"))
    assert len(fs) == 1 and fs[0].severity == "warn"


def test_illegal_plan_fires_tiling_lint(tmp_path):
    # Mutation: a winner whose bo breaks Mosaic's (8, 128) tiling rule —
    # the described-chip compile refuses such a kernel outright.
    path = str(tmp_path / "illegal.json")
    good = plans.plan_key(2, plans.shape_class(16, 16, (32, 32), (8, 8)),
                          "shared", "f32", "block_fwd")
    bad = plans.plan_key(1, plans.shape_class(16, 16, (64,), (16,)),
                         "shared", "f32", "block_fwd")
    store.save_cache({
        good: _entry(8, 16, 4, probe={"batch": 8, "hidden": 16,
                                      "spatial": [32, 32], "modes": [8, 8]}),
        bad: _entry(8, 4, 16),
    }, path=path)
    fs = store.check_tuning_cache(path)
    assert [f.target.split("::")[1] for f in fs] == [bad], fs
    assert "illegal plan" in fs[0].message


@pytest.mark.parametrize("rank", [1, 2, 3])
def test_legal_blocks_obey_tiling_rule(rank):
    # Every preference clamps to a plan the tiling rule admits, for
    # aligned, ragged and tiny dims alike.
    for dim in (1, 8, 16, 20, 64, 129, 200):
        for pref in (1, 2, 4, 8, 16, 32, 64, 128):
            bb, bo, bh = plans.legal_blocks(rank, 8, dim, dim, pref, pref,
                                          pref)
            o_pad, h_pad = -dim % bo + dim, -dim % bh + dim
            assert plans.tiling_legal(rank, o_pad, h_pad, bo, bh), (
                rank, dim, pref, bo, bh)
            assert bo % 8 == 0 and h_pad - dim < max(dim, 8), (dim, bh)
    # rank >= 2 keeps a small hidden tile free; rank 1 may not
    assert plans.legal_blocks(2, 8, 64, 64, 1, 8, 4)[2] == 4
    assert plans.legal_blocks(1, 8, 64, 64, 1, 8, 4)[2] == 8


def test_committed_cache_is_fresh():
    fs = [f for f in store.check_tuning_cache() if f.severity == "error"]
    assert fs == [], fs
    assert store.load_cache()["entries"], "committed cache must be non-empty"


# ---------------------------------------------------------------------------
# resolver precedence
# ---------------------------------------------------------------------------
def test_resolver_precedence(tmp_path, monkeypatch):
    cfg = get_config("fno2d", reduced=True)
    # cache hit
    p = resolve_block_plan(cfg, "block_fwd")
    assert p.source == "cache" and all(v > 0 for v in p.triple)
    # explicit override beats the cache, component-wise
    p2 = resolve_block_plan(cfg, "block_fwd", override=(4, 0, 0))
    assert p2.source == "override"
    assert p2.bb == 4 and (p2.bo, p2.bh) == (p.bo, p.bh)
    # cfg.block_plan participates as the override
    p3 = resolve_block_plan(with_block_plan(cfg, 2, 0, 8), "block_fwd")
    assert (p3.bb, p3.bh) == (2, 8) and p3.bo == p.bo
    # no cache -> static defaults
    monkeypatch.setattr(store, "load_cache",
                        lambda path=None: {"meta": {}, "entries": {}})
    p4 = resolve_block_plan(cfg, "block_fwd")
    assert p4.source == "default"
    assert p4.triple == plans._BLOCK_DEFAULTS[cfg.ndim]


def test_rank1_core_aliases_block_fwd():
    cfg = get_config("fno1d", reduced=True)
    lp = resolve_launch_plans(1, hidden=cfg.hidden,
                              spatial=tuple(cfg.spatial),
                              modes=tuple(cfg.modes))
    assert lp.core == lp.fwd
    assert resolve_block_plan(cfg, "core").key.endswith("block_fwd")


def test_serve_batch_block_routes_through_resolver():
    from repro.train.serve_fno_step import batch_block

    for arch in FNO_IDS:
        cfg = get_config(arch, reduced=True)
        assert batch_block(cfg) == resolve_block_plan(cfg, "block_fwd").bb


def test_serve_quantum_validates_against_tuned_plan():
    # ISSUE 10: the bucket-ladder quantum must stay a multiple of the
    # TUNED plan's batch block, not the static default — a retune that
    # changes bb can never silently misalign an explicit ladder.
    from repro.tuning import serve_quantum

    for arch in FNO_IDS:
        cfg = get_config(arch, reduced=True)
        bb = resolve_block_plan(cfg, "block_fwd").bb
        assert serve_quantum(cfg) == bb  # None -> the tuned bb itself
        assert serve_quantum(cfg, bb) == bb
        assert serve_quantum(cfg, 3 * bb) == 3 * bb  # e.g. bb x dp shards
        for bad in (bb + 1, -bb, 0):
            with pytest.raises(ValueError, match="tuned batch block"):
                serve_quantum(cfg, bad)


def test_serve_quantum_follows_block_plan_override():
    # A pinned cfg-level launch plan changes the resolved bb, and the
    # quantum validation must follow it (the override wins over cache).
    from repro.tuning import serve_quantum

    cfg = get_config("fno2d", reduced=True)
    base = resolve_block_plan(cfg, "block_fwd").bb
    pinned = with_block_plan(cfg, 2 * base, 0, 0)
    assert resolve_block_plan(pinned, "block_fwd").bb == 2 * base
    assert serve_quantum(pinned) == 2 * base
    with pytest.raises(ValueError, match="tuned batch block"):
        serve_quantum(pinned, base)  # a multiple of the OLD bb only


def test_fno_server_rejects_misaligned_quantum():
    # The server constructor routes through serve_quantum, so a bad
    # explicit quantum fails loudly at build time — not as internal
    # padding on the first request.
    from repro.core import fno as fno_mod
    from repro.train.serve_fno_step import FNOServer
    import dataclasses as dc

    cfg = dc.replace(get_config("fno2d", reduced=True), path="pallas",
                     fuse_block=True)
    params = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda: fno_mod.init_fno(jax.random.PRNGKey(0),
                                                cfg)))
    bb = resolve_block_plan(cfg, "block_fwd").bb
    with pytest.raises(ValueError, match="tuned batch block"):
        FNOServer(cfg, params, max_batch=2 * bb, quantum=bb + 1)
    server = FNOServer(cfg, params, max_batch=2 * bb, quantum=bb)
    assert server.buckets[0] == bb  # ladder starts at the tuned quantum
    assert all(b % bb == 0 for b in server.buckets)


# ---------------------------------------------------------------------------
# feasibility: every runnable cell resolves budget-fitting plans
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("reduced", [True, False])
def test_all_cells_resolve_feasible_plans(dtype, reduced):
    pol = PrecisionPolicy.from_name(dtype)
    for arch in FNO_IDS:
        cfg = get_config(arch, reduced=reduced)
        for variant in ("full", "partial"):
            ests = vmem.block_launch_estimates(cfg, variant=variant,
                                               policy=pol)
            for name, e in ests.items():
                assert e.total_bytes <= vmem.VMEM_BUDGET_BYTES, (
                    f"{arch} reduced={reduced} {dtype} {variant} {name}: "
                    f"{e.total_bytes / 2**20:.1f} MiB over budget")


def test_autotune_smoke_covers_reduced_cells(tmp_path):
    path, entries = autotune.tune(smoke=True, out=str(tmp_path / "b.json"),
                                  log=lambda *a: None)
    assert entries
    for key in entries:
        plans.parse_key(key)  # every key well-formed
    assert [f for f in store.check_tuning_cache(path)
            if f.severity == "error"] == []


# ---------------------------------------------------------------------------
# _pick_block clamp regressions (odd extents must not explode padding)
# ---------------------------------------------------------------------------
def test_pick_block_minimizes_pad_waste():
    assert plans._pick_block(129, 128) == 8      # pads to 136, not 256
    assert plans._pick_block(192, 128) == 96     # exact multiple, zero waste
    assert plans._pick_block(64, 128) == 64
    assert plans._pick_block(4, 128) == 8        # tiny dims keep one block
    assert plans._pick_block(1, 2) == 1          # no padding a singleton
    assert plans._pick_block(8, 2) == 2          # explicit small pref wins
    for dim, pref in ((129, 128), (65, 64), (33, 32), (7, 8)):
        b = plans._pick_block(dim, pref)
        padded = -dim % b + dim
        assert padded - dim < dim, (dim, pref, b)  # waste strictly < 100%


# ---------------------------------------------------------------------------
# parity: differing launch plans change nothing numerically
# ---------------------------------------------------------------------------
def _tiny(rank, seed=0):
    h, n, m = 8, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (2, h) + (n,) * rank, jnp.float32)
    wr = 0.1 * jax.random.normal(ks[1], (h, h), jnp.float32)
    wi = 0.1 * jax.random.normal(ks[2], (h, h), jnp.float32)
    wb = 0.1 * jax.random.normal(ks[3], (h, h), jnp.float32)
    bias = 0.1 * jax.random.normal(ks[4], (h,), jnp.float32)
    return x, wr, wi, wb, bias, (m,) * rank


@pytest.mark.parametrize("rank", [1, 2, 3])
def test_block_plan_parity_fwd_and_grads(rank):
    x, wr, wi, wb, bias, modes = _tiny(rank)

    def run(block_plan):
        def loss(p):
            y = ops.fno_block_nd(x, p["wr"], p["wi"], p["wb"], p["b"],
                                 modes, path="pallas", interpret=True,
                                 block_plan=block_plan)
            return jnp.sum(y ** 2), y
        (l, y), g = jax.value_and_grad(loss, has_aux=True)(
            {"wr": wr, "wi": wi, "wb": wb, "b": bias})
        return y, g

    def rel(a, b):  # block size changes accumulation order, not math
        return jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30)

    y0, g0 = run(None)            # tuned-cache resolution
    y1, g1 = run((1, 4, 4))       # deliberately different plan
    assert rel(y0, y1) < 1e-5
    for k in g0:
        assert rel(g0[k], g1[k]) < 1e-5, k


def test_tuned_vs_default_parity_reduced_2d():
    cfg = get_config("fno2d", reduced=True)
    x, wr, wi, wb, bias, _ = _tiny(2, seed=1)
    modes = (4, 4)
    y_tuned = ops.fno_block_nd(x, wr, wi, wb, bias, modes, path="pallas",
                               interpret=True)
    dflt = plans._BLOCK_DEFAULTS[cfg.ndim]
    y_dflt = ops.fno_block_nd(x, wr, wi, wb, bias, modes, path="pallas",
                              interpret=True, block_plan=dflt)
    assert jnp.max(jnp.abs(y_tuned - y_dflt)) < 1e-5


# ---------------------------------------------------------------------------
# custom_vjp plumbing: LaunchPlans is hashable and jit-cache friendly
# ---------------------------------------------------------------------------
def test_launch_plans_hashable_and_override():
    lp = LaunchPlans.uniform((2, 128, 32))
    assert hash(lp) == hash(LaunchPlans.uniform((2, 128, 32)))
    ov = lp.with_override(bb=4)
    assert ov.fwd == (4, 128, 32) and ov.wgrad == (4, 128, 32)
    assert lp.with_override() is lp
    assert lp.for_launch("gz_recompute") == (2, 128, 32)
