"""Multi-device distribution tests (subprocess with 8 virtual CPU devices):
sharded-vs-single equivalence, pipeline parallelism, gradient compression,
elastic restore, dry-run cell compilation, and the DP×TP fused-FNO path
(ISSUE 5: the shard_map dispatch in kernels.ops + the FNO leaf specs)."""
import pytest


def test_sharded_train_step_matches_single(subproc):
    subproc("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.launch.mesh import make_debug_mesh
    from repro.distributed import sharding as shd
    from repro.models import transformer as tf
    from repro.optim import AdamW
    from repro.optim.schedule import constant
    from repro.train.train_step import make_train_step

    cfg = get_config("qwen2-1.5b", reduced=True)
    key = jax.random.PRNGKey(0)
    params = tf.init_lm(key, cfg, jnp.float32)
    opt = AdamW(lr=constant(1e-3))
    opt_state = opt.init(params)
    tokens = jax.random.randint(key, (4, 32), 0, cfg.vocab_size)
    batch = {"tokens": tokens,
             "labels": jax.random.randint(key, (4, 32), 0, cfg.vocab_size)}

    step = make_train_step(cfg, opt)
    p1, o1, m1 = jax.jit(step)(params, opt_state, batch)

    mesh = make_debug_mesh(4, 2)
    ctx = shd.make_context(cfg, mesh)
    pspec = shd.param_specs(cfg, mesh, params)
    ospec = {"m": pspec, "v": pspec, "step": jax.sharding.PartitionSpec()}
    bspec = shd.batch_specs(cfg, ctx, batch)
    sh = lambda t: shd.shardings_from_specs(t, mesh)
    def step_ctx(p, o, b):
        with shd.sharding_context(ctx):
            return step(p, o, b)
    j = jax.jit(step_ctx, in_shardings=(sh(pspec), sh(ospec), sh(bspec)))
    p2, o2, m2 = j(params, opt_state, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-5)
    d = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), p1, p2)
    mx = max(jax.tree_util.tree_leaves(d))
    assert mx < 2e-4, mx
    print("sharded==single OK", mx)
    """)


def test_gpipe_matches_sequential(subproc):
    subproc("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.distributed.pipeline import make_gpipe_fn

    S, M, mb, d = 4, 6, 2, 16
    mesh = make_mesh((S,), ("stage",))
    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (S, d, d)) / d**0.5
    x = jax.random.normal(key, (M, mb, d))

    def stage_fn(w, xin):  # per-stage computation
        return jnp.tanh(xin @ w[0])

    f = make_gpipe_fn(stage_fn, mesh=mesh, axis="stage", num_stages=S,
                      stage_param_spec=P("stage"), x_spec=P())
    out = f(ws, x)

    # sequential reference
    ref = x
    for s in range(S):
        ref = jnp.tanh(ref @ ws[s])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    print("gpipe OK")
    """)


def test_compressed_psum_error_feedback(subproc):
    subproc("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.distributed import compression as comp
    from repro.distributed.sharding import compat_shard_map
    from repro.launch.mesh import make_mesh

    n = 8
    mesh = make_mesh((n,), ("dp",))
    key = jax.random.PRNGKey(0)
    g = jax.random.normal(key, (n, 64, 64))

    def one(gs, res):
        return comp.ef_psum(gs, res, "dp")
    f = compat_shard_map(one, mesh, in_specs=(P("dp"), P("dp")),
                         out_specs=(P("dp"), P("dp")))

    res = jnp.zeros_like(g)
    exact = jnp.sum(g, axis=0)
    summed, res = f(g, res)
    err1 = float(jnp.abs(summed[0] - exact).max() / jnp.abs(exact).max())
    assert err1 < 0.05, err1  # int8 quantization error bound

    # error feedback: accumulated compressed sums converge to accumulated
    # exact sums over repeated reductions of the same gradient
    acc_c = jnp.zeros_like(exact)
    res = jnp.zeros_like(g)
    T = 20
    for _ in range(T):
        s, res = f(g, res)
        acc_c = acc_c + s[0]
    err_T = float(jnp.abs(acc_c / T - exact).max() / jnp.abs(exact).max())
    assert err_T < err1 / 2, (err1, err_T)
    print("compression OK", err1, err_T)
    """)


def test_elastic_restore_across_meshes(subproc):
    subproc("""
    import tempfile, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.launch.mesh import make_debug_mesh
    from repro.distributed import sharding as shd
    from repro.checkpoint import Checkpointer
    from repro.models import transformer as tf

    cfg = get_config("qwen2-1.5b", reduced=True)
    key = jax.random.PRNGKey(0)
    params = tf.init_lm(key, cfg, jnp.float32)

    mesh_a = make_debug_mesh(4, 2)  # 8 chips ("before failure")
    sh_a = shd.shardings_from_specs(
        shd.param_specs(cfg, mesh_a, params), mesh_a)
    params_a = jax.device_put(params, sh_a)

    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        ck.save(10, params_a)
        # "lost half the fleet": restore onto a 2x2 mesh
        mesh_b = make_debug_mesh(2, 2)
        sh_b = shd.shardings_from_specs(
            shd.param_specs(cfg, mesh_b, params), mesh_b)
        restored = ck.restore(10, params, shardings=sh_b)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)), params, restored)
        leaf = restored["layers"]["attn"]["wq"]["w"]
        assert leaf.sharding.mesh.shape["data"] == 2
    print("elastic restore OK")
    """)


@pytest.mark.parametrize("arch,shape", [
    ("qwen2-1.5b", "train_4k"),
    ("mixtral-8x7b", "decode_32k"),
    ("mamba2-370m", "long_500k"),
    ("gemma3-27b", "prefill_32k"),
])
def test_reduced_cells_compile_multipod(subproc, arch, shape):
    subproc(f"""
    import jax
    from repro.launch import cells as cm
    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh(2, 2, 2)  # pod x data x model
    cell = cm.build_cell("{arch}", "{shape}", mesh, reduced=True)
    j = jax.jit(cell.step_fn, in_shardings=cell.in_shardings)
    co = j.lower(*cell.args).compile()
    ca = co.cost_analysis()
    assert ca.get("flops", 0) > 0
    print("cell OK", "{arch}", "{shape}")
    """)


# ---------------------------------------------------------------------------
# Sharded FNO (ISSUE 5): the fused pallas block under DP and DP×TP meshes
# must match the single-device XLA oracle to the test_precision f32
# tolerance (2e-4); TP shards the hidden k-loop axis with the partial
# pre-activations psum-reduced inside the shard_map dispatch.
# ---------------------------------------------------------------------------
def test_fno_dp_tp_fused_block_matches_single(subproc):
    subproc("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.launch.mesh import make_debug_mesh
    from repro.distributed import sharding as shd
    from repro.core import fno as fno_mod

    cfg = dataclasses.replace(get_config("fno2d", reduced=True),
                              path="pallas", fuse_block=True)
    key = jax.random.PRNGKey(0)
    params = fno_mod.init_fno(key, cfg)
    x = jax.random.normal(key, (8, cfg.in_channels) + tuple(cfg.spatial))
    y_ref = fno_mod.apply_fno(params, cfg, x, path="xla")

    for dp, tp in ((8, 1), (4, 2), (2, 4)):
        mesh = make_debug_mesh(dp, tp)
        ctx = shd.make_context(cfg, mesh, kind="serve")
        # tp=1 folds model into the batch axes (pure DP); tp>1 shards the
        # hidden k-loop axis over "model"
        assert (ctx.model_axis == "model") == (tp > 1), (dp, tp, ctx)
        def fwd(p, xx):
            with shd.sharding_context(ctx):
                return fno_mod.apply_fno(p, cfg, xx, path="pallas")
        y = jax.jit(fwd)(params, x)
        err = float(jnp.abs(y - y_ref).max())
        assert err < 2e-4, (dp, tp, err)
        print(f"dp={dp} tp={tp} max_err={err:.2e}")
    print("fno dp/tp parity OK")
    """)


def test_fno_tp_bf16_matches_single_device(subproc):
    # The TP cast contract: partial pre-activations cross the psum at the
    # ACCUMULATOR dtype (f32), so the bf16 DP×TP block must match the
    # single-device bf16 pallas path to f32-parity tolerance — not merely
    # the bf16-vs-f32 tolerance.
    subproc("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.configs.fno import with_precision
    from repro.launch.mesh import make_debug_mesh
    from repro.distributed import sharding as shd
    from repro.core import fno as fno_mod

    cfg = dataclasses.replace(
        with_precision(get_config("fno2d", reduced=True), "bf16"),
        path="pallas", fuse_block=True)
    key = jax.random.PRNGKey(0)
    params = fno_mod.init_fno(key, cfg)
    x = jax.random.normal(key, (8, cfg.in_channels) + tuple(cfg.spatial))
    y_single = fno_mod.apply_fno(params, cfg, x, path="pallas")
    assert y_single.dtype == jnp.bfloat16, y_single.dtype

    mesh = make_debug_mesh(2, 4)
    ctx = shd.make_context(cfg, mesh)
    assert ctx.model_axis == "model"
    def fwd(p, xx):
        with shd.sharding_context(ctx):
            return fno_mod.apply_fno(p, cfg, xx, path="pallas")
    y = jax.jit(fwd)(params, x)
    assert y.dtype == jnp.bfloat16, y.dtype
    err = float(jnp.abs(y.astype(jnp.float32)
                        - y_single.astype(jnp.float32)).max())
    scale = float(jnp.abs(y_single.astype(jnp.float32)).max())
    assert err < 2e-2 * max(scale, 1.0), (err, scale)
    print("fno bf16 tp parity OK", err)
    """)


def test_fno_dp_tp_grads_match_single(subproc):
    subproc("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.launch.mesh import make_debug_mesh
    from repro.distributed import sharding as shd
    from repro.core import fno as fno_mod

    cfg = dataclasses.replace(get_config("fno2d", reduced=True),
                              path="pallas", fuse_block=True)
    key = jax.random.PRNGKey(0)
    params = fno_mod.init_fno(key, cfg)
    batch = {
        "x": jax.random.normal(key, (8, cfg.in_channels)
                               + tuple(cfg.spatial)),
        "y": jax.random.normal(jax.random.fold_in(key, 1),
                               (8, cfg.out_channels) + tuple(cfg.spatial)),
    }
    g_ref = jax.grad(
        lambda p: fno_mod.fno_loss(p, cfg, batch, path="xla"))(params)

    mesh = make_debug_mesh(4, 2)
    ctx = shd.make_context(cfg, mesh)
    def loss(p):
        with shd.sharding_context(ctx):
            return fno_mod.fno_loss(p, cfg, batch, path="pallas")
    g = jax.jit(jax.grad(loss))(params)
    d = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), g, g_ref)
    mx = max(jax.tree_util.tree_leaves(d))
    assert mx < 1e-4, mx
    print("fno dp x tp grads OK", mx)
    """)


def test_fno_train_step_has_no_explicit_psum():
    # The ef_psum scope contract (distributed/compression.py): the FNO
    # train step hand-writes NO gradient collective — outside a sharding
    # context the whole step traces zero collectives. Under a DP jit the
    # all-reduce is GSPMD's (derived from the batch-axis sharding; the
    # only trace-level psums a DP context adds are shard_map's OWN
    # weight-grad transposes inside the fused-block dispatch). Wiring
    # tree_ef_psum into the step would both break this budget and
    # double-reduce.
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.analysis import jaxpr_lint as jl
    from repro.configs import get_config
    from repro.core import fno as fno_mod
    from repro.optim import AdamW
    from repro.optim.schedule import constant
    from repro.train.train_step import make_train_step

    cfg = dataclasses.replace(get_config("fno2d", reduced=True),
                              path="pallas", fuse_block=True)
    params = fno_mod.init_fno(jax.random.PRNGKey(0), cfg)
    opt = AdamW(lr=constant(1e-3))
    state = opt.init(params)
    batch = {"x": jnp.zeros((2, cfg.in_channels) + tuple(cfg.spatial)),
             "y": jnp.zeros((2, cfg.out_channels) + tuple(cfg.spatial))}
    step = make_train_step(cfg, opt, fno_path="pallas")
    counts = jl.collective_counts(step, params, state, batch)
    assert counts == {}, counts


def test_fno_collective_bytes_model():
    """The roofline collective-traffic model (ISSUE 8) — pure math, no
    devices: scattered interior layers move exactly HALF the psum
    layout's wire bytes, the final layer always all-reduces, and TP that
    folds away (tp=1 or hidden % tp != 0) costs zero."""
    import math

    from repro.configs import get_config
    from repro.configs.fno import with_precision
    from repro.roofline.analysis import fno_collective_bytes

    cfg = get_config("fno2d", reduced=True)
    sc = fno_collective_bytes(cfg, 4, 2, scattered=True, batch=8)
    ps = fno_collective_bytes(cfg, 4, 2, scattered=False, batch=8)
    assert sc["interior_per_layer"] == 0.5 * ps["interior_per_layer"]
    assert sc["final"] == ps["final"]  # the projection needs full hidden
    L = cfg.num_layers
    assert ps["total"] == L * ps["interior_per_layer"]
    assert sc["total"] == (L - 1) * sc["interior_per_layer"] + sc["final"]
    # exact ring wire bytes: T = (8/4)·hidden·∏spatial·4 B, tp=2
    t = 2 * cfg.hidden * math.prod(cfg.spatial) * 4
    assert ps["interior_per_layer"] == 2 * (2 - 1) / 2 * t
    # bf16 activations halve the collective traffic
    sc16 = fno_collective_bytes(with_precision(cfg, "bf16"), 4, 2, batch=8)
    assert sc16["total"] == 0.5 * sc["total"]
    # degradation mirrors make_context
    assert fno_collective_bytes(cfg, 8, 1)["total"] == 0.0
    assert fno_collective_bytes(cfg, 2, 3)["total"] == 0.0  # 16 % 3 != 0


def test_fno_tp_scatter_layout_parity_and_budget(subproc):
    # ISSUE 8 tentpole: the scattered TP layout (interior layers complete
    # their sharded k-loop with a psum_scatter emitting the NEXT layer's
    # hidden shard; only the final layer psums) — fwd + grad parity vs the
    # single-device XLA oracle, and the exact collective budget.
    subproc("""
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.mesh import make_debug_mesh
    from repro.distributed import sharding as shd
    from repro.core import fno as fno_mod
    from repro.analysis import jaxpr_lint as jl

    cfg = dataclasses.replace(get_config("fno2d", reduced=True),
                              path="pallas", fuse_block=True)
    assert cfg.tp_layout == "scatter"  # scattered is the default layout
    key = jax.random.PRNGKey(0)
    params = fno_mod.init_fno(key, cfg)
    x = jax.random.normal(key, (8, cfg.in_channels) + tuple(cfg.spatial))
    y_ref = fno_mod.apply_fno(params, cfg, x, path="xla")
    g_ref = jax.grad(lambda p: jnp.sum(
        fno_mod.apply_fno(p, cfg, x, path="xla") ** 2))(params)
    denom = max(float(jnp.abs(l).max())
                for l in jax.tree_util.tree_leaves(g_ref))

    for dp, tp in ((4, 2), (2, 4)):
        mesh = make_debug_mesh(dp, tp)
        ctx = shd.make_context(cfg, mesh, kind="serve")
        assert ctx.model_axis == "model"
        # fresh closures per mesh: jax.make_jaxpr caches on function
        # identity + avals, and the thread-local sharding context is
        # invisible to that cache — a reused closure would replay the
        # previous mesh's trace.
        def fwd(p, xx, _ctx=ctx):
            with shd.sharding_context(_ctx):
                return fno_mod.apply_fno(p, cfg, xx, path="pallas")
        y = jax.jit(fwd)(params, x)
        err = float(jnp.abs(y - y_ref).max())
        assert err < 2e-4, (dp, tp, err)
        g = jax.jit(jax.grad(
            lambda p, xx, _f=fwd: jnp.sum(_f(p, xx) ** 2)))(params, x)
        gerr = max(float(jnp.abs(a - b).max()) for a, b in zip(
            jax.tree_util.tree_leaves(g),
            jax.tree_util.tree_leaves(g_ref))) / denom
        assert gerr < 2e-4, (dp, tp, gerr)
        counts = jl.collective_counts(fwd, params, x)
        rs = counts.get("reduce_scatter", 0) + counts.get("psum_scatter", 0)
        assert rs == cfg.num_layers - 1, counts  # one per INTERIOR layer
        assert counts.get("psum", 0) == 1, counts  # final layer only
        assert jl.pallas_count(fwd, params, x) == cfg.num_layers
        print(f"dp{dp}xtp{tp}: fwd={err:.2e} relgrad={gerr:.2e} "
              f"coll={counts}")
    print("scattered TP layout parity + budget OK")
    """)


def test_fno_tp_layouts_agree_and_overlap_ring(subproc):
    # The three TP collective plans are the same math: psum layout,
    # scattered layout, and the scattered layout with the ppermute ring
    # (tp_overlap) all match bitwise-tight; the ring traces tp-1
    # ppermutes per interior layer in place of the one-shot
    # reduce-scatter. Grads flow through the ring natively (ppermute
    # transposes to ppermute).
    subproc("""
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.mesh import make_debug_mesh
    from repro.distributed import sharding as shd
    from repro.core import fno as fno_mod
    from repro.analysis import jaxpr_lint as jl

    cfg0 = dataclasses.replace(get_config("fno2d", reduced=True),
                               path="pallas", fuse_block=True)
    key = jax.random.PRNGKey(0)
    params = fno_mod.init_fno(key, cfg0)
    x = jax.random.normal(key, (8, cfg0.in_channels) + tuple(cfg0.spatial))
    dp, tp = 2, 4
    mesh = make_debug_mesh(dp, tp)

    outs, grads, colls = {}, {}, {}
    for layout, overlap in (("psum", False), ("scatter", False),
                            ("scatter", True)):
        cfg = dataclasses.replace(cfg0, tp_layout=layout,
                                  tp_overlap=overlap)
        ctx = shd.make_context(cfg, mesh, kind="serve")
        def fwd(p, xx, _cfg=cfg, _ctx=ctx):  # fresh closure per variant
            with shd.sharding_context(_ctx):
                return fno_mod.apply_fno(p, _cfg, xx, path="pallas")
        name = layout + ("+ring" if overlap else "")
        outs[name] = jax.jit(fwd)(params, x)
        grads[name] = jax.jit(jax.grad(
            lambda p, xx, _f=fwd: jnp.sum(_f(p, xx) ** 2)))(params, x)
        colls[name] = jl.collective_counts(fwd, params, x)

    for name in ("scatter", "scatter+ring"):
        err = float(jnp.abs(outs[name] - outs["psum"]).max())
        assert err < 1e-5, (name, err)
        gerr = max(float(jnp.abs(a - b).max()) for a, b in zip(
            jax.tree_util.tree_leaves(grads[name]),
            jax.tree_util.tree_leaves(grads["psum"])))
        assert gerr < 1e-4, (name, gerr)
    L = cfg0.num_layers
    assert colls["psum"] == {"psum": L}, colls["psum"]
    assert colls["scatter"].get("ppermute", 0) == 0, colls["scatter"]
    ring = colls["scatter+ring"]
    assert ring.get("ppermute", 0) == (tp - 1) * (L - 1), ring
    assert ring.get("reduce_scatter", 0) == 0 and \
        ring.get("psum_scatter", 0) == 0, ring
    assert ring.get("psum", 0) == 1, ring
    print("layout equivalence + overlap ring OK", colls)
    """)


def test_fno_fused_ends_sharded_dispatch(subproc):
    # cfg.fuse_ends under shard_map: pure DP keeps the ends fused (zero
    # collectives, num_layers pallas_calls, parity); with TP on, the guard
    # in core.fno falls back to staged ends while the scattered interior
    # collectives stay intact.
    subproc("""
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.mesh import make_debug_mesh
    from repro.distributed import sharding as shd
    from repro.core import fno as fno_mod
    from repro.analysis import jaxpr_lint as jl

    cfg = dataclasses.replace(get_config("fno2d", reduced=True),
                              path="pallas", fuse_block=True,
                              fuse_ends=True)
    key = jax.random.PRNGKey(0)
    params = fno_mod.init_fno(key, cfg)
    x = jax.random.normal(key, (8, cfg.in_channels) + tuple(cfg.spatial))
    y_ref = fno_mod.apply_fno(params, cfg, x, path="xla")

    # pure DP (8x1) and DP with the model axis folded (4x2, strategy=dp):
    # ends stay fused.
    for mesh, strategy in ((make_debug_mesh(8, 1), None),
                           (make_debug_mesh(4, 2), "dp")):
        ctx = shd.make_context(cfg, mesh, fno_strategy=strategy,
                               kind="serve")
        assert ctx.model_axis is None
        def fwd(p, xx, _ctx=ctx):  # fresh closure per context
            with shd.sharding_context(_ctx):
                return fno_mod.apply_fno(p, cfg, xx, path="pallas")
        y = jax.jit(fwd)(params, x)
        err = float(jnp.abs(y - y_ref).max())
        assert err < 2e-4, err
        assert jl.pallas_count(fwd, params, x) == cfg.num_layers
        assert jl.collective_counts(fwd, params, x) == {}

    # TP on: fuse_ends is ignored (the projection needs the full
    # post-psum hidden vector), the scattered budget is unchanged.
    ctx = shd.make_context(cfg, make_debug_mesh(4, 2), kind="serve")
    assert ctx.model_axis == "model"
    def fwd_tp(p, xx, _ctx=ctx):
        with shd.sharding_context(_ctx):
            return fno_mod.apply_fno(p, cfg, xx, path="pallas")
    y = jax.jit(fwd_tp)(params, x)
    assert float(jnp.abs(y - y_ref).max()) < 2e-4
    counts = jl.collective_counts(fwd_tp, params, x)
    rs = counts.get("reduce_scatter", 0) + counts.get("psum_scatter", 0)
    assert rs == cfg.num_layers - 1 and counts.get("psum", 0) == 1, counts
    print("fused ends sharded dispatch OK")
    """)


def test_fno_leaf_specs_and_guard(subproc):
    subproc("""
    import jax, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_config
    from repro.launch.mesh import make_debug_mesh
    from repro.distributed import sharding as shd
    from repro.core import fno as fno_mod

    cfg = get_config("fno2d", reduced=True)  # hidden=16
    params = jax.eval_shape(
        lambda: fno_mod.init_fno(jax.random.PRNGKey(0), cfg))

    # TP divides hidden (16 % 2 == 0): spectral shards the HIDDEN (k-loop)
    # axis, bypass shards its contraction dim, biases replicate.
    mesh = make_debug_mesh(4, 2)
    specs = shd.param_specs(cfg, mesh, params)
    blk = specs["blocks"][0]
    assert blk["spectral"]["wr"] == P(None, "model"), blk["spectral"]["wr"]
    assert blk["bypass"]["w"] == P("model", None), blk["bypass"]["w"]
    assert blk["bypass"]["b"] == P(None), blk["bypass"]["b"]
    assert specs["lift2"]["w"] == P("model", None)
    assert specs["proj1"]["w"] == P("model", None)

    # guard_spec regression: a model axis that does NOT divide hidden must
    # degrade the FNO leaf specs to replication, not error (mesh 2x3 on 8
    # forced devices: 16 % 3 != 0).
    mesh3 = shd.Mesh(np.array(jax.devices()[:6]).reshape(2, 3),
                     ("data", "model"))
    specs3 = shd.param_specs(cfg, mesh3, params)
    for leaf in jax.tree_util.tree_leaves(
            specs3, is_leaf=lambda s: isinstance(s, P)):
        assert all(e is None for e in tuple(leaf)), leaf
    # ...and make_context folds the unusable model axis into the batch.
    ctx3 = shd.make_context(cfg, mesh3)
    assert ctx3.model_axis is None and "model" in ctx3.batch_axes

    # fno_tp=False (pure DP) replicates even when hidden divides.
    specs_dp = shd.param_specs(cfg, mesh, params, fno_tp=False)
    for leaf in jax.tree_util.tree_leaves(
            specs_dp, is_leaf=lambda s: isinstance(s, P)):
        assert all(e is None for e in tuple(leaf)), leaf

    # spec trees always match the params structure exactly.
    assert (jax.tree_util.tree_structure(specs,
                is_leaf=lambda s: isinstance(s, P)).num_leaves
            == jax.tree_util.tree_structure(params).num_leaves)
    print("fno leaf specs + guard OK")
    """)


@pytest.mark.parametrize("shape,kw,want_tp", [
    # training defaults to pure DP (batch >> hidden: model axis folds into
    # the batch, weights replicate); TP is opt-in via fno_strategy
    ("train_4k", "", False),
    ("train_4k", ", fno_strategy='auto'", True),
    # the serving cell keeps the auto DP x TP grid
    ("prefill_32k", "", True),
])
def test_fno_cells_compile_dp_tp(subproc, shape, kw, want_tp):
    subproc(f"""
    import jax
    from repro.launch import cells as cm
    from repro.launch.mesh import make_debug_mesh
    mesh = make_debug_mesh(4, 2)
    cell = cm.build_cell("fno2d", "{shape}", mesh, reduced=True{kw})
    # the production FNO cells run the fused pallas path by default
    assert (cell.ctx.model_axis == "model") == {want_tp}, cell.ctx
    j = jax.jit(cell.step_fn, in_shardings=cell.in_shardings)
    co = j.lower(*cell.args).compile()
    ca = co.cost_analysis()
    assert ca.get("flops", 0) > 0
    print("fno cell OK", "{shape}")
    """)
