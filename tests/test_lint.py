"""Contract-linter tests (ISSUE 6): the clean repo passes every checker,
and mutation-style fixtures that deliberately violate each contract make
exactly the targeted checker fire with a pointed message."""
import os
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import ast_lint, errors, jaxpr_lint, vmem
from repro.configs import get_config
from repro.configs.base import FNOConfig, PrecisionPolicy
from repro.kernels import ops

MODES2 = (3, 4)


def _block_args(dtype="f32"):
    return jaxpr_lint.block_args(2, "shared", dtype)


def _block(policy):
    return lambda *a: ops.fno_block_nd(*a, MODES2, path="pallas",
                                       variant="full", policy=policy)


# ---------------------------------------------------------------------------
# clean repo: every layer passes
# ---------------------------------------------------------------------------
def test_ast_lints_clean_on_repo():
    assert ast_lint.run_ast_lints() == []


def test_config_registry_clean():
    assert ast_lint.check_config_registry() == []


def test_block_matrix_subset_clean():
    fs = jaxpr_lint.lint_block_matrix(ranks=(2,), layouts=("shared",),
                                      variants=("full",), dtypes=("f32",))
    assert fs == []


@pytest.mark.parametrize("rank", [1, 2, 3])
@pytest.mark.parametrize("layer,names", [
    ("spectral", ["spectral_fwd", "dx_adjoint", "wgrad"]),
    ("block", ["block_fwd", "gz_recompute", "dx_adjoint", "wgrad"]),
], ids=["spectral", "block"])
def test_grad_traces_the_named_launches(layer, names, rank):
    # The spectral layer and the block share one custom_vjp: the layer is
    # the block with no bypass and a linear epilogue, so its gradient
    # skips the gz recompute and its forward launch is spectral_fwd.
    modes = jaxpr_lint._MODES[rank]
    args = jaxpr_lint.block_args(rank, "shared", "f32")
    if layer == "block":
        fn = lambda *a: ops.fno_block_nd(*a, modes, path="pallas")
    else:
        args = args[:3]
        fn = lambda *a: ops.spectral_layer_nd(*a, modes, path="pallas")
    grad = jax.grad(lambda *a: jnp.sum(fn(*a) ** 2),
                    argnums=tuple(range(len(args))))
    traced = [e.params["name"] for e in jaxpr_lint.launch_eqns(grad, *args)
              if e.primitive.name == "pallas_call"]
    assert traced == names


def test_fused_block_contract_wrapper_clean():
    assert jaxpr_lint.fused_block_contract() == []


def test_vmem_reduced_configs_fit():
    cfgs = [(get_config(a, reduced=True), True)
            for a in ("fno1d", "fno2d", "fno3d")]
    assert errors(vmem.check_vmem(configs=cfgs)) == []


def test_vmem_full_size_configs_clean_with_tuned_cache():
    # Since the tuned cache (ISSUE 7), EVERY config — the big full-size
    # grids included — must resolve a budget-feasible plan at ERROR
    # severity. fno3d is the stress case: its x windows alone forced the
    # static defaults ~9x over budget before tuning.
    fs = vmem.check_vmem(configs=[get_config("fno3d")])
    assert fs == [], fs


def test_vmem_errors_without_tuned_cache(monkeypatch):
    # Mutation: with the cache gone, resolution falls back to the static
    # defaults, which overflow VMEM on the full-size 3D grid — the
    # checker must fire at error severity (the pre-tuning 42-warning
    # state is no longer tolerated).
    from repro.tuning import store

    monkeypatch.setattr(store, "load_cache",
                        lambda path=None: {"meta": {}, "entries": {}})
    fs = vmem.check_vmem(configs=[get_config("fno3d")], dtypes=("f32",),
                         variants=("full",))
    assert fs and errors(fs)
    assert any("regenerate the cache" in f.message for f in fs)


def test_sharded_and_serve_lints_clean(subproc):
    subproc("""
    import sys
    sys.path.insert(0, {src!r})
    from repro.analysis import format_findings, jaxpr_lint
    fs = jaxpr_lint.lint_sharded_blocks(mesh_grids=((4, 2), (8, 1)),
                                        dtypes=("f32",))
    fs += jaxpr_lint.lint_serve(mesh_grids=((4, 2),), dtypes=("f32",))
    assert not fs, format_findings(fs)
    print("sharded+serve lints OK")
    """.format(src=os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")))


# ---------------------------------------------------------------------------
# trace-lint mutations: each contract violation makes its checker fire
# ---------------------------------------------------------------------------
def test_mutation_split_pallas_call_fires_count_checker():
    pol = PrecisionPolicy.from_name("f32")
    blk = _block(pol)
    args = _block_args()

    def doubled(*a):  # a second kernel launch where the contract wants one
        return blk(*a) + blk(*a)

    fs = jaxpr_lint.check_pallas_count(doubled, args, 1, target="mutant")
    assert len(fs) == 1 and fs[0].checker == "pallas-count"
    assert "traced 2 pallas_calls, want exactly 1" in fs[0].message
    # the clean block passes the same checker
    assert jaxpr_lint.check_pallas_count(blk, args, 1, target="ok") == []


def test_mutation_stray_cast_fires_cast_checker():
    pol = PrecisionPolicy.from_name("f32")
    blk = _block(pol)
    args = _block_args()

    def leaky(*a):  # a stray down-cast the f32 policy does not own
        return blk(*a).astype(jnp.bfloat16)

    fs = jaxpr_lint.check_cast_ownership(leaky, args, pol, target="mutant")
    assert len(fs) == 1 and fs[0].checker == "cast-ownership"
    assert "float32->bfloat16" in fs[0].message
    assert jaxpr_lint.check_cast_ownership(blk, args, pol, target="ok") == []


def test_bf16_policy_allows_its_boundary_casts():
    pol = PrecisionPolicy.from_name("bf16")
    blk = _block(pol)
    args = jaxpr_lint.block_args(2, "shared", "bf16")
    assert jaxpr_lint.check_cast_ownership(blk, args, pol, target="ok") == []


def test_mutation_doubled_psum_fires_collective_checker():
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import compat_shard_map
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    x = jnp.zeros((4, 4))

    def once(xl):
        return jax.lax.psum(xl, "model")

    def twice(xl):  # one psum over budget
        return jax.lax.psum(jax.lax.psum(xl, "model"), "model")

    fn1 = compat_shard_map(once, mesh, in_specs=(P(),), out_specs=P())
    fn2 = compat_shard_map(twice, mesh, in_specs=(P(),), out_specs=P())
    assert jaxpr_lint.check_collective_budget(fn1, (x,), psums=1,
                                              target="ok") == []
    fs = jaxpr_lint.check_collective_budget(fn2, (x,), psums=1,
                                            target="mutant")
    assert len(fs) == 1 and fs[0].checker == "collective-budget"
    assert "traced 2 psum(s), want exactly 1" in fs[0].message


def test_mutation_foreign_collective_fires_collective_checker():
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import compat_shard_map
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("data",))
    x = jnp.zeros((4, 4))

    def gathers(xl):
        return jax.lax.all_gather(xl, "data")

    fn = compat_shard_map(gathers, mesh, in_specs=(P(),), out_specs=P(None))
    fs = jaxpr_lint.check_collective_budget(fn, (x,), psums=0,
                                            target="mutant")
    assert len(fs) == 1
    assert "all_gather" in fs[0].message


def test_mutation_scatter_budget_fires_both_ways():
    # ISSUE 8: the scattered-layout budget (psum_scatters per interior
    # layer, a single final psum). An interior layer that all-reduces
    # instead of scattering fires BOTH messages: one psum over budget,
    # one psum_scatter missing.
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import compat_shard_map
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    x = jnp.zeros((4, 4))

    def scatters(xl):
        return jax.lax.psum_scatter(xl, "model", scatter_dimension=0,
                                    tiled=True)

    def psums(xl):  # the psum layout leaking into a scattered budget
        return jax.lax.psum(xl, "model")

    ok = compat_shard_map(scatters, mesh, in_specs=(P(),),
                          out_specs=P("model"))
    bad = compat_shard_map(psums, mesh, in_specs=(P(),), out_specs=P())
    assert jaxpr_lint.check_collective_budget(
        ok, (x,), psums=0, psum_scatters=1, target="ok") == []
    fs = jaxpr_lint.check_collective_budget(
        bad, (x,), psums=0, psum_scatters=1, target="mutant")
    assert len(fs) == 2 and all(
        f.checker == "collective-budget" for f in fs)
    msgs = " | ".join(f.message for f in fs)
    assert "traced 1 psum(s), want exactly 0" in msgs
    assert "traced 0 psum_scatter(s), want exactly 1" in msgs


def test_resilient_serve_lint_clean_and_mutation():
    # ISSUE 9: the resilience trace contract. Clean: the ResilientServer
    # production step traces exactly num_layers pallas_calls while the
    # degraded XLA step traces ZERO. Mutation: a "fallback" that launches
    # the pallas path itself (defeating the whole point of degradation)
    # makes the degraded-step checker fire.
    import dataclasses

    from repro.core import fno as fno_mod
    from repro.train import serve_runtime as srt

    fs = jaxpr_lint.lint_resilient_serve(dtypes=("f32",))
    assert fs == [], fs

    cfg = dataclasses.replace(get_config("fno2d", reduced=True),
                              path="pallas", fuse_block=True)
    params = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda: fno_mod.init_fno(jax.random.PRNGKey(0),
                                                cfg)))
    rs = srt.ResilientServer(cfg, params, replicas=1, max_batch=2)
    xb = jnp.zeros((rs.primary.buckets[0], cfg.in_channels)
                   + tuple(cfg.spatial), jnp.float32)
    args = (params, {"x": xb})

    def kernel_launching_fallback(p, batch):  # the mutant degraded step
        return rs.primary.step_fn(p, batch)

    fs = jaxpr_lint.check_pallas_count(kernel_launching_fallback, args, 0,
                                       target="mutant fallback")
    assert len(fs) == 1 and fs[0].checker == "pallas-count"
    assert (f"traced {fno_mod.pallas_launches(cfg)} pallas_calls, want "
            f"exactly 0" in fs[0].message)


def _rollout_fixture():
    """Reduced fno2d fused server with zero params (tracing only — no
    kernels execute) plus a bucket-sized batch, for the rollout lints."""
    import dataclasses

    from repro.core import fno as fno_mod
    from repro.train import serve_fno_step as sfs

    cfg = dataclasses.replace(get_config("fno2d", reduced=True),
                              path="pallas", fuse_block=True)
    params = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda: fno_mod.init_fno(jax.random.PRNGKey(0),
                                                cfg)))
    server = sfs.FNOServer(cfg, params, max_batch=2)
    xb = jnp.zeros((server.buckets[0], cfg.in_channels)
                   + tuple(cfg.spatial), jnp.float32)
    return cfg, server, (params, {"x": xb})


def test_rollout_lint_clean_and_depth_invariant():
    # ISSUE 10: the rollout trace contract. The device-resident K-step
    # rollout is ONE lax.scan whose body traces once, so the pallas_call
    # count stays exactly one forward's (num_layers + 2 ends) for ANY
    # depth — pinned here for the acceptance K in {1, 4} via the sweep
    # entry point AND the raw checker. ``steps`` must be bound statically
    # (functools.partial) before tracing: a traced depth would abstract
    # the scan length.
    import functools

    from repro.core import fno as fno_mod

    fs = jaxpr_lint.lint_rollout(archs=("fno2d",), dtypes=("f32",),
                                 ks=(1, 4))
    assert fs == [], fs

    cfg, server, args = _rollout_fixture()
    for k in (1, 4):
        fn = functools.partial(server.rollout_step_fn, steps=k)
        assert jaxpr_lint.check_pallas_count(
            fn, args, fno_mod.pallas_launches(cfg),
            target=f"rollout K={k}") == []
        assert jaxpr_lint.check_cast_ownership(
            fn, args, cfg.precision, target=f"rollout K={k}") == []


def test_mutation_unrolled_rollout_fires_count_checker():
    # The mutant the contract exists to kill: a python-loop rollout
    # re-traces the whole network every step, so K=4 launches
    # K * num_layers kernels (and recompiles per depth). The count
    # checker must fire with the exact inflated count.
    from repro.core import fno as fno_mod

    cfg, _, args = _rollout_fixture()

    def unrolled(p, batch):  # the staged loop masquerading as a rollout
        x = batch["x"]
        for _ in range(4):
            y = fno_mod.apply_fno(p, cfg, x, path="pallas")
            x = jnp.concatenate([y, x[:, cfg.out_channels:].astype(y.dtype)],
                                axis=1)
        return x[:, :cfg.out_channels]

    want = fno_mod.pallas_launches(cfg)
    fs = jaxpr_lint.check_pallas_count(unrolled, args, want,
                                       target="unrolled rollout")
    assert len(fs) == 1 and fs[0].checker == "pallas-count"
    assert (f"traced {4 * want} pallas_calls, want exactly {want}"
            in fs[0].message)


def test_mutation_psum_layout_fails_scatter_budget(subproc):
    # End-to-end mutation on the REAL serve path: hold the legacy psum
    # layout to the scattered layout's budget — both messages fire
    # (num_layers psums where 1 is allowed, zero interior scatters).
    subproc("""
    import sys
    sys.path.insert(0, {src!r})
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.mesh import make_debug_mesh
    from repro.distributed import sharding as shd
    from repro.core import fno as fno_mod
    from repro.analysis import jaxpr_lint as jl

    cfg = dataclasses.replace(get_config("fno2d", reduced=True),
                              path="pallas", fuse_block=True,
                              tp_layout="psum")
    params = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda: fno_mod.init_fno(jax.random.PRNGKey(0),
                                                cfg)))
    x = jnp.zeros((8, cfg.in_channels) + tuple(cfg.spatial))
    ctx = shd.make_context(cfg, make_debug_mesh(4, 2), kind="serve")
    def fwd(p, xx):
        with shd.sharding_context(ctx):
            return fno_mod.apply_fno(p, cfg, xx, path="pallas")
    L = cfg.num_layers
    fs = jl.check_collective_budget(fwd, (params, x), psums=1,
                                    psum_scatters=L - 1, target="mutant")
    assert len(fs) == 2, fs
    msgs = " | ".join(f.message for f in fs)
    assert f"traced {{L}} psum(s), want exactly 1" in msgs, msgs
    assert f"traced 0 psum_scatter(s), want exactly {{L - 1}}" in msgs, msgs
    print("psum-layout-vs-scattered-budget mutation OK")
    """.format(src=os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")))


# ---------------------------------------------------------------------------
# AST-lint mutations (tmp files, scanned with the tmp dir as root)
# ---------------------------------------------------------------------------
def _lint_snippet(tmp_path, rel, code):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(code))
    return ast_lint.run_ast_lints(root=tmp_path)


def test_mutation_raw_shard_map_import_fires(tmp_path):
    fs = _lint_snippet(tmp_path, "distributed/rogue.py", """
        import jax
        f = jax.shard_map(lambda x: x, mesh=None, in_specs=(),
                          out_specs=())
        """)
    assert len(fs) == 1 and fs[0].checker == "compat-shard-map"
    assert "compat_shard_map" in fs[0].message
    assert fs[0].target == "distributed/rogue.py:3"


def test_shard_map_home_is_exempt(tmp_path):
    fs = _lint_snippet(tmp_path, "distributed/sharding.py", """
        import jax
        f = jax.shard_map(lambda x: x, mesh=None, in_specs=(),
                          out_specs=())
        """)
    assert fs == []


def test_mutation_bare_pallas_call_fires(tmp_path):
    fs = _lint_snippet(tmp_path, "kernels/rogue.py", """
        import jax
        from jax.experimental import pallas as pl

        def call(x):
            return pl.pallas_call(lambda i, o: None, grid=(1,),
                                  out_shape=x)(x)
        """)
    assert len(fs) == 1 and fs[0].checker == "pallas-compiler-params"
    assert "_compiler_params" in fs[0].message


def test_pallas_call_through_shim_passes(tmp_path):
    fs = _lint_snippet(tmp_path, "kernels/fine.py", """
        from jax.experimental import pallas as pl
        from repro.kernels import _compiler_params

        def call(x):
            return pl.pallas_call(
                lambda i, o: None, grid=(1,), out_shape=x,
                compiler_params=_compiler_params(
                    dimension_semantics=("parallel",)))(x)
        """)
    assert fs == []


def test_mutation_raw_fft_fires(tmp_path):
    fs = _lint_snippet(tmp_path, "kernels/rogue_fft.py", """
        import jax.numpy as jnp

        def fwd(x):
            return jnp.fft.rfft(x, axis=-1)
        """)
    assert len(fs) == 1 and fs[0].checker == "no-raw-fft"


def test_mutation_dtype_literal_fires_and_pragma_allows(tmp_path):
    bad = _lint_snippet(tmp_path, "kernels/ops.py", """
        import jax.numpy as jnp

        def sneaky(x):
            return x.astype(jnp.float32)
        """)
    assert len(bad) == 1 and bad[0].checker == "dtype-literal"
    assert "sneaky" in bad[0].message

    ok = _lint_snippet(tmp_path, "kernels/ops.py", """
        import jax.numpy as jnp

        def sneaky(x):
            return x.astype(jnp.float32)  # lint: allow-dtype
        """)
    assert ok == []


def test_dtype_literal_ignored_outside_scope(tmp_path):
    fs = _lint_snippet(tmp_path, "models/free.py", """
        import jax.numpy as jnp

        def fine(x):
            return x.astype(jnp.float32)
        """)
    assert fs == []


# ---------------------------------------------------------------------------
# registry + vmem mutations
# ---------------------------------------------------------------------------
def test_mutation_registry_gap_fires(monkeypatch):
    import repro.configs as configs

    real = list(configs.runnable_cells())

    def with_empty_reason():  # skipped cell with a blank reason
        yield from real[:-1]
        a, s, _ = real[-1]
        yield a, s, "   "

    monkeypatch.setattr(configs, "runnable_cells", with_empty_reason)
    fs = ast_lint.check_config_registry()
    assert any(f.checker == "config-registry" and "EMPTY" in f.message
               for f in fs)

    def missing_arch():  # an arch the grid never enumerates
        yield from (row for row in real if row[0] != "fno2d-large")

    monkeypatch.setattr(configs, "runnable_cells", missing_arch)
    fs = ast_lint.check_config_registry()
    assert any(f.target == "fno2d-large"
               and "never enumerated" in f.message for f in fs)


def test_mutation_oversized_launch_fires_vmem_checker():
    big = FNOConfig(name="fno2d-absurd", ndim=2, hidden=512, num_layers=1,
                    in_channels=1, out_channels=1, spatial=(256, 256),
                    modes=(64, 64), weight_mode="per_mode")
    fs = vmem.check_vmem(configs=[(big, True)], dtypes=("f32",),
                         variants=("full",))
    assert fs and all(f.checker == "vmem-budget" for f in fs)
    assert errors(fs), "must-fit config over budget must be an error"


def test_launch_estimates_report_all_kernels():
    est = vmem.block_launch_estimates(get_config("fno2d", reduced=True))
    assert set(est) == {"block_fwd", "gz_recompute", "dx_adjoint", "wgrad"}
    assert all(e.total_bytes > 0 for e in est.values())
    part = vmem.block_launch_estimates(get_config("fno2d", reduced=True),
                                       variant="partial")
    assert "core" in part and "block_fwd" not in part


def test_ends_launch_estimate_and_feasibility():
    # ISSUE 8: fuse_ends adds exactly one launch kind to the estimate set
    # (the ends-fused forward — backward re-stages, no new kernels). The
    # acca scratch [lift, bb, *spatial] dominates: reduced shapes fit,
    # the full-size 3D grid does not, and opting in surfaces that as a
    # vmem-budget error instead of a Mosaic failure mid-run.
    from repro.configs.fno import with_fuse_ends

    cfg = with_fuse_ends(get_config("fno2d", reduced=True))
    est = vmem.block_launch_estimates(cfg)
    assert "block_fwd_ends" in est
    e = est["block_fwd_ends"]
    assert 0 < e.total_bytes <= vmem.VMEM_BUDGET_BYTES
    assert e.scratch_bytes > est["block_fwd"].scratch_bytes  # + acca
    # without the flag the launch is absent (default sweeps unchanged)
    assert "block_fwd_ends" not in vmem.block_launch_estimates(
        get_config("fno2d", reduced=True))
    fs = vmem.check_vmem(configs=[with_fuse_ends(get_config("fno3d"))],
                         dtypes=("f32",), variants=("full",))
    assert any(f.target.endswith("block_fwd_ends") for f in fs), fs
    assert errors(fs)
