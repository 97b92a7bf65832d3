"""Trace-level contract lints over production entry points.

Each checker traces a function with ``jax.make_jaxpr`` and walks the
launch-level jaxpr (``roofline.hlo_counter.iter_jaxpr_eqns`` with
``into_kernels=False`` — pallas_call bodies are opaque, exactly the level
the contracts are stated at):

  * **pallas-count** — the fusion contract (ROADMAP.md, DESIGN.md §3.4):
    one FNO block forward on the full-fusion path == ONE pallas_call,
    jax.grad of the block == exactly FOUR (fwd + gz recompute + dx adjoint
    + extended wgrad), a fused model forward / serve step == num_layers.
  * **cast-ownership** — DESIGN.md §4: launch-level
    ``convert_element_type`` ops between float dtypes may only move
    between the dtypes the active ``PrecisionPolicy`` names (so the f32
    preset admits NO float↔float casts, the bf16 preset only f32↔bf16);
    anything else is a stray cast that would silently change numerics.
  * **collective-budget** — DESIGN.md §6: on the scattered TP layout one
    ``psum_scatter`` per interior layer emits the next layer's hidden
    shard and only the FINAL layer completes with a ``psum``; the legacy
    psum layout budgets one ``psum`` per layer; pure DP budgets zero of
    either; and never an explicit all_gather / all_to_all / ppermute on
    the FNO forward or serve path (the opt-in ring-overlap variant, which
    trades the one psum_scatter for tp-1 ppermutes, is smoke-checked by
    ``scripts/overlap_smoke.py`` rather than budgeted here).

``lint_*`` drivers sweep the production matrix (ranks 1-3 × weight
layouts × fusion variants × f32/bf16 × DP/TP); ``scripts/lint.py`` is the
CLI. ``fused_block_contract`` / ``serve_step_contract`` are the thin
wrappers behind ``scripts/fused_block_smoke.py`` and the serve driver's
inline assert.
"""
from __future__ import annotations

import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.analysis import Finding
from repro.configs.base import PrecisionPolicy
from repro.roofline.hlo_counter import iter_jaxpr_eqns

# Explicit cross-device primitives a trace can contain. GSPMD-inserted
# collectives (post-trace) are invisible here by design: the contract
# governs the collectives the code *asks for*, i.e. the shard_map psum.
COLLECTIVE_PRIMS = ("psum", "all_gather", "all_to_all", "ppermute",
                    "reduce_scatter")

DTYPES = ("f32", "bf16")
LAYOUTS = ("shared", "per_mode")
VARIANTS = ("full", "partial")


# ---------------------------------------------------------------------------
# jaxpr walkers
# ---------------------------------------------------------------------------
def launch_eqns(fn, *args, **kwargs) -> list:
    """All launch-level eqns of fn(*args, **kwargs) (pallas_call opaque)."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    return list(iter_jaxpr_eqns(closed.jaxpr, into_kernels=False))


def pallas_count(fn, *args, **kwargs) -> int:
    return sum(1 for e in launch_eqns(fn, *args, **kwargs)
               if e.primitive.name == "pallas_call")


def float_casts(fn, *args, **kwargs) -> List[Tuple[str, str]]:
    """Launch-level float→float ``convert_element_type`` (src, dst) dtype
    name pairs. Same-dtype and int/bool converts are not casts in the
    cast-ownership sense and are dropped."""
    out: List[Tuple[str, str]] = []
    for eqn in launch_eqns(fn, *args, **kwargs):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = jnp.dtype(eqn.invars[0].aval.dtype)
        dst = jnp.dtype(eqn.params["new_dtype"])
        if src == dst:
            continue
        if not (jnp.issubdtype(src, jnp.floating)
                and jnp.issubdtype(dst, jnp.floating)):
            continue
        out.append((src.name, dst.name))
    return out


def collective_counts(fn, *args, **kwargs) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for eqn in launch_eqns(fn, *args, **kwargs):
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            counts[name] = counts.get(name, 0) + 1
    return counts


def allowed_casts(policy: PrecisionPolicy) -> frozenset:
    """The float↔float cast pairs a policy legitimizes: any move between
    the dtypes the policy itself names (plus f32 — master weights and the
    loss reduction are always f32, DESIGN.md §4). The f32 preset therefore
    allows NO float casts; bf16 allows exactly f32↔bf16."""
    ds = {policy.param_dtype, policy.compute_dtype, policy.spectral_dtype,
          policy.accum_dtype, policy.grad_acc_dtype, "float32"}
    ds = {jnp.dtype(d).name for d in ds}
    return frozenset((a, b) for a in ds for b in ds if a != b)


# ---------------------------------------------------------------------------
# checkers
# ---------------------------------------------------------------------------
def check_pallas_count(fn, args: Sequence, want: int, *, target: str,
                       kwargs: Optional[dict] = None) -> List[Finding]:
    got = pallas_count(fn, *args, **(kwargs or {}))
    if got == want:
        return []
    return [Finding(
        "pallas-count", target,
        f"traced {got} pallas_calls, want exactly {want} — the fusion "
        f"contract (one fused kernel per block fwd, 4 per grad, one per "
        f"layer at model level) is broken")]


def check_cast_ownership(fn, args: Sequence, policy: PrecisionPolicy, *,
                         target: str,
                         kwargs: Optional[dict] = None) -> List[Finding]:
    allowed = allowed_casts(policy)
    bad = [c for c in float_casts(fn, *args, **(kwargs or {}))
           if c not in allowed]
    if not bad:
        return []
    uniq = sorted(set(bad))
    shown = ", ".join(f"{s}->{d}" for s, d in uniq)
    return [Finding(
        "cast-ownership", target,
        f"{len(bad)} stray launch-level float cast(s) outside the "
        f"PrecisionPolicy boundaries: {shown} (policy allows "
        f"{sorted(set(a for a, _ in allowed)) or ['no float casts']}; "
        f"see DESIGN.md §4 for who owns each cast)")]


def check_collective_budget(fn, args: Sequence, *, psums: int, target: str,
                            psum_scatters: int = 0,
                            kwargs: Optional[dict] = None) -> List[Finding]:
    """Budget the explicit collectives a traced path may contain.

    psums: full all-reduces (one per TP layer on the psum layout; exactly
    one — the final layer's — on the scattered layout). psum_scatters:
    reduce-scatters emitting the next layer's hidden shard (one per
    INTERIOR TP layer on the scattered layout, zero otherwise).
    ``lax.psum_scatter`` traces as the ``reduce_scatter`` primitive,
    which is what the budget counts. Anything
    else (all_gather, all_to_all, ppermute) is unexpected on the FNO
    forward/serve path.
    """
    counts = collective_counts(fn, *args, **(kwargs or {}))
    findings = []
    got = counts.pop("psum", 0)
    if got != psums:
        findings.append(Finding(
            "collective-budget", target,
            f"traced {got} psum(s), want exactly {psums} (scattered "
            f"layout: only the final TP layer psums; psum layout: one per "
            f"TP layer; zero under pure DP — DESIGN.md §6)"))
    got_rs = counts.pop("reduce_scatter", 0)
    if got_rs != psum_scatters:
        findings.append(Finding(
            "collective-budget", target,
            f"traced {got_rs} psum_scatter(s), want exactly "
            f"{psum_scatters} (one per INTERIOR TP layer on the scattered "
            f"layout, emitting the next layer's hidden shard — "
            f"DESIGN.md §6)"))
    if counts:
        shown = ", ".join(f"{k}×{v}" for k, v in sorted(counts.items()))
        findings.append(Finding(
            "collective-budget", target,
            f"unexpected collective(s) on a path budgeted for psum/"
            f"psum_scatter only: {shown}"))
    return findings


# ---------------------------------------------------------------------------
# production entry-point builders (tiny shapes — these only trace)
# ---------------------------------------------------------------------------
_SPATIAL = {1: (16,), 2: (8, 8), 3: (8, 6, 6)}
_MODES = {1: (5,), 2: (3, 4), 3: (2, 3, 2)}


def _policy(dtype: str) -> PrecisionPolicy:
    return PrecisionPolicy.from_name(dtype)


def block_args(rank: int, layout: str, dtype: str):
    """(x, wr, wi, wb, bias) for one fno_block_nd trace at production
    boundary dtypes: x at the compute dtype (apply_fno casts the input
    once at the top), weights at the param dtype (master weights)."""
    pol = _policy(dtype)
    cp = jnp.dtype(pol.compute_dtype)
    pp = jnp.dtype(pol.param_dtype)
    b, h, o = 2, 4, 4
    modes = _MODES[rank]
    wshape = (o, h) + (modes if layout == "per_mode" else ())
    x = jnp.zeros((b, h) + _SPATIAL[rank], cp)
    wr = jnp.zeros(wshape, pp)
    wi = jnp.zeros(wshape, pp)
    wb = jnp.zeros((o, h), pp)
    bias = jnp.zeros((o,), pp)
    return x, wr, wi, wb, bias


def expected_block_calls(rank: int, variant: str,
                         layer: str = "block") -> Tuple[int, int]:
    """(fwd, grad) pallas_call counts for one block, or for the spectral
    layer alone (``layer="spectral"``). Full fusion is one kernel; the
    paper-faithful partial variant runs outer-fwd + core + outer-inv for
    rank ≥ 2 (rank 1 has no outer stages). The backward is always the
    fused adjoint: gz recompute + dx + extended wgrad = +3 for the block;
    the linear spectral layer needs no recompute, dx + wgrad = +2."""
    fwd = 1 if (variant == "full" or rank == 1) else 3
    return fwd, fwd + (3 if layer == "block" else 2)


def lint_block_matrix(ranks: Sequence[int] = (1, 2, 3),
                      layouts: Sequence[str] = LAYOUTS,
                      variants: Sequence[str] = VARIANTS,
                      dtypes: Sequence[str] = DTYPES,
                      layers: Sequence[str] = ("block", "spectral")
                      ) -> List[Finding]:
    """fwd + grad of ``ops.fno_block_nd`` and of ``ops.spectral_layer_nd``
    (the same custom_vjp with no bypass) across the whole single-device
    matrix: pallas counts and cast ownership."""
    from repro.kernels import ops

    findings: List[Finding] = []
    for layer, rank, layout, variant, dtype in itertools.product(
            layers, ranks, layouts, variants, dtypes):
        entry = "fno_block_nd" if layer == "block" else "spectral_layer_nd"
        target = f"{entry} r{rank}/{layout}/{variant}/{dtype}"
        pol = _policy(dtype)
        modes = _MODES[rank]
        args = block_args(rank, layout, dtype)
        if layer == "spectral":
            args = args[:3]
        fn = lambda *a: getattr(ops, entry)(  # noqa: E731
            *a, modes, path="pallas", variant=variant, policy=pol)
        loss = lambda *a: jnp.sum(fn(*a) ** 2)  # noqa: E731
        grad = lambda *a: jax.grad(  # noqa: E731
            loss, argnums=tuple(range(len(a))))(*a)
        want_fwd, want_grad = expected_block_calls(rank, variant, layer)
        findings += check_pallas_count(fn, args, want_fwd,
                                       target=f"{target} fwd")
        findings += check_pallas_count(grad, args, want_grad,
                                       target=f"{target} grad")
        findings += check_cast_ownership(fn, args, pol,
                                         target=f"{target} fwd")
        findings += check_cast_ownership(grad, args, pol,
                                         target=f"{target} grad")
    return findings


def lint_model(archs: Sequence[str] = ("fno1d", "fno2d", "fno3d"),
               dtypes: Sequence[str] = DTYPES) -> List[Finding]:
    """Whole fused-model forward (``apply_fno`` with fuse_block): exactly
    num_layers pallas_calls and policy-clean casts."""
    from repro.configs import get_config
    from repro.configs.fno import with_fuse_block, with_precision
    from repro.core import fno as fno_mod

    findings: List[Finding] = []
    for arch, dtype in itertools.product(archs, dtypes):
        cfg = with_fuse_block(
            with_precision(get_config(arch, reduced=True), dtype), True)
        target = f"apply_fno {arch}/fuse_block/{dtype}"
        params = jax.eval_shape(lambda: fno_mod.init_fno(
            jax.random.PRNGKey(0), cfg))
        params = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), params)
        x = jnp.zeros((2, cfg.in_channels) + tuple(cfg.spatial))
        model = lambda p, xx: fno_mod.apply_fno(  # noqa: E731
            p, cfg, xx, path="pallas")
        findings += check_pallas_count(model, (params, x), cfg.num_layers,
                                       target=target)
        findings += check_cast_ownership(model, (params, x), cfg.precision,
                                         target=target)
    return findings


def _mesh_or_finding(dp: int, tp: int, target: str):
    from repro.launch.mesh import make_mesh
    need = dp * tp
    if jax.device_count() < need:
        return None, [Finding(
            "collective-budget", target,
            f"skipped: needs {need} devices, have {jax.device_count()} "
            f"(run under XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{need}, as scripts/lint.py does)", severity="warn")]
    return make_mesh((dp, tp), ("data", "model")), []


def lint_sharded_blocks(mesh_grids: Sequence[Tuple[int, int]] = ((8, 1),
                                                                 (4, 2)),
                        dtypes: Sequence[str] = DTYPES,
                        layouts: Sequence[str] = ("psum", "scatter")
                        ) -> List[Finding]:
    """``fno_block_nd_sharded`` under DP and DP×TP, both TP layouts: still
    one pallas_call per shard, exactly one psum (psum layout) or exactly
    one psum_scatter (scattered layout) iff TP is on, policy-clean
    casts."""
    from repro.kernels import ops

    findings: List[Finding] = []
    for (dp, tp), dtype, layout in itertools.product(mesh_grids, dtypes,
                                                     layouts):
        if tp == 1 and layout != layouts[0]:
            continue  # layouts coincide under pure DP — lint once
        target = f"fno_block_nd_sharded dp{dp}xtp{tp}/{dtype}/{layout}"
        mesh, fs = _mesh_or_finding(dp, tp, target)
        findings += fs
        if mesh is None:
            continue
        pol = _policy(dtype)
        rank = 2
        modes = _MODES[rank]
        x, wr, wi, wb, bias = block_args(rank, "shared", dtype)
        x = jnp.zeros((dp * 2,) + x.shape[1:], x.dtype)  # batch % dp == 0
        fn = lambda *a: ops.fno_block_nd_sharded(  # noqa: E731
            *a, modes, mesh=mesh, batch_axes=("data",),
            model_axis="model", policy=pol, tp_layout=layout)
        args = (x, wr, wi, wb, bias)
        scat = layout == "scatter" and tp > 1
        findings += check_pallas_count(fn, args, 1, target=target)
        findings += check_collective_budget(
            fn, args, psums=1 if (tp > 1 and not scat) else 0,
            psum_scatters=1 if scat else 0, target=target)
        findings += check_cast_ownership(fn, args, pol, target=target)
    return findings


def lint_serve(arch: str = "fno2d",
               mesh_grids: Sequence[Tuple[int, int]] = ((8, 1), (4, 2)),
               dtypes: Sequence[str] = DTYPES,
               layouts: Sequence[str] = ("scatter", "psum")
               ) -> List[Finding]:
    """``FNOServer.step_fn`` through the shard_map dispatch, both TP
    layouts: num_layers pallas_calls; on the scattered layout one
    psum_scatter per interior layer and ONE psum on the final layer, on
    the psum layout one psum per layer (iff TP); zero all-gathers, clean
    casts."""
    from repro.configs import get_config
    from repro.configs.fno import with_precision
    from repro.core import fno as fno_mod
    from repro.distributed import sharding as shd
    from repro.train import serve_fno_step as sfs

    findings: List[Finding] = []
    for (dp, tp), dtype, layout in itertools.product(mesh_grids, dtypes,
                                                     layouts):
        if tp == 1 and layout != layouts[0]:
            continue  # layouts coincide under pure DP — lint once
        target = f"FNOServer.step_fn {arch} dp{dp}xtp{tp}/{dtype}/{layout}"
        mesh, fs = _mesh_or_finding(dp, tp, target)
        findings += fs
        if mesh is None:
            continue
        cfg = with_precision(get_config(arch, reduced=True), dtype)
        import dataclasses
        cfg = dataclasses.replace(cfg, path="pallas", fuse_block=True,
                                  tp_layout=layout)
        ctx = shd.make_context(cfg, mesh, kind="serve")
        params = fno_mod.init_fno(jax.random.PRNGKey(0), cfg)
        server = sfs.FNOServer(cfg, params, ctx=ctx, max_batch=2)
        xb = jnp.zeros((server.buckets[0], cfg.in_channels)
                       + tuple(cfg.spatial), jnp.float32)
        args = (params, {"x": xb})
        tp_on = ctx.model_axis is not None
        scat = tp_on and layout == "scatter"
        findings += check_pallas_count(server.step_fn, args, cfg.num_layers,
                                       target=target)
        findings += check_collective_budget(
            server.step_fn, args,
            psums=(1 if scat else cfg.num_layers) if tp_on else 0,
            psum_scatters=cfg.num_layers - 1 if scat else 0, target=target)
        findings += check_cast_ownership(server.step_fn, args,
                                         cfg.precision, target=target)
    return findings


def lint_rollout(archs: Sequence[str] = ("fno1d", "fno2d", "fno3d"),
                 dtypes: Sequence[str] = DTYPES,
                 ks: Sequence[int] = (1, 4)) -> List[Finding]:
    """The rollout trace contract (DESIGN.md §10): a K-step device-
    resident rollout (``FNOServer.rollout_step_fn`` — one ``lax.scan``
    whose body is the fused forward) traces EXACTLY ``num_layers``
    pallas_calls regardless of K, because the scan body traces once. An
    unrolled per-step loop would trace K × num_layers — K kernel-launch
    sets and K HBM round-trips of the carry — which is precisely the
    staged dispatch the rollout tier exists to eliminate. Casts stay
    policy-owned: the single carry cast at the top is the policy's own
    input cast, and every scan iteration reuses the carry dtype."""
    import dataclasses
    import functools

    from repro.configs import get_config
    from repro.configs.fno import with_precision
    from repro.core import fno as fno_mod
    from repro.train import serve_fno_step as sfs

    findings: List[Finding] = []
    for arch, dtype in itertools.product(archs, dtypes):
        cfg = dataclasses.replace(
            with_precision(get_config(arch, reduced=True), dtype),
            path="pallas", fuse_block=True)
        params = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(lambda: fno_mod.init_fno(
                jax.random.PRNGKey(0), cfg)))
        server = sfs.FNOServer(cfg, params, max_batch=2)
        xb = jnp.zeros((server.buckets[0], cfg.in_channels)
                       + tuple(cfg.spatial), jnp.float32)
        args = (params, {"x": xb})
        for k in ks:
            target = f"FNOServer.rollout_step_fn {arch}/{dtype} K={k}"
            fn = functools.partial(server.rollout_step_fn, steps=k)
            findings += check_pallas_count(fn, args, cfg.num_layers,
                                           target=target)
            findings += check_cast_ownership(fn, args, cfg.precision,
                                             target=target)
    return findings


def lint_resilient_serve(arch: str = "fno2d",
                         dtypes: Sequence[str] = DTYPES) -> List[Finding]:
    """The resilience contract at trace level (DESIGN.md §9): the
    ``ResilientServer`` production step stays EXACTLY the fused serving
    step — num_layers pallas_calls, no extra collectives — while the
    degraded (XLA-oracle) step contains ZERO pallas_calls and zero
    explicit collectives. The fallback lives in its own jit entry; if it
    ever leaked into the hot trace (or started launching kernels itself)
    the degradation ladder would be serving the very path it is supposed
    to be a refuge from."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.fno import with_precision
    from repro.core import fno as fno_mod
    from repro.train import serve_runtime as srt

    findings: List[Finding] = []
    for dtype in dtypes:
        cfg = dataclasses.replace(
            with_precision(get_config(arch, reduced=True), dtype),
            path="pallas", fuse_block=True)
        params = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(lambda: fno_mod.init_fno(
                jax.random.PRNGKey(0), cfg)))
        rs = srt.ResilientServer(cfg, params, replicas=1, max_batch=2)
        xb = jnp.zeros((rs.primary.buckets[0], cfg.in_channels)
                       + tuple(cfg.spatial), jnp.float32)
        args = (params, {"x": xb})
        tgt = f"ResilientServer {arch}/{dtype}"
        findings += check_pallas_count(
            rs.primary.step_fn, args, cfg.num_layers,
            target=f"{tgt} production step")
        findings += check_pallas_count(
            rs.fallback.step_fn, args, 0, target=f"{tgt} degraded step")
        findings += check_collective_budget(
            rs.fallback.step_fn, args, psums=0,
            target=f"{tgt} degraded step")
        findings += check_cast_ownership(
            rs.fallback.step_fn, args, cfg.precision,
            target=f"{tgt} degraded step")
    return findings


# ---------------------------------------------------------------------------
# thin-wrapper entry points for the existing CI guards
# ---------------------------------------------------------------------------
def fused_block_contract() -> List[Finding]:
    """The PR-4 trace-count guard as framework checks: block fwd == 1,
    grad == 4, reduced fno2d fused model == num_layers pallas_calls
    (scripts/fused_block_smoke.py wraps this)."""
    findings = lint_block_matrix(ranks=(2,), layouts=("shared",),
                                 variants=("full",), dtypes=("f32",))
    findings += lint_model(archs=("fno2d",), dtypes=("f32",))
    return findings


def serve_step_contract(server, cfg) -> List[Finding]:
    """The serve driver's fusion-contract assert (one pallas_call per
    layer through the shard_map dispatch) as a framework check."""
    xb = jnp.zeros((server.buckets[0], cfg.in_channels)
                   + tuple(cfg.spatial), jnp.float32)
    return check_pallas_count(
        server.step_fn, (server.params, {"x": xb}), cfg.num_layers,
        target=f"{cfg.name} serve step")
