"""Comm/compute-overlap smoke (CI, scripts/check.sh — ISSUE 8).

The scattered TP layout's opt-in overlap mode (cfg.tp_overlap) replaces
each interior layer's one-shot psum_scatter with a ppermute ring
(distributed/sharding.ring_scatter_sum): tp-1 async chunk hops XLA can
hide under the neighboring layers' k-loop compute. Same math, same
sharding, same wire bytes — only the schedule changes. This smoke pins
that contract on a forced dp2×tp4 CPU mesh:

  * the ring forward matches the one-shot scattered forward (and the
    XLA oracle) to fused-kernel tolerance;
  * jax.grad flows through the ring natively (ppermute transposes to
    ppermute — no custom_vjp needed) and matches the one-shot grads;
  * the traced collective plan is exactly (tp-1) ppermutes per INTERIOR
    layer, ZERO reduce-scatters, and the final layer's single psum.

Pure CPU, seconds — the interpret-mode kernels execute on tiny reduced
shapes. The modeled wire-byte claim (0.5x per interior layer vs the psum
layout, unchanged by the ring) lives in roofline.analysis
.fno_collective_bytes.
"""
import os
import sys

# Virtual devices for the DP×TP mesh — MUST precede any jax import.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.analysis import jaxpr_lint as jl
    from repro.configs import get_config
    from repro.core import fno as fno_mod
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_mesh

    dp, tp = 2, 4
    assert jax.device_count() >= dp * tp, (
        f"needs {dp * tp} devices, have {jax.device_count()} — run with "
        f"XLA_FLAGS=--xla_force_host_platform_device_count={dp * tp}")
    cfg0 = dataclasses.replace(get_config("fno2d", reduced=True),
                               path="pallas", fuse_block=True)
    L = cfg0.num_layers
    key = jax.random.PRNGKey(0)
    params = fno_mod.init_fno(key, cfg0)
    x = jax.random.normal(key, (8, cfg0.in_channels) + tuple(cfg0.spatial))
    y_ref = fno_mod.apply_fno(params, cfg0, x, path="xla")
    mesh = make_mesh((dp, tp), ("data", "model"))

    outs, grads, colls = {}, {}, {}
    for overlap in (False, True):
        cfg = dataclasses.replace(cfg0, tp_layout="scatter",
                                  tp_overlap=overlap)
        ctx = shd.make_context(cfg, mesh, kind="serve")
        assert ctx.model_axis == "model", ctx

        # fresh closures per variant: jax.make_jaxpr caches on function
        # identity + avals and cannot see the thread-local context
        def fwd(p, xx, _cfg=cfg, _ctx=ctx):
            with shd.sharding_context(_ctx):
                return fno_mod.apply_fno(p, _cfg, xx, path="pallas")

        name = "ring" if overlap else "oneshot"
        outs[name] = jax.jit(fwd)(params, x)
        grads[name] = jax.jit(jax.grad(
            lambda p, xx, _f=fwd: jnp.sum(_f(p, xx) ** 2)))(params, x)
        colls[name] = jl.collective_counts(fwd, params, x)

    err_ref = float(jnp.abs(outs["ring"] - y_ref).max())
    err_one = float(jnp.abs(outs["ring"] - outs["oneshot"]).max())
    assert err_ref < 2e-4, f"ring vs XLA oracle: {err_ref}"
    assert err_one < 1e-5, f"ring vs one-shot scatter: {err_one}"
    gerr = max(float(jnp.abs(a - b).max()) for a, b in zip(
        jax.tree_util.tree_leaves(grads["ring"]),
        jax.tree_util.tree_leaves(grads["oneshot"])))
    assert gerr < 1e-4, f"ring grads vs one-shot: {gerr}"

    one = colls["oneshot"]
    ring = colls["ring"]
    rs = one.get("reduce_scatter", 0) + one.get("psum_scatter", 0)
    assert rs == L - 1 and one.get("psum", 0) == 1, one
    assert ring.get("ppermute", 0) == (tp - 1) * (L - 1), ring
    assert ring.get("reduce_scatter", 0) == 0 and \
        ring.get("psum_scatter", 0) == 0, ring
    assert ring.get("psum", 0) == 1, ring

    print(f"overlap smoke OK: dp{dp}xtp{tp}, ring=ppermute x "
          f"{(tp - 1) * (L - 1)} (interior) + 1 final psum, "
          f"fwd_err={err_one:.2e} grad_err={gerr:.2e}")


if __name__ == "__main__":
    main()
