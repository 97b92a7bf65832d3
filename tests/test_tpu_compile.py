"""The fused FNO block compiled for a described TPU v5e, no chip attached.

Interpret mode (every other kernel test) runs the Pallas kernels as plain
XLA ops on the CPU, so it never sees Mosaic's tiling rule, its matmul
forms, or its VMEM limit. These tests hand the TPU compiler the
full-width block forward and its gradient with ``interpret=False`` and
assert that a Mosaic kernel (``tpu_custom_call``) comes out for every
launch: one for the forward, four under ``jax.value_and_grad``
(block_fwd, gz_recompute, dx_adjoint, wgrad), each instruction named for
its launch kind, and the model ends' ``end_mlp`` launches at the served
shapes. The whole fno2d serve step is compiled once more, to hold the
names the device trace reads: the kernels' launch names and the model
ends' named scopes.

Rank 3 runs in the engine's merged layout (``spectral.kernel_layout``):
the forward of fno3d and of Li et al.'s fno3d-ns compiles; their weight
gradient does not yet (the rank-3 wgrad kernel flattens its spectrum in
a reshape Mosaic refuses, ROADMAP S2), so rank 3 is compiled forward only.

The topology is described inside a module-scoped fixture, never at import
time: only one process may hold the TPU library, and pytest-xdist workers
all import every test file.
"""
import collections
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.configs.base import PrecisionPolicy
from repro.kernels import LAUNCH_NAMES, ops

# One instruction line of compiled HLO: "%name = <shape> opcode(...)",
# where the shape may be a tuple.
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+) = "
                    r"(?P<shape>.*?) (?P<op>[a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="(?P<path>[^"]*)"')
_BYTES = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "pred": 1}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-chip executable is written to the persistent cache but
    # can never be read back without a chip: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _block_shapes(cfg, pol, sharding, batch=8):
    """Shapes of one full-width block's operands, placed on the chip."""
    h = cfg.hidden
    w = (h, h) + (cfg.kept_modes if cfg.weight_mode == "per_mode" else ())
    sd = lambda shape, dt: jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                                sharding=sharding)
    return (sd((batch, h) + tuple(cfg.spatial), pol.compute_dtype),
            sd(w, pol.param_dtype), sd(w, pol.param_dtype),
            sd((h, h), pol.param_dtype), sd((h,), pol.param_dtype))


def _compiled_text(fn, args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernel_launches(text: str) -> collections.Counter:
    """Mosaic kernels of a compiled program, counted by launch name (the
    instruction name without its ".N")."""
    return collections.Counter(
        re.sub(r"\.\d+$", "", m.group("name"))
        for line in text.splitlines()
        if "tpu_custom_call" in line and (m := _INSTR.match(line)))


def _entry_ops(text: str):
    """(name, opcode, output bytes, op_name, label) of each instruction
    of the entry computation; the label is the innermost op_name
    component that is a launch name, "lift" or "projection", else None."""
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    out = []
    for line in entry.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        nbytes = sum(
            _BYTES.get(dt, 4) * math.prod(int(d) for d in dims.split(",")
                                          if d)
            for dt, dims in re.findall(r"(\w+)\[([\d,]*)\]",
                                       m.group("shape")))
        hit = _OP_NAME.search(line)
        path = hit.group("path") if hit else ""
        label = next((p for p in reversed(path.split("/"))
                      if p in LAUNCH_NAMES + ("lift", "projection")), None)
        out.append((m.group("name"), m.group("op"), nbytes, path, label))
    return out


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("arch", ["fno1d", "fno2d", "fno2d-large"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_fused_block_compiles_for_v5e(one_chip, arch, dtype, grad):
    cfg = get_config(arch)
    pol = PrecisionPolicy.from_name(dtype)
    args = _block_shapes(cfg, pol, one_chip)

    def block(x, wr, wi, wb, bias):
        return ops.fno_block_nd(x, wr, wi, wb, bias, cfg.modes,
                                path="pallas", variant="full",
                                policy=pol, interpret=False)

    if grad:
        def fn(x, *params):
            def loss(x, *params):
                return jnp.sum(block(x, *params).astype(jnp.float32))
            return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(
                x, *params)
    else:
        fn = block
    text = _compiled_text(fn, args)
    assert text.count("tpu_custom_call") == (4 if grad else 1), arch
    want = (["block_fwd", "gz_recompute", "dx_adjoint", "wgrad"] if grad
            else ["block_fwd"])
    assert _kernel_launches(text) == collections.Counter(want), arch


@pytest.mark.parametrize("arch", ["fno3d", "fno3d-ns"])
def test_rank3_block_forward_compiles_for_v5e(one_chip, arch):
    """A rank-3 block forward at full width, f32: one block_fwd kernel
    over the merged [s_1, s_2·s_3] layout; fno3d-ns with its own
    truncation (± corners), activation (ReLU) and per-mode weight."""
    cfg = get_config(arch)
    pol = PrecisionPolicy.from_name("f32")

    def block(x, wr, wi, wb, bias):
        return ops.fno_block_nd(x, wr, wi, wb, bias, cfg.modes,
                                path="pallas", variant="full", policy=pol,
                                act=cfg.activation,
                                truncation=cfg.truncation, interpret=False)

    text = _compiled_text(block, _block_shapes(cfg, pol, one_chip, batch=2))
    assert text.count("tpu_custom_call") == 1, arch
    assert _kernel_launches(text) == collections.Counter(["block_fwd"])


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
@pytest.mark.parametrize("arch", ["fno2d", "fno2d-large"])
def test_partial_block_compiles_for_v5e(one_chip, arch, dtype):
    """The paper-faithful partial fusion: the outer rDFT, the fused core
    (cDFT → CGEMM → icDFT) and the outer irDFT — three kernels."""
    cfg = get_config(arch)
    pol = PrecisionPolicy.from_name(dtype)

    def block(x, wr, wi, wb, bias):
        return ops.fno_block_nd(x, wr, wi, wb, bias, cfg.modes,
                                path="pallas", variant="partial",
                                policy=pol, interpret=False)

    text = _compiled_text(block, _block_shapes(cfg, pol, one_chip))
    assert text.count("tpu_custom_call") == 3, arch
    assert _kernel_launches(text) == collections.Counter(
        ["rdft", "core", "irdft"]), arch


def test_serve_step_names_its_work_for_v5e(one_chip, monkeypatch):
    """The fno2d serve step at batch 8, as the fused production path runs
    it: the four block kernels are block_fwd launches and the two staged
    ends end_mlp launches, every large XLA op around them carries a
    launch name or the scope of a model end, the two end_mlp launches
    (the same kernel, told apart only by their scopes) carry the lift and
    the projection scope, and no activation-sized copy relays the last
    block's output for the projection."""
    from repro.core import fno
    from repro.train.serve_fno_step import make_fno_serve_step

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("fno2d"), path="pallas",
                              fuse_block=True)
    place = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip)
    params = jax.tree.map(place, jax.eval_shape(
        lambda k: fno.init_fno(k, cfg), jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((8, cfg.in_channels) + tuple(cfg.spatial),
                             jnp.float32, sharding=one_chip)
    text = _compiled_text(make_fno_serve_step(cfg), (params, {"x": x}))

    assert _kernel_launches(text) == collections.Counter(
        {"block_fwd": cfg.num_layers, "end_mlp": 2})
    ops_ = _entry_ops(text)
    large = [(name, op, label) for name, op, nbytes, _, label in ops_
             if op in ("fusion", "copy", "custom-call")
             and nbytes >= 2 ** 20]
    assert large and all(label for *_, label in large), large
    assert not [n for n, op, _ in large if op == "copy"], large
    ends = {scope for name, _, _, path, _ in ops_
            if name.startswith("end_mlp")
            for scope in ("lift", "projection")
            if f"/{scope}/" in path}
    assert ends == {"lift", "projection"}, ends


@pytest.mark.parametrize("arch,batch", [("fno2d", 64), ("fno3d-ns", 4),
                                        ("fno3d-ns", 32)])
def test_end_mlp_compiles_for_v5e(one_chip, monkeypatch, arch, batch):
    """The lift and the projection of each benchmark model as end_mlp
    launches at their served shapes (fno3d-ns's 4-sample chunk and its
    whole request), f32 at "highest": the six bf16 products stacked along
    the contraction are Mosaic-legal, one kernel per end."""
    from repro.core import fno

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = get_config(arch)
    ksp = fno.input_layout(dataclasses.replace(cfg, path="pallas",
                                               fuse_block=True))
    sd = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,  # noqa
                                            sharding=one_chip)
    inner = cfg.lifting_dim or 2 * cfg.hidden
    lift_w = ([cfg.in_channels, cfg.hidden] if cfg.lift_layers == 1 else
              [cfg.in_channels, inner, cfg.hidden])
    ends = {"lift": (cfg.in_channels, lift_w),
            "projection": (cfg.hidden, [cfg.hidden, inner,
                                        cfg.out_channels])}
    for end, (cin, widths) in ends.items():
        layers = tuple((sd((a, b)), sd((b,)))
                       for a, b in zip(widths[:-1], widths[1:]))

        def fn(x, layers):
            return ops.end_mlp(x, layers, cfg.activation, interpret=False)

        with jax.default_matmul_precision("highest"):
            text = _compiled_text(fn, (sd((batch, cin) + ksp), layers))
        assert _kernel_launches(text) == collections.Counter(
            ["end_mlp"]), (arch, end)
