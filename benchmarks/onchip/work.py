"""The work an FNO step must do, from its shapes alone: operations, and the
least bytes it must move. Independent of how the program splits, fuses
or implements the layer, so a change to the kernels leaves it unchanged.

Operations are the truncated-DFT layer algebra (TurboFNO, arXiv:2504.11681
§3; each truncated transform as a dense matmul with the kept rows of the
DFT matrix): per sample and layer, for x [H, s_1..s_R] and modes k_j,

    rDFT along s_R     4·H·(pencils)·s_R·k_R     (real input)
    cDFT along s_j     8·H·(pencils)·s_j·k_j     (j = R-1 .. 1)
    CGEMM              8·(∏k)·H·O                (shared or per-mode)
    inverse chain      the same stages mirrored, with O channels
    bypass 1x1         2·(∏s)·H·O
    epilogue           12·(∏s)·O                 (bias, residual, GELU)

plus the lifting and projection MLPs. A training step is three forward
passes' worth (forward, and a backward of twice the forward); operations
recomputed by the program do not count.

Least bytes, per layer pass: each operand read once and each result
written once. A forward block reads its input activation and its weights
and writes its output. Its backward reads the output cotangent, the input
activation and the weights, and writes the input cotangent and the weight
gradients. DFT matrices are not counted: an FFT needs none.

``cfg`` is a configuration file's dict (``configs/<name>.json``).
"""
from __future__ import annotations

import math
from typing import Any, Dict

_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def _lift(cfg: Dict[str, Any]) -> int:
    return cfg.get("lifting_dim") or 2 * cfg["hidden"]


def block_flops(cfg: Dict[str, Any]) -> float:
    """Forward operations of one FNO block for one sample."""
    h = o = cfg["hidden"]
    spatial, modes = list(cfg["spatial"]), list(cfg["modes"])
    r = len(spatial)
    cur = list(spatial)

    def stage(ch, ax, real):
        pencils = math.prod(cur) // cur[ax]
        return (4 if real else 8) * ch * pencils * spatial[ax] * modes[ax]

    ops = stage(h, r - 1, True)
    cur[r - 1] = modes[r - 1]
    for ax in range(r - 2, -1, -1):
        ops += stage(h, ax, False)
        cur[ax] = modes[ax]
    ops += 8 * math.prod(modes) * h * o
    for ax in range(r - 1):
        ops += stage(o, ax, False)
        cur[ax] = spatial[ax]
    ops += stage(o, r - 1, True)
    sp = math.prod(spatial)
    return float(ops + 2 * sp * h * o + 12 * sp * o)


def ends_flops(cfg: Dict[str, Any]) -> float:
    """Forward operations of the lifting and projection MLPs, one sample."""
    h, lift = cfg["hidden"], _lift(cfg)
    sp = math.prod(cfg["spatial"])
    return float(2 * sp * (cfg["in_channels"] * lift + lift * h)
                 + 2 * sp * (h * lift + lift * cfg["out_channels"]))


def model_flops(cfg: Dict[str, Any], batch: int, *, training: bool) -> float:
    """Operations of a whole step over ``batch`` samples."""
    fwd = batch * (cfg["num_layers"] * block_flops(cfg) + ends_flops(cfg))
    return (3.0 if training else 1.0) * fwd


def blocks_flops(cfg: Dict[str, Any], batch: int, *, training: bool) -> float:
    """Operations of the FNO blocks alone over ``batch`` samples."""
    fwd = batch * cfg["num_layers"] * block_flops(cfg)
    return (3.0 if training else 1.0) * fwd


def block_weight_elems(cfg: Dict[str, Any]) -> int:
    """Weights of one block: complex spectral weight, bypass and bias."""
    h = o = cfg["hidden"]
    per = math.prod(cfg["modes"]) if cfg["weight_mode"] == "per_mode" else 1
    return 2 * h * o * per + h * o + o


def blocks_bytes(cfg: Dict[str, Any], batch: int, *, training: bool) -> float:
    """Least bytes the FNO blocks must move over ``batch`` samples."""
    act_b = _BYTES[cfg.get("dtype", "float32")]
    par_b = _BYTES[cfg.get("param_dtype", "float32")]
    sp = math.prod(cfg["spatial"])
    act = batch * cfg["hidden"] * sp * act_b  # one activation tensor
    w = block_weight_elems(cfg) * par_b
    per_layer = 2 * act + w  # read x and W, write y
    if training:
        per_layer += 3 * act + 2 * w  # read gy, x, W; write dx, dW
    return float(cfg["num_layers"] * per_layer)


def least_seconds(cfg: Dict[str, Any], batch: int, peak: Dict[str, float],
                  *, training: bool) -> Dict[str, Any]:
    """The least time the chip could take for the FNO blocks over
    ``batch`` samples: the larger of operations over peak FLOP/s and least
    bytes over HBM bandwidth, with the side that binds."""
    t_ops = blocks_flops(cfg, batch, training=training) / peak["flops_per_s"]
    t_mem = (blocks_bytes(cfg, batch, training=training)
             / peak["hbm_bytes_per_s"])
    return {"seconds": max(t_ops, t_mem),
            "bound": "compute" if t_ops >= t_mem else "memory",
            "ops_s": t_ops, "bytes_s": t_mem}
