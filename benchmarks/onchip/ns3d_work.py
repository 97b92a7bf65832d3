"""The work of ``fno3d-ns`` (Li et al.'s FNO-3D) from its shapes: the
benchmark's own algebra (``work.py``) at the bins the model keeps.

The configuration gives its modes per corner, (8, 8, 8); the layer keeps
the ± corners of x and y and the first 8 bins of the half-spectrum axis t,
16·16·8 = 2,048 modes. ``work.py`` counts a truncated transform as a
dense matmul with the kept rows, which is indifferent to which rows they
are, so its functions apply unchanged at the kept counts. The ends are
Li et al.'s: a one-layer lift (fc0, in_channels -> width) and the
projection through ``lifting_dim`` (fc1 -> fc2).
"""
from __future__ import annotations

import math
from typing import Any, Dict

import work


def kept(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration with ``modes`` as the kept bins per axis."""
    modes = list(cfg["modes"])
    if cfg.get("truncation", "low") == "corners":
        modes = [2 * m for m in modes[:-1]] + modes[-1:]
    return dict(cfg, modes=modes)


def kept_modes(cfg: Dict[str, Any]) -> int:
    return math.prod(kept(cfg)["modes"])


def ends_flops(cfg: Dict[str, Any]) -> float:
    """The one-layer lift and the two-layer projection, one sample."""
    h, lift = cfg["hidden"], cfg["lifting_dim"]
    sp = math.prod(cfg["spatial"])
    return float(2 * sp * cfg["in_channels"] * h
                 + 2 * sp * (h * lift + lift * cfg["out_channels"]))


def model_flops(cfg: Dict[str, Any], batch: int) -> float:
    """Forward operations of the whole model over ``batch`` samples."""
    return batch * (cfg["num_layers"] * work.block_flops(kept(cfg))
                    + ends_flops(cfg))


def least_seconds(cfg: Dict[str, Any], batch: int,
                  peak: Dict[str, float]) -> Dict[str, Any]:
    """The blocks' least time for one served request (``work``)."""
    return work.least_seconds(kept(cfg), batch, peak, training=False)
