"""Architecture/shape registry.

``get_config("qwen2-1.5b")`` → full ModelConfig; ``get_config(id, reduced=True)``
→ CPU-smoke-sized variant of the same family. ``runnable_cells()`` enumerates
the (arch × shape) dry-run cells together with skip reasons (docs/DESIGN.md §5).
"""
from __future__ import annotations

from typing import Dict, Iterator, Optional, Tuple, Union

from repro.configs import (arctic_480b, chatglm3_6b, fno, gemma3_27b,
                           hubert_xlarge, hymba_1_5b, internvl2_26b,
                           mamba2_370m, mixtral_8x7b, nemotron_4_340b,
                           qwen2_1_5b)
from repro.configs.base import (SHAPES, SMOKE_SHAPES, FNOConfig, ModelConfig,
                                PrecisionPolicy, ShapeSpec)

_ARCH_MODULES = {
    "qwen2-1.5b": qwen2_1_5b,
    "gemma3-27b": gemma3_27b,
    "nemotron-4-340b": nemotron_4_340b,
    "chatglm3-6b": chatglm3_6b,
    "mamba2-370m": mamba2_370m,
    "hubert-xlarge": hubert_xlarge,
    "internvl2-26b": internvl2_26b,
    "mixtral-8x7b": mixtral_8x7b,
    "arctic-480b": arctic_480b,
    "hymba-1.5b": hymba_1_5b,
}

_FNO_FACTORIES = {
    "fno1d": (fno.fno1d, fno.reduced_1d),
    "fno2d": (fno.fno2d, fno.reduced_2d),
    "fno2d-large": (fno.fno2d_large, fno.reduced_2d),
    "fno3d": (fno.fno3d, fno.reduced_3d),
    "fno3d-ns": (fno.fno3d_ns, fno.reduced_3d_ns),
}

ARCH_IDS: Tuple[str, ...] = tuple(_ARCH_MODULES)
FNO_IDS: Tuple[str, ...] = tuple(_FNO_FACTORIES)
ALL_IDS: Tuple[str, ...] = ARCH_IDS + FNO_IDS


def get_config(arch: str, reduced: bool = False) -> Union[ModelConfig, FNOConfig]:
    if arch in _ARCH_MODULES:
        mod = _ARCH_MODULES[arch]
        cfg = mod.reduced() if reduced else mod.config()
        cfg.validate()
        return cfg
    if arch in _FNO_FACTORIES:
        full, red = _FNO_FACTORIES[arch]
        cfg = red() if reduced else full()
        cfg.validate()
        return cfg
    raise KeyError(f"unknown arch {arch!r}; known: {ALL_IDS}")


def get_shape(name: str, reduced: bool = False) -> ShapeSpec:
    table = SMOKE_SHAPES if reduced else SHAPES
    return table[name]


def skip_reason(arch: str, shape: str) -> Optional[str]:
    """Why an (arch × shape) cell is skipped, or None if runnable."""
    cfg = get_config(arch)
    if isinstance(cfg, FNOConfig):
        if shape in ("train_4k", "prefill_32k"):
            return None  # train cell / batched serving cell (ISSUE 5)
        return "FNO is a batch workload: no autoregressive decode shapes"
    if shape in ("decode_32k", "long_500k") and not cfg.is_decoder:
        return "encoder-only: no autoregressive decode step"
    if shape == "long_500k" and not cfg.sub_quadratic:
        return "pure full-attention: 500k context needs sub-quadratic attention"
    return None


def runnable_cells() -> Iterator[Tuple[str, str, Optional[str]]]:
    """Yield (arch, shape, skip_reason) for every seeded (arch × shape)
    cell — the 40 assigned LM cells AND the FNO archs (60 total), so no
    config can exist without either a runnable cell or a stated skip
    reason (the contract ``analysis.ast_lint.check_config_registry``
    enforces)."""
    for arch in ALL_IDS:
        for shape in SHAPES:
            yield arch, shape, skip_reason(arch, shape)
