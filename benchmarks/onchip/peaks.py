"""Published peaks of each accelerator the benchmark may run on, keyed by
the ``device_kind`` that JAX reports. A kind that is not in the table is
an error: a roofline or MFU against a guessed peak means nothing.

f32 cells are held to the bf16 peak as well: the program sets no matmul
precision, and a TPU runs an f32 matmul at the default precision as
bf16 passes on the MXU, so bf16 is the rate its f32 dots can reach.
"""
from __future__ import annotations

from typing import Dict

V5E_SOURCE = ("Google Cloud documentation, 'TPU v5e' "
              "(cloud.google.com/tpu/docs/v5e): per chip 197 TFLOP/s bf16, "
              "16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI")

PEAKS: Dict[str, Dict[str, float]] = {
    # JAX names a TPU v5e chip "TPU v5 lite".
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}

SOURCES = {"TPU v5 lite": V5E_SOURCE}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table's row for ``device_kind``; raises ``KeyError`` for a
    kind the table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
