"""Pure-jnp oracles for every Pallas kernel, built on jnp.fft (NOT the
matmul formulation) so kernel tests exercise a genuinely independent path.

These also serve as the "PyTorch-style staged baseline" in benchmarks: each
stage materializes its output, exactly like cuFFT → copy → cuBLAS → copy →
cuFFT in the paper's baseline.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


# -- stage oracles -----------------------------------------------------------
def ref_truncated_rdft(x: jnp.ndarray, modes: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """rfft along last axis + slice (the separate 'truncation copy kernel')."""
    xf = jnp.fft.rfft(x.astype(jnp.float32), axis=-1)[..., :modes]
    return xf.real, xf.imag


def ref_padded_irdft(xr: jnp.ndarray, xi: jnp.ndarray, n: int) -> jnp.ndarray:
    """zero-pad to n//2+1 bins (the 'padding copy kernel') + irfft."""
    modes = xr.shape[-1]
    xf = (xr + 1j * xi).astype(jnp.complex64)
    pad = [(0, 0)] * (xf.ndim - 1) + [(0, n // 2 + 1 - modes)]
    return jnp.fft.irfft(jnp.pad(xf, pad), n=n, axis=-1).astype(jnp.float32)


def ref_truncated_cdft(xr, xi, modes: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    xf = jnp.fft.fft((xr + 1j * xi).astype(jnp.complex64), axis=-1)[..., :modes]
    return xf.real, xf.imag


def ref_padded_icdft(xr, xi, n: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    modes = xr.shape[-1]
    xf = (xr + 1j * xi).astype(jnp.complex64)
    pad = [(0, 0)] * (xf.ndim - 1) + [(0, n - modes)]
    out = jnp.fft.ifft(jnp.pad(xf, pad), n=n, axis=-1)
    return out.real.astype(jnp.float32), out.imag.astype(jnp.float32)


def ref_cgemm(ar, ai, br, bi) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Complex matmul (..., M, K) x (K, N) as 4 real matmuls."""
    cr = ar @ br - ai @ bi
    ci = ar @ bi + ai @ br
    return cr, ci


# -- fused-layer oracles -----------------------------------------------------
def _keep(xf, axis: int, kept: int, truncation: str):
    """The kept bins of a full complex axis: the first `kept` ("low"), or
    the kept/2 lowest and kept/2 highest ("corners")."""
    if truncation == "low":
        return jax.lax.slice_in_dim(xf, 0, kept, axis=axis)
    h, n = kept // 2, xf.shape[axis]
    return jnp.concatenate([jax.lax.slice_in_dim(xf, 0, h, axis=axis),
                            jax.lax.slice_in_dim(xf, n - h, n, axis=axis)],
                           axis=axis)


def _unkeep(yf, axis: int, n: int, truncation: str):
    """Zero-pad kept bins back to a full axis of length n."""
    kept = yf.shape[axis]
    if truncation == "low":
        pad = [(0, 0)] * yf.ndim
        pad[axis] = (0, n - kept)
        return jnp.pad(yf, pad)
    h = kept // 2
    zeros = list(yf.shape)
    zeros[axis] = n - kept
    return jnp.concatenate([jax.lax.slice_in_dim(yf, 0, h, axis=axis),
                            jnp.zeros(zeros, yf.dtype),
                            jax.lax.slice_in_dim(yf, h, kept, axis=axis)],
                           axis=axis)


def ref_fnond(x: jnp.ndarray, wr: jnp.ndarray, wi: jnp.ndarray,
              modes: Tuple[int, ...],
              truncation: str = "low") -> jnp.ndarray:
    """Staged rank-R FNO spectral layer.

    x: [B, H, s_1..s_R]; ``modes`` are kept counts per axis. truncation
    "low" keeps the LOW corner ``[:k_1, …, :k_R]`` only (TurboFNO, paper
    Fig. 4 — "first dimX/DimX fraction"); "corners" keeps classic FNO's ±
    corners of every complex axis (k/2 lowest and k/2 highest bins) and
    the first k_R of the rFFT axis. W: [O, H] or [O, H, k_1..k_R]. Output
    [B, O, s_1..s_R].

    rFFT along s_R, FFT along the rest → truncate → CGEMM over hidden →
    zero-pad → inverse transforms. Built on jnp.fft (NOT the matmul
    formulation) so it stays a genuinely independent oracle for the engine.
    """
    r = len(modes)
    spatial = x.shape[2:]
    xf = jnp.fft.rfft(x.astype(jnp.float32), axis=-1)[..., :modes[-1]]
    for j in range(r - 1):  # FFT along s_{R-1}, …, s_1 (axes in place)
        ax = xf.ndim - 2 - j
        xf = _keep(jnp.fft.fft(xf, axis=ax), ax, modes[r - 2 - j],
                   truncation)
    w = (wr + 1j * wi).astype(jnp.complex64)
    ms = "uvw"[:r]
    eq = (f"oh{ms},bh{ms}->bo{ms}" if w.ndim > 2
          else f"oh,bh{ms}->bo{ms}")
    yf = jnp.einsum(eq, w, xf)
    for j in range(r - 1):  # inverse FFT along s_1, …, s_{R-1}
        yf = jnp.fft.ifft(_unkeep(yf, 2 + j, spatial[j], truncation),
                          axis=2 + j)
    yf = _unkeep(yf, yf.ndim - 1, spatial[-1] // 2 + 1, "low")
    return jnp.fft.irfft(yf, n=spatial[-1], axis=-1).astype(jnp.float32)


def ref_fno1d(x: jnp.ndarray, wr: jnp.ndarray, wi: jnp.ndarray,
              modes: int) -> jnp.ndarray:
    """Staged FNO 1D spectral layer. x: [B, H, N]; W: [O, H] or [O, H, modes].

    rFFT → truncate → CGEMM over hidden → zero-pad → irFFT. Output [B, O, N].
    """
    return ref_fnond(x, wr, wi, (modes,))


def ref_fno2d(x: jnp.ndarray, wr: jnp.ndarray, wi: jnp.ndarray,
              modes: Tuple[int, int]) -> jnp.ndarray:
    """Staged FNO 2D spectral layer, TurboFNO truncation convention.

    x: [B, H, X, Y]; W: [O, H] or [O, H, kx, ky]. Output [B, O, X, Y].
    """
    return ref_fnond(x, wr, wi, tuple(modes))
