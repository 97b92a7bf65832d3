"""Seeded request inputs, made on the host where a front end holds them.

Darcy-flow inputs as Li et al. 2020 §5.2 pose them: a piecewise-constant
permeability (a Gaussian random field thresholded at 0 to 12 or 3), here
scaled by 1/10, beside the two coordinate grids: [n_samples, 3, n, n]
float32.
"""
from __future__ import annotations

import numpy as np


def darcy_inputs(rng: np.random.Generator, n_samples: int, n: int,
                 alpha: float = 2.0, tau: float = 3.0) -> np.ndarray:
    kx = np.fft.fftfreq(n, 1.0 / n)
    ky = np.fft.rfftfreq(n, 1.0 / n)
    spec = (kx[:, None] ** 2 + ky[None, :] ** 2 + tau ** 2) ** (-alpha / 2)
    coef = (rng.standard_normal((n_samples, n, ky.size))
            + 1j * rng.standard_normal((n_samples, n, ky.size))) * spec
    g = np.fft.irfft2(coef, s=(n, n), axes=(-2, -1))
    a = np.where(g > 0, 1.2, 0.3)
    grid = np.linspace(0.0, 1.0, n)
    out = np.empty((n_samples, 3, n, n), np.float32)
    out[:, 0] = a
    out[:, 1] = grid[None, :, None]
    out[:, 2] = grid[None, None, :]
    return out
