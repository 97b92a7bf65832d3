"""Seeded request inputs of Li et al.'s FNO-3D for Navier–Stokes
(arXiv:2010.08895 §5.3), made on the host where a front end holds them.

Each sample is ten observed vorticity frames on the unit torus, each
repeated over the T output steps, beside the x, y and t grids:
[n_samples, 13, s, s, T] float32, channels (w_1..w_10, x, y, t). Frame
t is w_t = cos(πt/20)·g₁ + sin(πt/20)·g₂, t = 1..10, of two draws g₁, g₂
of the paper's initial-condition law N(0, 7^{3/2}(−Δ + 49I)^{−2.5}),
sampled as Li et al.'s GaussianRF does it (α = 2.5, τ = 7). No solver
runs: the frames rotate between two fields, which is what a request's
size and the comparison with the reference need; the dynamics are not
part of either.
"""
from __future__ import annotations

import numpy as np

T_IN = 10


def initial_fields(rng: np.random.Generator, n: int, s: int,
                   alpha: float = 2.5, tau: float = 7.0) -> np.ndarray:
    """n draws [n, s, s] of N(0, 7^{3/2}(−Δ+49I)^{−2.5}) on the unit
    torus: complex normal Fourier coefficients scaled by the covariance's
    square root, the mean mode zero, the real part of the inverse FFT."""
    k = np.fft.fftfreq(s, 1.0 / s)
    sigma = tau ** (0.5 * (2 * alpha - 2))
    sqrt_eig = (s ** 2 * np.sqrt(2.0) * sigma
                * (4 * np.pi ** 2 * (k[:, None] ** 2 + k[None, :] ** 2)
                   + tau ** 2) ** (-alpha / 2.0))
    sqrt_eig[0, 0] = 0.0
    coef = (rng.standard_normal((n, s, s))
            + 1j * rng.standard_normal((n, s, s))) * sqrt_eig
    return np.fft.ifft2(coef, axes=(-2, -1)).real


def ns_inputs(rng: np.random.Generator, n_samples: int, s: int,
              t_out: int) -> np.ndarray:
    """[n_samples, T_IN + 3, s, s, t_out] float32: the frames, then the
    x, y and t grids (each linspace(0, 1))."""
    g = initial_fields(rng, 2 * n_samples, s).reshape(n_samples, 2, s, s)
    phase = np.pi * np.arange(1, T_IN + 1) / 20.0
    frames = (np.cos(phase)[None, :, None, None] * g[:, :1]
              + np.sin(phase)[None, :, None, None] * g[:, 1:])
    out = np.empty((n_samples, T_IN + 3, s, s, t_out), np.float32)
    out[:, :T_IN] = frames[..., None]
    out[:, T_IN] = np.linspace(0.0, 1.0, s)[:, None, None]
    out[:, T_IN + 1] = np.linspace(0.0, 1.0, s)[None, :, None]
    out[:, T_IN + 2] = np.linspace(0.0, 1.0, t_out)[None, None, :]
    return out
