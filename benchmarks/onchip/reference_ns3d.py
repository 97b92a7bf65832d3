"""Plain reference of Li et al.'s FNO-3D for Navier–Stokes (arXiv:2010.08895
§5.3, ν = 1e-3), the ``fno3d-ns`` configuration: its forward pass in
straightforward ``jax.numpy``.

    v0 = P·x + p                                  fc0, in_channels -> width
    v_{l+1} = σ_l( K_l(v_l) + W_l·v_l + b_l )     σ = ReLU, none after the last
    K_l(v) = irfftn( R_l · corners(rfftn(v)) )
    u = Q2·ReLU(Q1·v_L + q1) + q2                 fc1 width -> 128, fc2 -> 1

``rfftn`` runs over (x, y, t); the four corners keep the k lowest and the
k highest bins of x and of y and the first k of the half-spectrum axis t;
each corner is contracted per mode with its own block of the weight
R_l [O, H, 2k, 2k, k] (x and y: the k lowest bins, then the k highest).

Departures from the paper, each deliberate:

* BatchNorm is left out. In eval mode it is a per-channel affine map that
  folds exactly into R_l, W_l and b_l; with seeded random weights the
  folded weights are indistinguishable from other random weights.
* The weights are seeded random, made from the seed by the same
  initialisation as the system under test (``init_params``), not trained.
* The inputs are synthetic (``inputs_ns3d``): no solver runs.

Nothing here imports the system under test. Every contraction runs in
float32 at "highest" precision by default; ``precision`` and ``dtype``
give the lower-precision controls as ``reference.py`` defines them.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

import reference as ref


def init_params(key: jax.Array, cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Seeded weights: dense layers Glorot-normal with zero bias, spectral
    weights normal scaled by 1/sqrt(H·O) in the [O, H, 2k, 2k, k] corner
    layout; keys split 4 + 2·layers ways (fc0, unused, fc1, fc2, then
    spectral and bypass per layer)."""
    h = cfg["hidden"]
    n_layers = cfg["num_layers"]
    kx, ky, kt = cfg["modes"]
    keys = jax.random.split(key, 4 + 2 * n_layers)

    def dense(k, din, dout):
        k1, _ = jax.random.split(k)
        scale = (2.0 / (din + dout)) ** 0.5
        return {"w": scale * jax.random.normal(k1, (din, dout), jnp.float32),
                "b": jnp.zeros((dout,), jnp.float32)}

    def spectral(k):
        shape = (h, h, 2 * kx, 2 * ky, kt)
        kr, ki = jax.random.split(k)
        scale = 1.0 / (h * h) ** 0.5
        return {"wr": scale * jax.random.normal(kr, shape, jnp.float32),
                "wi": scale * jax.random.normal(ki, shape, jnp.float32)}

    return {
        "lift1": dense(keys[0], cfg["in_channels"], h),
        "proj1": dense(keys[2], h, cfg["lifting_dim"]),
        "proj2": dense(keys[3], cfg["lifting_dim"], cfg["out_channels"]),
        "blocks": [{"spectral": spectral(keys[4 + 2 * i]),
                    "bypass": dense(keys[5 + 2 * i], h, h)}
                   for i in range(n_layers)],
    }


def spectral(p, v: jax.Array, modes, prec: str = "highest") -> jax.Array:
    """K(v) for v [B, H, X, Y, T]: rfftn, the four corners contracted per
    mode, the rest zero, irfftn; the result in v's dtype."""
    dtype = v.dtype
    kx, ky, kt = modes
    nx, ny, nt = v.shape[2:]
    vf = ref._round(jnp.fft.rfftn(v.astype(jnp.float32), axes=(2, 3, 4)),
                    dtype)
    out = jnp.zeros(vf.shape[:1] + p["wr"].shape[:1] + vf.shape[2:],
                    jnp.complex64)
    xs = (slice(0, kx), slice(nx - kx, nx))
    ys = (slice(0, ky), slice(ny - ky, ny))
    ws = (slice(0, kx), slice(kx, 2 * kx))
    wy = (slice(0, ky), slice(ky, 2 * ky))
    eq = "ohxyt,bhxyt->boxyt"
    for cx in range(2):
        for cy in range(2):
            corner = vf[:, :, xs[cx], ys[cy], :kt]
            w = (slice(None), slice(None), ws[cx], wy[cy])
            prod = ref._cmul(p["wr"][w], p["wi"][w], corner, eq, dtype, prec)
            out = out.at[:, :, xs[cx], ys[cy], :kt].set(
                ref._round(prod, dtype))
    return jnp.fft.irfftn(out, s=(nx, ny, nt), axes=(2, 3, 4)).astype(dtype)


def forward(params, cfg: Dict[str, Any], x: jax.Array, dtype=jnp.float32,
            precision: str = "highest") -> jax.Array:
    """x [B, in_channels, X, Y, T] -> [B, out_channels, X, Y, T] in
    ``dtype`` (the parameters are cast to it), every contraction at
    ``precision``."""
    p, prec = ref.cast(params, dtype), precision
    modes = tuple(cfg["modes"])
    h = ref._dense(p["lift1"], x.astype(dtype), prec)
    last = len(p["blocks"]) - 1
    for i, blk in enumerate(p["blocks"]):
        h = (spectral(blk["spectral"], h, modes, prec)
             + ref._dense(blk["bypass"], h, prec))
        if i < last:
            h = jax.nn.relu(h)
    return ref._dense(p["proj2"], jax.nn.relu(ref._dense(p["proj1"], h,
                                                         prec)), prec)


def reference_outputs(key, cfg, xs, *, dtype=jnp.float32,
                      precision: str = "highest", rows: int = 8):
    """The reference's answers for the host inputs xs, ``rows`` samples
    at a time, as float32 numpy."""
    import numpy as np

    params = init_params(key, cfg)
    fwd = jax.jit(lambda p, x: forward(p, cfg, x, dtype, precision)
                  .astype(jnp.float32))
    return np.concatenate([np.asarray(fwd(params, jnp.asarray(xs[s:s + rows])))
                           for s in range(0, len(xs), rows)])
