"""Plain reference of the FNO the benchmark measures: its forward pass,
in straightforward ``jax.numpy``.

It follows Li et al. 2020 (arXiv:2010.08895) with TurboFNO's truncation
(arXiv:2504.11681, Fig. 4): lifting MLP, ``num_layers`` blocks of
``gelu(spectral(h) + h·W_b + b)``, projection MLP. The spectral layer is
an rFFT along the last axis and an FFT along the others, keeping the
first ``k`` bins of each axis (the low corner, no negative frequencies),
a complex contraction over the hidden channels with one ``[O, H]`` weight
(``shared``) or one per kept mode (``per_mode``, ``[O, H, k1, k2]``),
zero padding and the inverse transforms. GELU is the tanh form.

Nothing here imports the system under test. The weights are made from
the seed by the published initialisation (``init_params``), not taken
from the program. By default every contraction runs in float32 at
``"highest"`` precision. Lower precisions serve as controls, the same
on every platform: ``precision="high"`` rounds each contraction's
operands to the 16 significant bits of a bfloat16 head plus tail, as a
TPU's three-pass mode keeps; ``precision="default"`` rounds them to one
bfloat16, as its one pass does; ``dtype="bfloat16"`` holds parameters,
activations and every contraction's operands and results in bfloat16
(the FFTs run in float32 on bfloat16 inputs and their results are
rounded back).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp


def _bf16(z: jax.Array) -> jax.Array:
    """z rounded to bfloat16's 8 significant bits, kept in its own dtype.
    ``reduce_precision`` is an op of its own that XLA may not drop, where
    a cast down and back up may be folded away."""
    return jax.lax.reduce_precision(z, exponent_bits=8, mantissa_bits=7)


def _words(z: jax.Array, n: int) -> jax.Array:
    """z rounded to ``n`` bfloat16 words (8 or 16 significant bits)."""
    out = jnp.zeros_like(z)
    for _ in range(n):
        out = out + _bf16(z - out)
    return out


_WORDS = {"high": 2, "default": 1}


def einsum(eq: str, a: jax.Array, b: jax.Array, precision: str):
    """``jnp.einsum`` at ``precision``: "highest" (exact float32 products),
    or "high"/"default" (operands rounded as the TPU's three-pass and
    one-pass modes round them). bfloat16 operands are multiplied as they
    are."""
    if a.dtype == jnp.bfloat16:
        return jnp.einsum(eq, a, b)
    if precision != "highest":
        a, b = _words(a, _WORDS[precision]), _words(b, _WORDS[precision])
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


# -- parameters ---------------------------------------------------------------
def init_params(key: jax.Array, cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Seeded weights: dense layers Glorot-normal with zero bias, spectral
    weights normal scaled by 1/sqrt(H·O); keys split 4 + 2·layers ways
    (lift1, lift2, proj1, proj2, then spectral and bypass per layer)."""
    h = cfg["hidden"]
    lift = cfg.get("lifting_dim") or 2 * h
    n_layers = cfg["num_layers"]
    keys = jax.random.split(key, 4 + 2 * n_layers)

    def dense(k, din, dout):
        k1, _ = jax.random.split(k)
        scale = (2.0 / (din + dout)) ** 0.5
        return {"w": scale * jax.random.normal(k1, (din, dout), jnp.float32),
                "b": jnp.zeros((dout,), jnp.float32)}

    def spectral(k):
        shape = (h, h) if cfg["weight_mode"] == "shared" else (
            (h, h) + tuple(cfg["modes"]))
        kr, ki = jax.random.split(k)
        scale = 1.0 / (h * h) ** 0.5
        return {"wr": scale * jax.random.normal(kr, shape, jnp.float32),
                "wi": scale * jax.random.normal(ki, shape, jnp.float32)}

    return {
        "lift1": dense(keys[0], cfg["in_channels"], lift),
        "lift2": dense(keys[1], lift, h),
        "proj1": dense(keys[2], h, lift),
        "proj2": dense(keys[3], lift, cfg["out_channels"]),
        "blocks": [{"spectral": spectral(keys[4 + 2 * i]),
                    "bypass": dense(keys[5 + 2 * i], h, h)}
                   for i in range(n_layers)],
    }


def cast(tree, dtype):
    return jax.tree_util.tree_map(lambda a: a.astype(dtype), tree)


# -- forward -----------------------------------------------------------------
def _gelu(z):
    return 0.5 * z * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (z + 0.044715 * z * z * z)))


def _dense(p, x, prec):
    """Pointwise over channels: x [B, C, *s] -> [B, D, *s]."""
    y = einsum("bc...,cd->bd...", x, p["w"], prec)
    return y + p["b"].reshape((1, -1) + (1,) * (x.ndim - 2))


def _round(z, dtype):
    """A complex array rounded to ``dtype`` part by part (bfloat16 has no
    complex type); float32 leaves it as it is."""
    if jnp.dtype(dtype) == jnp.float32:
        return z
    return jax.lax.complex(_bf16(z.real), _bf16(z.imag))


def _cmul(w_re, w_im, x, eq, dtype, prec):
    """Complex contraction ``eq`` of a (re, im) weight and complex x, each
    real product at ``dtype``; returns complex64."""
    xr, xi = x.real.astype(dtype), x.imag.astype(dtype)
    ein = lambda a, b: einsum(eq, a, b, prec)  # noqa: E731
    re = ein(w_re, xr) - ein(w_im, xi)
    im = ein(w_re, xi) + ein(w_im, xr)
    return jax.lax.complex(re.astype(jnp.float32), im.astype(jnp.float32))


def spectral(p, h: jax.Array, modes: Sequence[int],
             prec: str = "highest") -> jax.Array:
    """Truncated spectral convolution over the trailing spatial axes of
    h [B, H, s_1..s_R]; the result is in h's dtype."""
    dtype = h.dtype
    r = len(modes)
    spatial = h.shape[2:]
    xf = jnp.fft.rfft(h.astype(jnp.float32), axis=-1)[..., :modes[-1]]
    xf = _round(xf, dtype)
    for j in range(r - 1):  # FFT along s_{R-1}, ..., s_1
        ax = h.ndim - 2 - j
        xf = jax.lax.slice_in_dim(jnp.fft.fft(xf, axis=ax), 0,
                                  modes[r - 2 - j], axis=ax)
        xf = _round(xf, dtype)
    ms = "uvw"[:r]
    per_mode = p["wr"].ndim > 2
    eq = f"oh{ms},bh{ms}->bo{ms}" if per_mode else f"oh,bh{ms}->bo{ms}"
    yf = _round(_cmul(p["wr"], p["wi"], xf, eq, dtype, prec), dtype)
    pad = [(0, 0), (0, 0)]
    pad += [(0, n - k) for n, k in zip(spatial[:-1], modes[:-1])]
    pad += [(0, spatial[-1] // 2 + 1 - modes[-1])]
    yf = jnp.pad(yf, pad)
    for j in range(r - 1):  # inverse FFT along s_1, ..., s_{R-1}
        yf = _round(jnp.fft.ifft(yf, n=spatial[j], axis=2 + j), dtype)
    return jnp.fft.irfft(yf, n=spatial[-1], axis=-1).astype(dtype)


def forward(params, cfg: Dict[str, Any], x: jax.Array, dtype=jnp.float32,
            precision: str = "highest") -> jax.Array:
    """x [B, in_channels, *spatial] -> [B, out_channels, *spatial] in
    ``dtype`` (the parameters are cast to it), every contraction at
    ``precision``."""
    p, prec = cast(params, dtype), precision
    modes = tuple(cfg["modes"])
    h = _dense(p["lift2"], _gelu(_dense(p["lift1"], x.astype(dtype), prec)),
               prec)
    for blk in p["blocks"]:
        h = _gelu(spectral(blk["spectral"], h, modes, prec)
                  + _dense(blk["bypass"], h, prec))
    return _dense(p["proj2"], _gelu(_dense(p["proj1"], h, prec)), prec)
