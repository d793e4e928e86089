"""Pure-jnp oracle for the fused interpolate+quantize phase sweep.

Mirrors repro.core.interpolation.predict_block for a sweep along the last
axis with stride s: targets are odd multiples of s, neighbours at +-s/+-3s,
cubic with linear/copy-left boundary fallback, then linear-scale
quantization with the contract's quantizer (``arith.bins``).  Like the
kernel, returns (q, pred); the writeback and escape screen belong to the
caller.
"""
from __future__ import annotations

import jax.numpy as jnp

from ...core import arith

COEF = (-1.0 / 16.0, 9.0 / 16.0, 9.0 / 16.0, -1.0 / 16.0)


def predict_ref(xhat: jnp.ndarray, s: int, interp: str = "cubic") -> jnp.ndarray:
    """Predictions for target columns (odd multiples of s) of shape (R, T)."""
    n = xhat.shape[-1]
    idx = jnp.arange(s, n, 2 * s)
    l1 = xhat[..., idx - s]
    r_ok = idx + s <= n - 1
    r1 = xhat[..., jnp.minimum(idx + s, n - 1)]
    lin = 0.5 * (l1 + r1)
    if interp == "linear":
        return jnp.where(r_ok, lin, l1)
    ll_ok = idx - 3 * s >= 0
    rr_ok = idx + 3 * s <= n - 1
    l3 = xhat[..., jnp.maximum(idx - 3 * s, 0)]
    r3 = xhat[..., jnp.minimum(idx + 3 * s, n - 1)]
    cub = COEF[0] * l3 + COEF[1] * l1 + COEF[2] * r1 + COEF[3] * r3
    return jnp.where(ll_ok & rr_ok & r_ok, cub, jnp.where(r_ok, lin, l1))


def interp_quant_ref(x: jnp.ndarray, xhat: jnp.ndarray, s: int, eb: float,
                     interp: str = "cubic"):
    """Returns (q int32 targets, pred targets) for the phase sweep."""
    n = x.shape[-1]
    idx = jnp.arange(s, n, 2 * s)
    pred = predict_ref(xhat, s, interp)
    pred = pred.astype(x.dtype)
    q = arith.bins(jnp, x[..., idx], pred, arith.consts(eb, x.dtype),
                   jnp.int32)
    return q, pred
