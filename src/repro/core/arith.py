"""The codec's arithmetic contract: one definition per field dtype.

Every value the interpolation sweep writes into the reconstruction surface
is computed by the formulas in this module, on the host (numpy) and inside
the Pallas kernels (``jax.numpy``) alike: the xp-generic functions take the
array namespace as their first argument, so the two substrates cannot
drift.  ``docs/format.md`` ("Arithmetic contract") is the normative text.

* **float32 fields** compute in IEEE binary32, round-to-nearest-even, with
  subnormals flushed: every operand that may be subnormal and every result
  that may underflow passes through :func:`flush`, which maps
  ``|v| < 2**-126`` to ``+0``.  The TPU's vector unit flushes subnormals,
  and the explicit flush makes the host reference (and the CPU backends,
  which differ from each other in how they treat them) agree with it.
* **every other dtype** computes in float64 without flushing — the
  arithmetic archives always had; such fields run on the numpy reference,
  or on the CPU under the Pallas interpreter, never on a TPU.

Quantizer.  The bin of a residual ``r`` is ``rint(r / (2*eb))`` in
float64 (a correctly rounded divide) and ``rint(r * inv)`` in float32,
with ``inv = f32(1 / f32(2*eb))`` — a multiply, because the chip's divide
is not correctly rounded.  The bin's exact value does not matter for the
error bound (see :func:`screen`); it only has to be the same everywhere.
Bins beyond ``QMAX`` come back as the sentinel ``QSENTINEL`` (NaN too),
which :func:`screen` escapes.

The bound by construction.  :func:`screen` recomputes each element's
reconstruction exactly as the decoder will — ``flush(pred + dequant(q))``
— and sends every element whose reconstruction is not strictly within
``eb_lo`` (the largest value of the working dtype that is ``<= eb``) to
the lossless escape channel, after one try of the neighbouring bin.  Rounding is monotone, so ``fl(|d|) < eb_lo``
implies ``|d| < eb``: a full-precision read meets ``eb`` whatever the
rounding did.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import numpy as np

# 32-digit negabinary covers [-2863311530, 1431655765]; |q| <= 2**30 is safe
# on both sides and leaves headroom for the XOR/bitplane pipeline.
QMAX = 1 << 30
#: out-of-range bin marker: exactly representable in float32 and int32
QSENTINEL = -(1 << 31)

#: smallest normal binary32 magnitude; anything below it is flushed to +0
TINY32 = float(np.finfo(np.float32).tiny)

#: unit roundoff of the float32 contract (round-to-nearest)
U32 = 2.0 ** -24


def work_dtype(field_dtype) -> np.dtype:
    """Arithmetic dtype of a field: float32 stays float32, anything else
    computes in float64."""
    return np.dtype(np.float32) if np.dtype(field_dtype) == np.float32 \
        else np.dtype(np.float64)


@dataclass(frozen=True)
class Consts:
    """Per-(eb, dtype) quantizer constants, derived once on the host.

    ``w`` is the bin width, ``inv`` its reciprocal (float32 only), and
    ``eb_lo`` the largest working-dtype value ``<= eb`` — the strict
    threshold of :func:`screen`.  All are Python floats exactly
    representable in ``dtype``.
    """
    dtype: np.dtype
    eb: float
    w: float
    inv: float
    eb_lo: float

    @property
    def f32(self) -> bool:
        return self.dtype == np.float32


def consts(eb: float, dtype) -> Consts:
    """Quantizer constants for bound ``eb`` under ``dtype``'s contract.

    Raises ``ValueError`` for a float32 field whose bin width would not be
    a normal float32 (``2*eb`` below ``2**-126`` or above the float32
    range): such a bound cannot be honoured by the float32 arithmetic.
    """
    dt = work_dtype(dtype)
    eb = float(eb)
    if dt != np.float32:
        return Consts(dt, eb, 2.0 * eb, 1.0 / (2.0 * eb), eb)
    w = np.float32(2.0 * eb)
    if not (np.isfinite(w) and w >= TINY32):
        raise ValueError(
            f"error bound {eb!r} is outside what float32 arithmetic can "
            "honour (2*eb must be a normal float32); compress a float64 "
            "copy of the field with backend='numpy'")
    e = np.float32(eb)
    if float(e) > eb:
        e = np.nextafter(e, np.float32(0))
    return Consts(dt, eb, float(w), float(np.float32(1.0) / w), float(e))


def flush(xp, v, on: bool = True):
    """Flush-to-zero: ``|v| < 2**-126`` -> +0 (no-op when ``on`` is
    False, i.e. under the float64 contract)."""
    if not on:
        return v
    return xp.where(xp.abs(v) < TINY32, 0.0, v)


def predict(xp, l3, l1, r1, r3, cubic_ok, r_ok, interp: str, ftz: bool):
    """Interpolation prediction from the four neighbours of each target.

    ``l1``/``r1`` are the known points at -s/+s, ``l3``/``r3`` at -3s/+3s
    (any value where the matching mask is False); ``cubic_ok``/``r_ok``
    broadcast against them.  Boundary fallback: cubic -> linear -> copy-left.

    The spelling is contraction-proof: ``9*x`` is ``8*x + x`` (``8*x`` is
    exact, so an fma gives the same result) and the final scalings are
    exact powers of two, so a compiler fusing a multiply into the next add
    cannot change a bit.  The association is fixed:
    ``(((-l3 + 9 l1) + 9 r1) - r3) / 16``.
    """
    l1 = flush(xp, l1, ftz)
    r1 = flush(xp, r1, ftz)
    lin = flush(xp, flush(xp, l1 + r1, ftz) * 0.5, ftz)
    if interp == "linear":
        return xp.where(r_ok, lin, l1)
    l3 = flush(xp, l3, ftz)
    r3 = flush(xp, r3, ftz)
    s = flush(xp, -l3 + (8.0 * l1 + l1), ftz)
    s = flush(xp, s + (8.0 * r1 + r1), ftz)
    s = flush(xp, s - r3, ftz)
    cub = flush(xp, s * 0.0625, ftz)
    return xp.where(cubic_ok, cub, xp.where(r_ok, lin, l1))


def bins(xp, tgt, pred, c: Consts, int_dtype):
    """Quantization bins of ``tgt - pred``; out-of-range (and NaN) bins
    are the sentinel ``QSENTINEL``."""
    r = flush(xp, tgt - pred, c.f32)
    qf = xp.rint(r * c.inv) if c.f32 else xp.rint(r / c.w)
    ok = xp.abs(qf) <= QMAX
    return xp.where(ok, qf, float(QSENTINEL)).astype(int_dtype)


def dequantize(q: np.ndarray, c: Consts) -> np.ndarray:
    """Bins -> residuals in the working dtype (host only: every backend
    dequantizes here, so the residual bits have one definition)."""
    if c.f32:
        return np.asarray(q).astype(np.float32) * np.float32(c.w)
    return np.asarray(q, np.float64) * c.w


def recon(xp, pred, res, ftz: bool):
    """Reconstruction of a non-escaped element: ``flush(pred + res)``."""
    return flush(xp, pred + res, ftz)


def _verified(block, tgt, c: Consts):
    with np.errstate(invalid="ignore", over="ignore"):
        return np.abs(block - tgt) < c.eb_lo


def screen(tgt: np.ndarray, pred: np.ndarray, q: np.ndarray, c: Consts,
           ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The escape screen (host, shared by every backend).

    Returns ``(q, block, esc)``: int64 bins with escaped positions zeroed,
    the reconstructed block the decoder will produce (escaped positions
    hold their exact value ``tgt``), and the boolean escape mask.

    Each element's reconstruction ``recon(pred, dequantize(q))`` must be
    strictly within ``eb_lo`` of its target.  Rounding can push a target
    that sits near a bin edge just past the bound; such an element tries
    the neighbouring bin on the target's side once (it lies about one bin
    width, ``2 eb``, further along, so it usually verifies) and escapes
    when that misses too — as does every out-of-range (sentinel) bin.
    """
    q = np.array(q, np.int64, order="C")
    inr = (q <= QMAX) & (q >= -QMAX)
    q[~inr] = 0
    block = np.array(recon(np, pred, dequantize(q, c), c.f32),
                     c.dtype, order="C")
    esc = ~(inr & _verified(block, tgt, c))
    retry = esc & inr & np.isfinite(block) & np.isfinite(tgt)
    if retry.any():
        q2 = q[retry] + np.where(tgt[retry] > block[retry], 1, -1)
        b2 = np.asarray(recon(np, pred[retry], dequantize(q2, c), c.f32),
                        c.dtype)
        ok = _verified(b2, tgt[retry], c) & (np.abs(q2) <= QMAX)
        sel = np.flatnonzero(retry.ravel())[ok]
        q.ravel()[sel] = q2[ok]
        block.ravel()[sel] = b2[ok]
        esc.ravel()[sel] = False
    if esc.any():
        q[esc] = 0
        block[esc] = tgt[esc]
    return q, block, esc
