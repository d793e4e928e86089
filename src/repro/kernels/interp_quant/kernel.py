"""Fused interpolation-predict + quantize Pallas TPU kernel.

One (level, dim) sweep of §4.1 with the sweep axis laid out on lanes: for
a row block in VMEM, predict the target columns (odd multiples of stride
s) from their neighbours at +-s / +-3s and quantize the residual against
the original values, emitting the int32 bins and the predictions.  The
dequantized writeback and the escape screen are left to the caller
(``arith.screen`` on the host), so every backend shares one definition of
the values that reach the archive.

Layout.  The wrapper de-interleaves the sweep axis before the launch: the
known points are the even multiples of s, gathered once and padded by one
column on the left and two on the right (:func:`known`, an XLA strided
gather outside the kernel, as is the gather of the target columns).
Target j then sits between known points j and j+1, and the kernel forms
its four neighbours as contiguous lane slices at offsets 0..3 of that one
block (:func:`split_known`) — unit-offset slices, which the chip's
compiler accepts, where the strided lane slices of the interleaved axis
are refused.  The body is elementwise over (rows, T) blocks.  Boundary
fallback (cubic -> linear -> copy-left) is a lane-index mask from an
in-kernel iota, compared against static thresholds.

Arithmetic: ``arith.predict`` / ``arith.bins`` — the same functions the
numpy reference runs, in the field's dtype (float32 with subnormal
flushing, or float64).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core import arith

ROWS_B = 8  # sublane tile; row blocks are multiples of it

#: VMEM elements per operand block (per buffer): 64 Ki lanes-padded f32
_BLOCK_ELEMS = 1 << 16


def sweep_geometry(C: int, s: int):
    """(T, Ne): target count and known-point count of a stride-s sweep
    over an axis of length C."""
    return len(range(s, C, 2 * s)), len(range(0, C, 2 * s))


def row_block(R: int, T: int) -> int:
    """Rows per grid step: about ``_BLOCK_ELEMS`` lane-padded elements per
    operand block, a multiple of ``ROWS_B``, at most R rounded up."""
    lanes = -(-T // 128) * 128
    rb = max(ROWS_B, min(512, _BLOCK_ELEMS // lanes) // ROWS_B * ROWS_B)
    return min(rb, -(-R // ROWS_B) * ROWS_B)


def known(xh, s: int):
    """(R, C) surface -> (R, Ne + 3): its known points (even multiples of
    s), padded by one copy of the first on the left and two of the last on
    the right."""
    e = xh[..., ::2 * s]
    return jnp.concatenate([e[..., :1], e, e[..., -1:], e[..., -1:]],
                           axis=-1)


def split_known(ep, T: int):
    """:func:`known` points -> (l3, l1, r1, r3), each (R, T): the known
    points at -3s, -s, +s, +3s of every target, out-of-range neighbours
    clamped to the nearest known point (masked off by
    :func:`predict_core`)."""
    return tuple(ep[..., k:k + T] for k in range(4))


def targets(x, s: int):
    """(R, C) field -> (R, T) values at the target columns."""
    T, _ = sweep_geometry(x.shape[-1], s)
    return x[..., s::2 * s][..., :T]


def predict_core(l3, l1, r1, r3, *, Ne: int, interp: str, ftz: bool):
    """``arith.predict`` with the boundary masks of a sweep whose known
    points number ``Ne``: target j has a right neighbour iff j+1 < Ne and
    the cubic stencil iff also j >= 1 and j+2 < Ne."""
    j = jax.lax.broadcasted_iota(jnp.int32, l1.shape, l1.ndim - 1)
    r_ok = j < Ne - 1
    cubic_ok = (j >= 1) & (j < Ne - 2)
    return arith.predict(jnp, l3, l1, r1, r3, cubic_ok, r_ok, interp, ftz)


def quant_core(l3, l1, r1, r3, tgt, *, Ne: int, interp: str,
               c: arith.Consts):
    """(q int32, pred) of one phase block; shared by the kernel body and
    the XLA twin."""
    pred = predict_core(l3, l1, r1, r3, Ne=Ne, interp=interp, ftz=c.f32)
    return arith.bins(jnp, tgt, pred, c, jnp.int32), pred


def _kernel(e_ref, t_ref, q_ref, p_ref, *, Ne: int, interp: str,
            c: arith.Consts):
    q, pred = quant_core(*split_known(e_ref[...], t_ref.shape[-1]),
                         t_ref[...], Ne=Ne, interp=interp, c=c)
    q_ref[...] = q
    p_ref[...] = pred


@functools.partial(jax.jit, static_argnames=("s", "c", "interp"))
def interp_quant_xla(x: jax.Array, xhat: jax.Array, *, s: int,
                     c: arith.Consts, interp: str = "cubic"):
    """Jitted XLA twin of :func:`interp_quant_pallas` (the
    ``IPCOMP_KERNEL_MODE=xla`` path on CPU): the same gather and core."""
    T, Ne = sweep_geometry(x.shape[-1], s)
    return quant_core(*split_known(known(xhat, s), T), targets(x, s),
                      Ne=Ne, interp=interp, c=c)


@functools.partial(jax.jit,
                   static_argnames=("s", "c", "interp", "interpret"))
def interp_quant_pallas(x: jax.Array, xhat: jax.Array, *, s: int,
                        c: arith.Consts, interp: str = "cubic",
                        interpret: bool = True):
    """x, xhat: (R, C) in the working dtype.  Returns (q (R, T) int32,
    pred (R, T)) for the targets at odd multiples of s."""
    R, C = x.shape
    T, Ne = sweep_geometry(C, s)
    assert T > 0
    ops = [known(xhat, s), targets(x, s)]
    rb = row_block(R, Ne + 3)
    pad = (-R) % rb
    if pad:
        ops = [jnp.pad(a, ((0, pad), (0, 0))) for a in ops]
    bspec = pl.BlockSpec((rb, T), lambda i: (i, 0))
    q, pred = pl.pallas_call(
        functools.partial(_kernel, Ne=Ne, interp=interp, c=c),
        grid=((R + pad) // rb,),
        in_specs=[pl.BlockSpec((rb, Ne + 3), lambda i: (i, 0)), bspec],
        out_specs=[bspec, bspec],
        out_shape=[jax.ShapeDtypeStruct((R + pad, T), jnp.int32),
                   jax.ShapeDtypeStruct((R + pad, T), x.dtype)],
        interpret=interpret,
        name="interp_quant",
    )(*ops)
    return q[:R], pred[:R]
