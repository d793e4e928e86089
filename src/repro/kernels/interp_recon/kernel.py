"""Fused interpolation-predict + add-residual Pallas TPU kernel (decode).

The exact inverse of ``interp_quant``'s phase sweep: for a row block in
VMEM, predict the target columns (odd multiples of stride s) from their
neighbours on the partially reconstructed surface, then add the
dequantized residual.  This is the hot loop of retrieval (paper
Algorithms 1–2): every (level, dim) phase of
``interpolation.reconstruct_batch`` maps to one launch.

It shares the encode kernel's layout (the wrapper gathers the known points
of the sweep axis once; the body slices their four neighbours and is
elementwise) and its
prediction core, and computes ``arith.recon`` — the writeback the encoder's
escape screen verified — so decoded bits equal the encoder's.  The residual
arrives dequantized by the host (``arith.dequantize``), and the
escape-override writeback stays with the caller.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core import arith
from ..interp_quant.kernel import (known, predict_core, row_block,
                                   split_known, sweep_geometry)


def recon_core(l3, l1, r1, r3, res, *, Ne: int, interp: str, ftz: bool):
    """``pred + res`` of one phase block (shared by kernel and XLA twin)."""
    pred = predict_core(l3, l1, r1, r3, Ne=Ne, interp=interp, ftz=ftz)
    return arith.recon(jnp, pred, res, ftz)


def _kernel(e_ref, res_ref, out_ref, *, Ne: int, interp: str, ftz: bool):
    out_ref[...] = recon_core(*split_known(e_ref[...], res_ref.shape[-1]),
                              res_ref[...], Ne=Ne, interp=interp, ftz=ftz)


@functools.partial(jax.jit, static_argnames=("s", "interp", "interpret"))
def interp_recon_pallas(xhat: jax.Array, res: jax.Array, *, s: int,
                        interp: str = "cubic", interpret: bool = True):
    """xhat: (R, C), res: (R, T), both in the working dtype.  Returns
    recon (R, T): ``pred + res`` at the target columns (odd multiples of
    s)."""
    R, C = xhat.shape
    T, Ne = sweep_geometry(C, s)
    assert T > 0 and res.shape == (R, T)
    ops = [known(xhat, s), res]
    rb = row_block(R, Ne + 3)
    pad = (-R) % rb
    if pad:
        ops = [jnp.pad(a, ((0, pad), (0, 0))) for a in ops]
    bspec = pl.BlockSpec((rb, T), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, Ne=Ne, interp=interp,
                          ftz=xhat.dtype == jnp.float32),
        grid=((R + pad) // rb,),
        in_specs=[pl.BlockSpec((rb, Ne + 3), lambda i: (i, 0)), bspec],
        out_specs=bspec,
        out_shape=jax.ShapeDtypeStruct((R + pad, T), xhat.dtype),
        interpret=interpret,
        name="interp_recon",
    )(*ops)
    return out[:R]


@functools.partial(jax.jit, static_argnames=("s", "interp"))
def interp_recon_xla(xhat: jax.Array, res: jax.Array, *, s: int,
                     interp: str = "cubic"):
    """Jitted XLA twin of :func:`interp_recon_pallas` (the
    ``IPCOMP_KERNEL_MODE=xla`` path on CPU): the same gather and core."""
    T, Ne = sweep_geometry(xhat.shape[-1], s)
    return recon_core(*split_known(known(xhat, s), T), res, Ne=Ne,
                      interp=interp, ftz=xhat.dtype == jnp.float32)
