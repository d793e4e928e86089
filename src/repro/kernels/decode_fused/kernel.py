"""Fused progressive-decode megakernel (unpack + dequantize).

Per level, ONE launch takes the packed plane words of a loaded prefix and
returns the new truncated negabinary words plus the level's dequantized
contribution in the field's arithmetic (``core.arith``):

* float32 fields: the full residual of the new truncation,
  ``f32(bin(nb_new)) * w`` — the progressive session re-sweeps from the
  current residuals, so the previous words are not an input;
* float64 fields (CPU only): Algorithm 2's residual *delta*
  ``(bin(nb_new) - bin(nb_old)) * 2.0 * eb`` against the session's
  previous truncation; both bins are int32-valued, so their f64
  difference is exact and the single rounding is the ``* eb`` the host
  spelling ``(dq * 2.0) * eb`` performs.

Either output equals the host arithmetic bit for bit.  ``low_zero``
(plane-prefix truncation) and the scale (``w`` or ``eb``) are RUNTIME
(1, 1) operands, so one trace serves every prefix depth and every level,
and vmapping gives each batched chunk its own pair.  The scale's dtype
selects the output.

``decode_fused_core`` is the pure-jnp core shared by the Pallas body and
the jitted XLA twin (``IPCOMP_KERNEL_MODE=xla``); it builds on
``bitplane_pack.kernel.unpack_words`` so the unpack arithmetic has exactly
one definition in the tree.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..bitplane_pack.kernel import GROUP, NEG_M, ROWS_B, unpack_words


def decode_fused_core(planes, nb_old, lz, scale, *, W: int):
    """(32, R, W) packed planes + runtime (lz, scale) -> (nb_new uint32,
    out), both (R, W*GROUP).  ``scale`` float32 = the bin width ``w``:
    ``out`` is the float32 residual ``f32(bin(nb_new)) * w`` (``nb_old``
    unused, may be None).  ``scale`` float64 = ``eb``: ``out`` is the f64
    delta ``(bin(nb_new) - bin(nb_old)) * 2 * eb``.
    """
    q_new, nb_new = unpack_words(planes, lz, W=W)
    if scale.dtype == jnp.float32:
        return nb_new, q_new.astype(jnp.float32) * scale
    u_old = (nb_old ^ NEG_M) - NEG_M
    q_old = jax.lax.bitcast_convert_type(u_old, jnp.int32)
    dq = q_new.astype(jnp.float64) - q_old.astype(jnp.float64)
    # one rounding, at * eb — matches the host reference's association
    return nb_new, (dq * 2.0) * scale


def _fused_kernel(*refs, W: int, with_old: bool):
    it = iter(refs)
    p_ref = next(it)
    old = next(it)[...] if with_old else None
    lz_ref, sc_ref, nb_ref, out_ref = it
    nb_new, out = decode_fused_core(p_ref[...], old, lz_ref[0, 0],
                                    sc_ref[0, 0], W=W)
    nb_ref[...] = nb_new
    out_ref[...] = out


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_fused_pallas(planes: jax.Array, nb_old, low_zero: jax.Array,
                        scale: jax.Array, *, interpret: bool = True):
    """planes: (32, R, W) uint32; nb_old: (R, W*32) uint32 previous
    progressive words (float64 delta mode) or None (float32 mode);
    low_zero, scale: (1, 1) runtime operands.  Returns (nb_new (R, W*32)
    uint32, out (R, W*32) in ``scale``'s dtype)."""
    P, R, W = planes.shape
    assert P == 32 and R % ROWS_B == 0
    with_old = scale.dtype != jnp.float32
    # ROWS_B rows per step, like bitplane_unpack: the unpack's (rows, W,
    # 32) intermediates are lane-padded 4x in VMEM, and 16-row blocks
    # already overflow it on a v5e
    RB = ROWS_B
    bspec_sc = pl.BlockSpec((1, 1), lambda i: (0, 0))
    bspec_row = pl.BlockSpec((RB, W * GROUP), lambda i: (i, 0))
    in_specs = [pl.BlockSpec((32, RB, W), lambda i: (0, i, 0))]
    args = [planes]
    if with_old:
        assert nb_old.shape == (R, W * GROUP)
        in_specs.append(bspec_row)
        args.append(nb_old)
    return pl.pallas_call(
        functools.partial(_fused_kernel, W=W, with_old=with_old),
        grid=(R // RB,),
        in_specs=in_specs + [bspec_sc, bspec_sc],
        out_specs=[bspec_row, bspec_row],
        out_shape=[jax.ShapeDtypeStruct((R, W * GROUP), jnp.uint32),
                   jax.ShapeDtypeStruct((R, W * GROUP), scale.dtype)],
        interpret=interpret,
        name="decode_fused",
    )(*args, low_zero, scale)


@jax.jit
def decode_fused_xla(planes: jax.Array, nb_old, low_zero: jax.Array,
                     scale: jax.Array):
    """Jitted XLA twin of :func:`decode_fused_pallas` (same core, whole
    array, compiled on any backend)."""
    P, R, W = planes.shape
    return decode_fused_core(planes, nb_old, low_zero[0, 0], scale[0, 0],
                             W=W)
