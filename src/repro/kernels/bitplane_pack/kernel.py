"""Negabinary + XOR-predictive bitplane packing Pallas TPU kernel (§4.4).

Key TPU adaptation (DESIGN.md §3): the paper's per-plane predictive coding
    enc_k = b_k ^ b_{k+1} ^ b_{k+2}
collapses, over ALL planes at once, into THREE integer ops on the whole
word:      enc = nb ^ (nb >> 1) ^ (nb >> 2)
so the kernel converts q -> negabinary -> XOR-encoded word in O(1) VPU ops
per element, then bit-transposes lanes into packed uint32 plane words
(32 lanes -> one word per plane, MSB-first within the word).

Block layout: the wrapper transposes each row of 32*W bins so that
element j of every 32-element group sits on the leading axis — (32, R, W)
int32, ``qt[j, r, w] = q[r, 32*w + j]`` — and the kernel transposes each
32 x 32 bit matrix (rows = the 32 elements' encoded words) so that bit k
of slab j lands at bit (31 - j) of plane k's word: shifts, XORs and ANDs
over (ROWS_B, W) tiles, with no lane reshape, no reversal and no
reduction (the chip's compiler supports neither a lane-splitting reshape
nor an unsigned reduction).  Output (32, R, W): plane k, MSB-first within
each word.

The decode direction (``bitplane_unpack_pallas``) is the exact inverse with
the same collapsed-word trick: unpacked plane bits are OR-merged back into
the encoded word, the XOR recurrence is undone by its closed-form inverse
(1+x+x^2)^-1 = sum_k x^{3k}(1+x) over GF(2) — 22 shift/XORs instead of the
host's 32-step sequential MSB-down recurrence — and the negabinary word is
decoded back to the int32 quantization bin.

``low_zero`` — the count of absent low negabinary digits a loaded plane
prefix implies — is a RUNTIME operand (a (1, 1) uint32 array), not a
static argname: mixed plane prefixes batch into one launch (each vmapped
element carries its own mask width) instead of fragmenting a chunk group
into one launch per ``(nbits, prefix)`` bucket, and refine ladders stop
re-tracing the kernel once per distinct prefix.

``loss_extrema`` is the XLA reduction the pack's program runs beside the
kernel over the same bins: per stream, the extremes of every low-digit
value, from which the host finishes the level's truncation-loss table
without converting the stream to negabinary itself.

``unpack_words`` is the pure-jnp core shared by the Pallas kernel body and
the jitted XLA twin (``IPCOMP_KERNEL_MODE=xla`` — see ``kernels.mode``):
one definition, so the two execution modes cannot drift.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

ROWS_B = 8
GROUP = 32          # lanes packed per output word
NEG_M = np.uint32(0xAAAAAAAA)


def pack_core(qt):
    """(32, R, W) int32 element-major bins -> 32 XOR-coded plane word
    arrays (R, W) uint32, plane k first.  Shared by the Pallas kernel
    body and the XLA twin.

    Packing is a 32x32 bit-matrix transpose per word position: row j is
    element j's encoded word, and plane k's word holds bit k of every row
    (row j at bit 31 - j).  The transpose runs as the five masked swap
    stages of Hacker's Delight's transpose32, each one vectorized over
    the stacked rows (a leading-axis reshape pairs row i with row i + j).
    """
    u32 = jnp.uint32
    nb = (qt.astype(u32) + NEG_M) ^ NEG_M           # negabinary (§4.4.2)
    a = nb ^ (nb >> u32(1)) ^ (nb >> u32(2))        # 2-bit-prefix XOR
    R, W = a.shape[1:]
    j, m = 16, 0x0000FFFF
    while j:
        x = a.reshape(GROUP // (2 * j), 2, j, R, W)
        lo, hi = x[:, 0], x[:, 1]
        t = (lo ^ (hi >> u32(j))) & u32(m)
        a = jnp.stack([lo ^ t, hi ^ (t << u32(j))], axis=1)
        a = a.reshape(GROUP, R, W)
        j >>= 1
        m ^= (m << j) & 0xFFFFFFFF
    # row r now holds bit (31 - r) of every input word
    return [a[GROUP - 1 - k] for k in range(32)]


#: for b = 0..32: the mask of the b low negabinary digits, and the digits
#: of M (the negative powers of -2) under it
_LOW = np.array([(1 << b) - 1 for b in range(33)], np.uint32)
_LOW_M = _LOW & NEG_M


def loss_extrema(q):
    """(R, C) int32 bins -> (2, 33) int32: for b = 0..32, the max (row 0)
    and min (row 1) over the stream of the value of each bin's b low
    negabinary digits.

    Truncating b digits drops exactly those digits, so |value - truncated
    value| is |low-digit value|, and ``negabinary.truncation_loss_table``'s
    entry b is max(max, -min) * 2 eb.  The low-digit value is the
    negabinary decode restricted to the mask, ((nb & m) ^ (M & m)) -
    (M & m).  For bins within +-``arith.QMAX`` it fits int32 at every b,
    and the reference's decode does not wrap, so the two agree exactly.
    Zero pad bins add zeros, which cannot change max(max, -min).
    """
    u32 = jnp.uint32
    nb = (q.astype(u32) + NEG_M) ^ NEG_M
    shape = (33,) + (1,) * q.ndim
    low = jnp.asarray(_LOW).reshape(shape)
    low_m = jnp.asarray(_LOW_M).reshape(shape)
    v = jax.lax.bitcast_convert_type(((nb & low) ^ low_m) - low_m, jnp.int32)
    axes = tuple(range(1, v.ndim))
    return jnp.stack([jnp.max(v, axis=axes), jnp.min(v, axis=axes)])


def _kernel(q_ref, out_ref):
    for k, p in enumerate(pack_core(q_ref[...])):
        out_ref[k, :, :] = p


def unpack_words(planes, lz, *, W: int):
    """Pure core of the unpack direction: (32, R, W) packed plane words +
    runtime ``lz`` (uint32 scalar, low digits to mask) -> (q int32, nb
    uint32), both (R, W*GROUP).  Shared verbatim by the Pallas kernel body
    and the jitted XLA twin so the two modes stay bit-identical."""
    R = planes.shape[1]
    # planes -> XOR-encoded word: bit k of element (r, w*32 + j) is bit
    # (31 - j) of word p[k, r, w] (lane 0 = MSB, np.packbits order)
    j = jax.lax.broadcasted_iota(jnp.uint32, (R, W, GROUP), dimension=2)
    shift = jnp.uint32(GROUP - 1) - j
    enc = jnp.zeros((R, W, GROUP), jnp.uint32)
    for k in range(32):
        w = planes[k].reshape(R, W, 1)
        enc = enc | (((w >> shift) & jnp.uint32(1)) << jnp.uint32(k))
    enc = enc.reshape(R, W * GROUP)
    # XOR-undo: enc = nb ^ (nb>>1) ^ (nb>>2) is multiplication by P(x) =
    # 1 + x + x^2 over GF(2) (x = shift-right-by-one, nilpotent at x^32);
    # P^-1 = (1+x)/(1+x^3) = sum_k x^{3k} (1 + x), a closed form that
    # replaces the host's sequential MSB-down recurrence with 22 shift/XORs
    nb = jnp.zeros_like(enc)
    for k3 in range(0, 32, 3):
        t = enc >> jnp.uint32(k3)
        nb = nb ^ t
        if k3 + 1 < 32:
            nb = nb ^ (t >> jnp.uint32(1))
    # a loaded prefix of planes means low negabinary digits are absent:
    # the recurrence above would free-run on zero input below the cutoff,
    # so mask — this IS the truncation the progressive format defines
    # (§4.4).  lz is a runtime value in [0, 32): shift-by-lz is defined.
    nb = nb & (jnp.uint32(0xFFFFFFFF) << lz.astype(jnp.uint32))
    # negabinary decode (§4.4.2): x = (nb ^ M) - M, modular in uint32; the
    # truncated word itself is emitted too — it is the canonical progressive
    # state (decode_level's contract), already in register here
    u = (nb ^ NEG_M) - NEG_M
    return jax.lax.bitcast_convert_type(u, jnp.int32), nb


def _unpack_kernel(p_ref, lz_ref, q_ref, nb_ref, *, W: int):
    q, nb = unpack_words(p_ref[...], lz_ref[0, 0], W=W)
    nb_ref[...] = nb
    q_ref[...] = q


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitplane_unpack_pallas(planes: jax.Array, low_zero: jax.Array, *,
                           interpret: bool = True):
    """planes: (32, R, W) uint32 packed plane words (the ``bitplane_pack``
    layout; unloaded planes all-zero); low_zero: (1, 1) uint32 runtime
    operand.  Returns (q, nb), both (R, W*32): the int32 bins after
    XOR-undo + negabinary decode, and the truncated negabinary words
    themselves, with the ``low_zero`` least-significant digits masked to
    zero (the progressive truncation of a plane prefix).
    """
    P, R, W = planes.shape
    assert P == 32 and R % ROWS_B == 0
    grid = (R // ROWS_B,)
    bspec_out = pl.BlockSpec((ROWS_B, W * GROUP), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_unpack_kernel, W=W),
        name="bitplane_unpack",
        grid=grid,
        in_specs=[pl.BlockSpec((32, ROWS_B, W), lambda i: (0, i, 0)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=[bspec_out, bspec_out],
        out_shape=[jax.ShapeDtypeStruct((R, W * GROUP), jnp.int32),
                   jax.ShapeDtypeStruct((R, W * GROUP), jnp.uint32)],
        interpret=interpret,
    )(planes, low_zero)


@jax.jit
def bitplane_unpack_xla(planes: jax.Array, low_zero: jax.Array):
    """Jitted XLA twin of :func:`bitplane_unpack_pallas`: the same
    ``unpack_words`` core over the whole array, compiled by XLA on any
    backend (the ``IPCOMP_KERNEL_MODE=xla`` path)."""
    P, R, W = planes.shape
    return unpack_words(planes, low_zero[0, 0], W=W)


def element_major(q):
    """(R, C) int32 bins, C % GROUP == 0 -> the kernel's (32, R, C//GROUP)
    element-major layout (an XLA transpose in the wrapper)."""
    R, C = q.shape
    return jnp.transpose(q.reshape(R, C // GROUP, GROUP), (2, 0, 1))


@jax.jit
def bitplane_pack_xla(q: jax.Array):
    """Jitted XLA twin of :func:`bitplane_pack_pallas`."""
    return jnp.stack(pack_core(element_major(q)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitplane_pack_pallas(q: jax.Array, *, interpret: bool = True):
    """q: (R, C) int32, R % ROWS_B == 0, C % GROUP == 0.

    Returns packed (32, R, C // GROUP) uint32, plane k = bit k of the
    XOR-encoded negabinary words.
    """
    R, C = q.shape
    assert R % ROWS_B == 0 and C % GROUP == 0
    W = C // GROUP
    spec = pl.BlockSpec((32, ROWS_B, W), lambda i: (0, i, 0))
    return pl.pallas_call(
        _kernel,
        grid=(R // ROWS_B,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((32, R, W), jnp.uint32),
        interpret=interpret,
        name="bitplane_pack",
    )(element_major(q))
