"""Public jit'd wrappers for the bitplane packing / unpacking kernels."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import dispatch, mode
from .kernel import (GROUP, ROWS_B, bitplane_pack_pallas, bitplane_pack_xla,
                     bitplane_unpack_pallas, bitplane_unpack_xla,
                     loss_extrema)

# words per row fed to the unpack kernel: 128 lanes of uint32 = 4096
# elements per row, matching the 1-D pack wrapper's C = 128 * GROUP
_UNPACK_W = 128


def _lz_array(low_zero, B: int | None = None):
    """Normalize ``low_zero`` to the kernel's runtime-operand layout:
    (1, 1) uint32 for a scalar call, (B, 1, 1) for a batched one (a lone
    int broadcasts to every batch row)."""
    if B is None:
        return jnp.full((1, 1), int(low_zero), jnp.uint32)
    lz = np.asarray(low_zero, np.uint32).reshape(-1)
    if lz.size == 1:
        lz = np.full(B, lz[0], np.uint32)
    assert lz.size == B, "per-chunk low_zero must match the batch size"
    return jnp.asarray(lz).reshape(B, 1, 1)


@functools.partial(jax.jit, static_argnames=("interpret", "xla"))
def _pack_level(q, *, interpret: bool, xla: bool):
    """(R, C) int32 bins -> (packed planes, ``loss_extrema``): the pack and
    the stream's truncation-loss extrema as one device program over the
    bins the pack has uploaded anyway."""
    packed = (bitplane_pack_xla(q) if xla
              else bitplane_pack_pallas(q, interpret=interpret))
    return packed, loss_extrema(q)


def bitplane_pack(q, *, interpret: bool | None = None):
    """(n,) or (R, C) int32 -> (32, R', W) packed planes (+ padding info).

    Pads to (ROWS_B, GROUP) multiples; returns (packed, n_valid, extrema)
    where the flattened valid prefix of each plane covers the original n
    elements and ``extrema`` is the stream's (2, 33) ``loss_extrema``.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    xla = mode.use_xla()
    q = jnp.asarray(q, jnp.int32)
    if q.ndim == 1:
        n = q.shape[0]
        C = 128 * GROUP
        R = -(-n // C)
        q = jnp.pad(q, (0, R * C - n)).reshape(R, C)
    else:
        n = q.size
    R, C = q.shape
    pr, pc = (-R) % ROWS_B, (-C) % GROUP
    if pr or pc:
        q = jnp.pad(q, ((0, pr), (0, pc)))
    dispatch.record("bitplane_pack", interpret=interpret and not xla)
    packed, extrema = _pack_level(q, interpret=interpret, xla=xla)
    return packed, n, extrema


def bitplane_pack_batch(q, *, interpret: bool | None = None, mesh=None):
    """(B, n) int32 stacked 1-D level streams -> ((B, 32, R, W) packed, n,
    (B, 2, 33) extrema).

    Each batch row gets the 1-D wrapper's layout — pad at the END of its
    flat stream, so ``blobs_from_packed`` per chunk sees the same valid
    prefix as an unbatched call — and the whole stack runs as ONE
    ``jax.vmap``-ed kernel launch instead of B, which also returns each
    row's ``loss_extrema``.

    With ``mesh``, the batch axis is zero-padded to a mesh multiple
    (all-zero pad streams pack to all-zero words, sliced back off) and
    split across the 1-D codec mesh; each device packs its local rows
    with the same vmapped kernel.  A stack already placed by
    ``codec_mesh.place`` is not padded again, and its outputs keep the
    pad rows.  One function holds both layouts so the
    byte-critical stream padding cannot drift between them.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    xla = mode.use_xla()
    q = jnp.asarray(q, jnp.int32)
    B, n = q.shape
    C = 128 * GROUP
    R = -(-n // C)
    padb = 0
    if mesh is not None:
        from ...parallel import codec_mesh
        padb = codec_mesh.pad_to_shards(B, mesh)
    q = jnp.pad(q, ((0, padb), (0, R * C - n))).reshape(B + padb, R, C)
    pr = (-R) % ROWS_B
    if pr:
        q = jnp.pad(q, ((0, 0), (0, pr), (0, 0)))

    if mesh is None:
        dispatch.record("bitplane_pack", interpret=interpret and not xla)
        kernel = functools.partial(_pack_level, interpret=interpret, xla=xla)
        packed, extrema = jax.vmap(kernel)(q)
    else:
        dispatch.record("bitplane_pack", interpret=interpret and not xla,
                        devices=codec_mesh.shard_count(mesh))
        packed, extrema = codec_mesh.shard_vmap(
            _pack_level, mesh, n_out=2, interpret=interpret, xla=xla)(q)
    return packed[:B], n, extrema[:B]


def bitplane_pack_sharded(q, *, mesh, interpret: bool | None = None):
    """Sharded twin: ``bitplane_pack_batch`` with the (B, n) stack split
    over the 1-D codec ``mesh`` (thin alias)."""
    return bitplane_pack_batch(q, interpret=interpret, mesh=mesh)


def bitplane_unpack(plane_words, n: int, *, low_zero: int = 0,
                    with_nb: bool = False,
                    interpret: bool | None = None):
    """(32, NW) uint32 per-plane word streams -> (n,) int32 bins.

    ``plane_words[k]`` is plane k's packed words (32 consecutive elements
    per word, element 0 at the MSB — the flat stream ``bitplane_pack``
    emits and the archive stores); absent planes are all-zero rows.
    ``low_zero`` masks that many least-significant negabinary digits, i.e.
    decodes the truncation defined by a loaded MSB-first plane prefix; it
    is a RUNTIME operand of the kernel, so distinct prefixes share one
    trace.  ``with_nb=True`` returns (q, nb): the kernel holds the
    truncated negabinary word anyway, and the progressive state stores it
    — handing it out saves the caller an exactly-cancelling host
    re-encode.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    xla = mode.use_xla()
    pw = jnp.asarray(plane_words, jnp.uint32)
    P, NW = pw.shape
    assert P == 32, "expect one row per negabinary digit"
    need = -(-max(n, 1) // (GROUP * _UNPACK_W))  # rows of _UNPACK_W words
    R = -(-need // ROWS_B) * ROWS_B
    pad = R * _UNPACK_W - NW
    if pad:
        pw = jnp.pad(pw, ((0, 0), (0, pad)))
    pw = pw.reshape(32, R, _UNPACK_W)
    lz = _lz_array(low_zero)
    dispatch.record("bitplane_unpack", interpret=interpret and not xla)
    if xla:
        q, nb = bitplane_unpack_xla(pw, lz)
    else:
        q, nb = bitplane_unpack_pallas(pw, lz, interpret=interpret)
    if with_nb:
        return q.reshape(-1)[:n], nb.reshape(-1)[:n]
    return q.reshape(-1)[:n]


def bitplane_unpack_batch(plane_words, n: int, *, low_zero=0,
                          with_nb: bool = False,
                          interpret: bool | None = None, mesh=None):
    """(B, 32, NW) stacked per-plane word streams -> (B, n) int32 bins.

    The batched twin of ``bitplane_unpack`` for equal-n chunk groups: one
    ``jax.vmap``-ed launch decodes all B streams, each padded exactly like
    a lone call, so per-chunk outputs are bit-identical.  ``low_zero`` may
    be a single int or a length-B sequence — the mask width is a runtime
    per-row operand, so chunks with DIFFERENT loaded plane prefixes still
    share the one launch (the whole point of the dynamic operand: no more
    one-launch-per-(nbits, prefix) fragmentation).

    With ``mesh``, the stream stack is zero-padded to a mesh multiple
    (all-zero pad streams decode to zeros, sliced back off) and split
    across the 1-D codec mesh; every device decodes its local streams
    with the same vmapped kernel.  One function holds both layouts so the
    word padding/reshape math cannot drift between them.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    xla = mode.use_xla()
    pw = jnp.asarray(plane_words, jnp.uint32)
    B, P, NW = pw.shape
    assert P == 32, "expect one row per negabinary digit"
    need = -(-max(n, 1) // (GROUP * _UNPACK_W))
    R = -(-need // ROWS_B) * ROWS_B
    pad = R * _UNPACK_W - NW
    padb = 0
    if mesh is not None:
        from ...parallel import codec_mesh
        padb = codec_mesh.pad_to_shards(B, mesh)
    if pad or padb:
        pw = jnp.pad(pw, ((0, padb), (0, 0), (0, pad)))
    pw = pw.reshape(B + padb, 32, R, _UNPACK_W)
    lz = _lz_array(low_zero, B)
    if padb:
        lz = jnp.pad(lz, ((0, padb), (0, 0), (0, 0)))

    if xla:
        fn, params = bitplane_unpack_xla, {}
    else:
        fn, params = bitplane_unpack_pallas, dict(interpret=interpret)

    if mesh is None:
        dispatch.record("bitplane_unpack", interpret=interpret and not xla)
        q, nb = jax.vmap(functools.partial(fn, **params))(pw, lz)
    else:
        dispatch.record("bitplane_unpack", interpret=interpret and not xla,
                        devices=codec_mesh.shard_count(mesh))
        q, nb = codec_mesh.shard_vmap(fn, mesh, n_out=2, **params)(pw, lz)
    q = q.reshape(B + padb, -1)[:B, :n]
    nb = nb.reshape(B + padb, -1)[:B, :n]
    if with_nb:
        return q, nb
    return q


def bitplane_unpack_sharded(plane_words, n: int, *, mesh, low_zero=0,
                            with_nb: bool = False,
                            interpret: bool | None = None):
    """Sharded twin: ``bitplane_unpack_batch`` with the (B, 32, NW) stack
    split over the 1-D codec ``mesh`` (thin alias; equal-n groups only,
    like the batched twin — per-chunk ``low_zero`` rides along)."""
    return bitplane_unpack_batch(plane_words, n, low_zero=low_zero,
                                 with_nb=with_nb, interpret=interpret,
                                 mesh=mesh)
