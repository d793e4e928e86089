"""Public wrappers for the fused progressive-decode megakernel."""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...core import arith
from .. import dispatch, mode
from ..bitplane_pack.kernel import GROUP, ROWS_B
from ..bitplane_pack.ops import _UNPACK_W, _lz_array
from .kernel import decode_fused_pallas, decode_fused_xla


def _scale_array(eb, dtype, B: int | None = None):
    """Runtime scale operand of the fused kernel: the float32 bin width
    ``w`` (float32 fields) or ``eb`` itself (float64 delta mode), as a
    (1, 1) array or a (B, 1, 1) batched one (a lone value broadcasts)."""
    e = np.asarray(eb, np.float64).reshape(-1)
    if B is not None and e.size == 1:
        e = np.full(B, e[0], np.float64)
    assert B is None or e.size == B, "per-chunk eb must match the batch size"
    if np.dtype(dtype) == np.float32:
        e = np.array([arith.consts(v, np.float32).w for v in e], np.float32)
    shape = (1, 1) if B is None else (B, 1, 1)
    return jnp.asarray(e.reshape(shape))


def _x64(dtype):
    return jax.enable_x64(True) if np.dtype(dtype) == np.float64 \
        else contextlib.nullcontext()


def decode_fused(plane_words, nb_old, n: int, *, eb: float, low_zero=0,
                 interpret: bool | None = None, dtype=np.float64):
    """One launch per level: (32, NW) packed plane words (+ the previous
    (n,) negabinary state in float64 mode) -> (nb_new (n,) uint32, out (n,)).

    ``dtype`` is the field's working dtype: float32 returns the full
    float32 residual of the new truncation, float64 Algorithm 2's delta
    ``(bin_new - bin_old) * 2 * eb`` (see ``kernel.py``); both bit-identical
    to the host arithmetic.
    """
    out = decode_fused_batch(jnp.asarray(plane_words, jnp.uint32)[None],
                             None if nb_old is None
                             else jnp.asarray(nb_old, jnp.uint32)[None],
                             n, eb=[eb], low_zero=[low_zero],
                             interpret=interpret, dtype=dtype)
    return out[0][0], out[1][0]


def _fused(planes, nb_old, low_zero, scale, *, interpret: bool,
           xla: bool):
    """One chunk's fused decode in the chosen kernel mode."""
    if xla:
        return decode_fused_xla(planes, nb_old, low_zero, scale)
    return decode_fused_pallas(planes, nb_old, low_zero, scale,
                               interpret=interpret)


def _fused_f32(planes, low_zero, scale, *, interpret: bool, xla: bool):
    """:func:`_fused` of a float32 field, which keeps no previous words."""
    return _fused(planes, None, low_zero, scale, interpret=interpret,
                  xla=xla)


def decode_fused_batch(plane_words, nb_old, n: int, *, eb, low_zero=0,
                       interpret: bool | None = None, mesh=None,
                       dtype=np.float64):
    """Batched twin over stacked equal-n chunks: (B, 32, NW) plane words
    (+ (B, n) previous states in float64 mode) -> ((B, n) nb_new, (B, n)
    out), ONE launch.  ``low_zero`` and ``eb`` may be scalars or length-B
    sequences — both are runtime per-row operands, so chunks with
    different loaded prefixes AND different level error bounds share the
    launch.

    With ``mesh``, the stack is zero-padded to a mesh multiple (pad rows
    decode to zeros, sliced back off) and split across the 1-D codec mesh
    like every other sharded kernel wrapper.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    xla = mode.use_xla()
    f32 = np.dtype(dtype) == np.float32
    with _x64(dtype):
        pw = jnp.asarray(plane_words, jnp.uint32)
        B, P, NW = pw.shape
        assert P == 32, "expect one row per negabinary digit"
        need = -(-max(n, 1) // (GROUP * _UNPACK_W))
        R = -(-need // ROWS_B) * ROWS_B
        C = R * _UNPACK_W * GROUP
        pad = R * _UNPACK_W - NW
        padb = 0
        if mesh is not None:
            from ...parallel import codec_mesh
            padb = codec_mesh.pad_to_shards(B, mesh)
        if pad or padb:
            pw = jnp.pad(pw, ((0, padb), (0, 0), (0, pad)))
        pw = pw.reshape(B + padb, 32, R, _UNPACK_W)
        lz = _lz_array(low_zero, B)
        sc = _scale_array(eb, dtype, B)
        if padb:
            lz = jnp.pad(lz, ((0, padb), (0, 0), (0, 0)))
            sc = jnp.pad(sc, ((0, padb), (0, 0), (0, 0)))
        args = [pw]
        if not f32:
            old = jnp.asarray(nb_old, jnp.uint32).reshape(B, -1)
            old = jnp.pad(old, ((0, padb), (0, C - old.shape[1])))
            args.append(old.reshape(B + padb, R, _UNPACK_W * GROUP))
        args += [lz, sc]

        fn = _fused_f32 if f32 else _fused
        params = dict(interpret=interpret, xla=xla)
        if mesh is None:
            dispatch.record("decode_fused", interpret=interpret and not xla)
            nb_new, out = jax.vmap(functools.partial(fn, **params))(*args)
        else:
            dispatch.record("decode_fused", interpret=interpret and not xla,
                            devices=codec_mesh.shard_count(mesh))
            nb_new, out = codec_mesh.shard_vmap(fn, mesh, n_out=2,
                                                **params)(*args)
        nb_new = nb_new.reshape(B + padb, -1)[:B, :n]
        out = out.reshape(B + padb, -1)[:B, :n]
        return nb_new, out


def decode_fused_sharded(plane_words, nb_old, n: int, *, mesh, eb,
                         low_zero=0, interpret: bool | None = None,
                         dtype=np.float64):
    """Sharded twin: ``decode_fused_batch`` with the stack split over the
    1-D codec ``mesh`` (thin alias)."""
    return decode_fused_batch(plane_words, nb_old, n, eb=eb,
                              low_zero=low_zero, interpret=interpret,
                              mesh=mesh, dtype=dtype)
