"""Public wrappers for the fused interpolate+quantize kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import arith
from .. import dispatch, mode
from .kernel import interp_quant_pallas, interp_quant_xla


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interp_quant(x, xhat, *, s: int, eb: float, interp: str = "cubic",
                 interpret: bool | None = None):
    """Fused phase sweep of one (R, C) problem.

    Returns (q int32 (R, T), pred (R, T)) for targets at odd multiples of s
    along the last axis, in the arithmetic of ``x``'s dtype (see
    ``core.arith``); bins out of range are ``arith.QSENTINEL``.  The
    writeback and escape screen are the caller's (``arith.screen``).
    """
    q, pred = interp_quant_batch(jnp.asarray(x)[None],
                                 jnp.asarray(xhat)[None], s=s, eb=eb,
                                 interp=interp, interpret=interpret)
    return q[0], pred[0]


def interp_quant_batch(x, xhat, *, s: int, eb: float, interp: str = "cubic",
                       interpret: bool | None = None, mesh=None):
    """Batched phase sweep over stacked equal-shape chunks: (B, R, C).

    ``jax.vmap`` turns the batch axis into an extra grid dimension of ONE
    kernel launch, so B chunks cost a single dispatch instead of B; every
    operation is elementwise across the batch, so per-chunk results are
    bit-identical to B lone calls.

    With ``mesh`` (a 1-D codec mesh), the batch axis is zero-padded to a
    mesh multiple (``codec_mesh.pad_to_shards``) and ``shard_map`` places
    consecutive rows on consecutive devices, each running the same vmapped
    kernel — one collective-free launch per device, one *logical* dispatch
    total (recorded with ``devices=mesh size``), pad rows sliced off.  A
    stack already placed by ``codec_mesh.place`` (a mesh multiple) is
    neither padded nor sliced: its outputs keep the pad rows.
    """
    if interpret is None:
        interpret = not _on_tpu()
    xla = mode.use_xla()
    x = jnp.asarray(x)
    xhat = jnp.asarray(xhat, x.dtype)
    c = arith.consts(eb, x.dtype)
    B = x.shape[0]
    padb = 0
    if mesh is not None:
        from ...parallel import codec_mesh
        padb = codec_mesh.pad_to_shards(B, mesh)
        if padb:
            x = jnp.pad(x, ((0, padb), (0, 0), (0, 0)))
            xhat = jnp.pad(xhat, ((0, padb), (0, 0), (0, 0)))

    if xla:
        fn, params = interp_quant_xla, dict(s=s, c=c, interp=interp)
    else:
        fn, params = interp_quant_pallas, dict(s=s, c=c, interp=interp,
                                               interpret=interpret)

    if mesh is None:
        dispatch.record("interp_quant", interpret=interpret and not xla)
        q, pred = jax.vmap(functools.partial(fn, **params))(x, xhat)
    else:
        dispatch.record("interp_quant", interpret=interpret and not xla,
                        devices=codec_mesh.shard_count(mesh))
        q, pred = codec_mesh.shard_vmap(fn, mesh, n_out=2, **params)(x, xhat)
    return q[:B], pred[:B]


def interp_quant_sharded(x, xhat, *, s: int, eb: float, mesh,
                         interp: str = "cubic",
                         interpret: bool | None = None):
    """Sharded phase sweep: ``interp_quant_batch`` with the (B, R, C)
    batch axis split over the 1-D codec ``mesh`` (thin alias; see the
    batched entry for the layout/dispatch contract)."""
    return interp_quant_batch(x, xhat, s=s, eb=eb, interp=interp,
                              interpret=interpret, mesh=mesh)
