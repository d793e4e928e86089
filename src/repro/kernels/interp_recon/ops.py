"""Public wrappers for the fused interpolate+add-residual kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import dispatch, mode
from .kernel import interp_recon_pallas, interp_recon_xla


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interp_recon(xhat, res, *, s: int, interp: str = "cubic",
                 interpret: bool | None = None):
    """Fused decode phase sweep of one (R, C) problem.

    ``xhat`` (R, C) is the partially reconstructed surface (even multiples of
    s already known), ``res`` (R, T) the dequantized residuals for the target
    columns (odd multiples of s).  Returns recon (R, T) = pred + res; the
    caller scatters it back into the sweep view (and applies any escape
    overrides) — the exact inverse of ``interp_quant``'s contract.
    """
    xhat = jnp.asarray(xhat)
    return interp_recon_batch(xhat[None], jnp.asarray(res, xhat.dtype)[None],
                              s=s, interp=interp, interpret=interpret)[0]


def interp_recon_batch(xhat, res, *, s: int, interp: str = "cubic",
                       interpret: bool | None = None, mesh=None):
    """Batched decode phase sweep over stacked equal-shape chunks: (B, R, C).

    ``jax.vmap`` makes the batch axis an extra grid dimension of ONE kernel
    launch — B chunks, one dispatch; every operation is elementwise across
    the batch, so per-chunk reconstructions are bit-identical to lone
    calls.

    With ``mesh``, the batch axis is zero-padded to a mesh multiple and
    split across the 1-D codec mesh by ``shard_map`` around the identical
    vmapped kernel — no collectives, one logical dispatch, ``mesh size``
    device launches, pad rows sliced off.
    """
    if interpret is None:
        interpret = not _on_tpu()
    xla = mode.use_xla()
    xhat = jnp.asarray(xhat)
    res = jnp.asarray(res, xhat.dtype)
    B = xhat.shape[0]
    padb = 0
    if mesh is not None:
        from ...parallel import codec_mesh
        padb = codec_mesh.pad_to_shards(B, mesh)
        if padb:
            xhat = jnp.pad(xhat, ((0, padb), (0, 0), (0, 0)))
            res = jnp.pad(res, ((0, padb), (0, 0), (0, 0)))

    if xla:
        def kernel(a, b):
            return interp_recon_xla(a, b, s=s, interp=interp)
    else:
        def kernel(a, b):
            return interp_recon_pallas(a, b, s=s, interp=interp,
                                       interpret=interpret)

    if mesh is None:
        dispatch.record("interp_recon", interpret=interpret and not xla)
        out = jax.vmap(kernel)(xhat, res)
    else:
        dispatch.record("interp_recon", interpret=interpret and not xla,
                        devices=codec_mesh.shard_count(mesh))
        out = codec_mesh.shard_vmap(kernel, mesh)(xhat, res)
    return out[:B]


def interp_recon_sharded(xhat, res, *, s: int, mesh, interp: str = "cubic",
                         interpret: bool | None = None):
    """Sharded decode phase sweep: ``interp_recon_batch`` with the batch
    axis split over the 1-D codec ``mesh`` (thin alias)."""
    return interp_recon_batch(xhat, res, s=s, interp=interp,
                              interpret=interpret, mesh=mesh)
