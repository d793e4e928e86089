"""Codec chunk-grid mesh: the sharded-execution seam of the v2 pipeline.

The chunked (``IPC2``) container frames independently decodable axis-0
slabs, and the shape-group scheduler already stacks equal-shaped chunks
into one batch array per group (see ``core/pipeline/encode.py`` and
``docs/architecture.md``).  That stack axis is a pure data-parallel axis:
no chunk ever reads another chunk's data, in either codec direction.  This
module maps it onto devices:

  * :func:`codec_mesh` builds the 1-D device mesh (axis ``"chunks"``) the
    sharded kernel entry points shard over;
  * :func:`resolve_shard` turns the user-facing ``shard=`` argument of
    ``compress`` / ``retrieve`` / ``refine`` / ``decompress``
    (``None`` | ``"auto"`` | a device count | an explicit 1-D ``Mesh``)
    into a mesh or ``None``;
  * :func:`shard_vmap` wraps a per-chunk kernel function in
    ``vmap``-inside-``shard_map`` under one cached ``jit``: every device
    runs the same vmapped kernel on its local slice of the chunk stack —
    one collective-free launch per device per call, compiled once per
    kernel parameters, mesh and shape;
  * :func:`pad_to_shards` rounds a ragged group's stack up to a multiple
    of the mesh size so ``shard_map`` can split it evenly (pad problems
    are all-zero and their outputs are sliced off; the codec never sees
    them), and :func:`place` does that padding on the host while it puts
    each device's rows straight onto that device.

Mesh construction and ``shard_map`` itself go through the version-tolerant
``parallel.compat`` shims.  The sharded path is an execution detail by
contract: archives stay byte-identical and reconstructions bit-identical
to the single-device path (``tests/test_sharded_codec.py`` pins this), so
``shard=`` can differ between the writer and every reader.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from . import compat

#: the codec mesh's only axis: position in the stacked chunk group
CODEC_AXIS = "chunks"

AUTO = "auto"


def codec_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over the first ``n_devices`` local devices (all by default).

    The deterministic ``jax.devices()`` prefix order matters: dispatch
    accounting and the parity tests assume device i always holds stack
    rows ``[i*per_dev, (i+1)*per_dev)``.
    """
    n = jax.device_count() if n_devices is None else int(n_devices)
    if not 1 <= n <= jax.device_count():
        raise ValueError(f"codec mesh needs 1..{jax.device_count()} local "
                         f"devices, got {n}")
    return compat.make_mesh((n,), (CODEC_AXIS,), devices=jax.devices()[:n])


def shard_count(mesh: Mesh) -> int:
    """Devices in a codec mesh (validates it is 1-D)."""
    if len(mesh.axis_names) != 1:
        raise ValueError("codec sharding needs a 1-D mesh (one chunk-stack "
                         f"axis); got axes {tuple(mesh.axis_names)}")
    return int(mesh.devices.size)


def resolve_shard(shard) -> Optional[Mesh]:
    """User-facing ``shard=`` -> codec mesh or None (unsharded).

    ``None``/``False`` -> unsharded.  ``"auto"`` -> a mesh over every
    local device when there is more than one, else None — single-device
    "auto" stays on the plain batched path rather than paying shard_map
    overhead for a 1-way split.  A positive ``int`` ``n`` ->
    ``codec_mesh(n)``, a hard request like an explicit mesh: it raises
    unless ``1 <= n <= jax.device_count()`` (a deployment states its chip
    count, so more local devices than that are left alone).  An explicit
    :class:`Mesh` is validated (1-D) and used as-is, including the
    1-device case (useful for parity tests).  Whether the *backend* can
    shard is the pipeline's call (``CodecBackend.shards_encode`` /
    ``shards_decode``): backends without sharded primitives fall back to
    their scalar/batched path.
    """
    if shard is None or shard is False:
        return None
    if isinstance(shard, Mesh):
        shard_count(shard)  # validates 1-D
        return shard
    if isinstance(shard, int) and not isinstance(shard, bool):
        return codec_mesh(shard)
    if shard == AUTO:
        return codec_mesh() if jax.device_count() > 1 else None
    raise ValueError(f"shard must be None, 'auto', a device count, or a "
                     f"1-D Mesh; got {shard!r}")


def pad_to_shards(b: int, mesh: Mesh) -> int:
    """Rows to append so a ``b``-row stack splits evenly over the mesh."""
    return (-b) % shard_count(mesh)


def place(arrays: Sequence[np.ndarray], mesh: Mesh
          ) -> Tuple[list, int]:
    """Host stacks of equal length ``b`` -> (device arrays, pad rows).

    Each array is padded with zero rows up to a mesh multiple and split
    on axis 0 over the mesh, every device receiving only its own rows:
    the host builds no padded copy and no device forwards rows to
    another.  The result is the layout :func:`shard_vmap`'s programs take,
    so they run without moving data first.
    """
    b = arrays[0].shape[0]
    pad = pad_to_shards(b, mesh)
    sharding = NamedSharding(mesh, PartitionSpec(CODEC_AXIS))

    def put(a: np.ndarray):
        def rows(index):
            lo, hi, _ = index[0].indices(b + pad)
            if hi <= b:
                return a[lo:hi]
            part = np.zeros((hi - lo,) + a.shape[1:], a.dtype)
            if lo < b:
                part[:b - lo] = a[lo:b]
            return part
        return jax.make_array_from_callback((b + pad,) + a.shape[1:],
                                            sharding, rows)

    return [put(a) for a in arrays], pad


def shard_vmap(fn, mesh: Mesh, *, n_out: int = 1, **params):
    """``jit(shard_map(vmap(partial(fn, **params))))`` over axis 0 of
    every argument.

    ``fn`` is a per-chunk kernel function (the exact function the batched
    entry points vmap) and ``params`` its keyword parameters, all
    hashable; the returned callable takes stacked arrays whose leading
    dimension is a multiple of the mesh size (see :func:`pad_to_shards`)
    and runs ``vmap(fn)`` on each device's local rows.  ``n_out`` is the
    number of outputs (each sharded the same way).  No collectives are
    emitted — the chunk axis is embarrassingly parallel — so the
    per-device program is exactly the single-device batched program on a
    smaller stack, which is why sharded results are bit-identical.

    The callable is cached on (``fn``, mesh, ``n_out``, ``params``): a
    fresh ``shard_map`` per call would be traced and compiled on every
    call, where this one compiles once per input shape.
    """
    return _shard_vmap(fn, mesh, n_out, tuple(sorted(params.items())))


#: sharded programs kept: each error bound brings sweep programs of its
#: own (the bound is a compile-time constant of ``interp_quant``)
_PROGRAMS = 1024


@functools.lru_cache(maxsize=_PROGRAMS)
def _shard_vmap(fn, mesh: Mesh, n_out: int, params: tuple):
    spec = PartitionSpec(CODEC_AXIS)
    out_specs = spec if n_out == 1 else tuple(spec for _ in range(n_out))
    body = jax.vmap(functools.partial(fn, **dict(params)))
    return jax.jit(compat.shard_map(body, mesh=mesh, in_specs=spec,
                                    out_specs=out_specs))
