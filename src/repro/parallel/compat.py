"""Thin jax API adapters for the parallel substrate.

Every module that places work on a device mesh — the training launcher
(``launch/steps.py``), the logical-axis context (``parallel.api``), and the
codec's sharded chunk-grid executor (``parallel.codec_mesh``, see
``docs/architecture.md``) — goes through this file, so the keyword
conventions below live in one place:

:func:`shard_map`
    ``jax.shard_map`` with this repo's keyword vocabulary (``axis_names``
    optional, ``check_vma`` defaulting to False).

:func:`make_mesh`
    A :class:`jax.sharding.Mesh` from (axis sizes, axis names, optional
    explicit devices): ``jax.make_mesh`` when the device list is implicit,
    the explicit list verbatim otherwise.

Pure API adaptation, no policy: axis layout / sizing decisions live with
the callers (``launch/mesh.py`` for training, ``parallel.codec_mesh`` for
the codec).
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np


def shard_map(f, *, mesh, in_specs, out_specs, axis_names: Optional[set] = None,
              check_vma: bool = False):
    """Map ``f`` over shards of ``mesh`` (``jax.shard_map``)."""
    kw = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
              check_vma=check_vma)
    if axis_names is not None:
        kw["axis_names"] = axis_names
    return jax.shard_map(f, **kw)


def make_mesh(axis_shape: Tuple[int, ...], axis_names: Tuple[str, ...],
              devices: Optional[Sequence] = None) -> "jax.sharding.Mesh":
    """Build a :class:`jax.sharding.Mesh`.

    ``axis_shape``/``axis_names`` follow ``jax.make_mesh``; ``devices``
    optionally pins an explicit device list.  ``jax.make_mesh`` (which may
    reorder devices for interconnect locality) is used only when the
    device list is implicit — an explicit list is always honored verbatim,
    so callers that slice ``jax.devices()`` themselves (e.g.
    ``codec_mesh.codec_mesh(n)``) get a deterministic mesh.
    """
    if devices is None:
        return jax.make_mesh(axis_shape, axis_names)
    from jax.sharding import Mesh

    return Mesh(np.asarray(devices).reshape(axis_shape), axis_names)
