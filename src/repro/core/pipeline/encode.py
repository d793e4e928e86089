"""Compression side of the codec pipeline (paper Fig. 2, left to right).

  x --interpolation predictor--> residuals y_l --quantize--> q_l
    --negabinary--> nb_l --bitplanes + XOR predictive coding--> blobs
    --container--> archive bytes

The per-phase sweep and the per-level packing both go through the resolved
:class:`~.backends.CodecBackend` (numpy reference or Pallas kernels);
archives are byte-compatible, so the decode path never needs to know which
backend wrote them.

``chunk_elems=N`` splits the array into independent slabs of ~N elements
along axis 0 and frames the per-slab archives in a v2 container
(``container.write_chunked_archive``).  Chunking bounds compression working
memory and is the unit of batched execution: chunks are scheduled in
*shape groups* (every interior slab has the same shape; only the ragged
tail differs), and when the backend ships batched primitives
(``decorrelate_batch`` / ``encode_level_batch``), each group runs the
whole stack through ONE vmapped kernel dispatch per (level, dim) phase and
one per level for the bitplane pack — instead of one per chunk each.
Groups are capped at ``MAX_BATCH_CHUNKS`` chunks per stack, so batching
keeps the memory bound chunking exists to provide.  Archives are
byte-identical either way (``batch_chunks=False`` forces the per-chunk
loop; the parity tests pin the equivalence).  v1 (unchunked) archives
remain the default and are always readable.

``shard=`` lifts the same scheduler onto a device mesh: with a 1-D codec
mesh (``"auto"`` = all local devices when more than one; see
``parallel.codec_mesh`` and ``docs/architecture.md``), each shape group's
stacked slab is split across the mesh and every device runs the backend's
batched kernels on its local chunk shard — one collective-free logical
dispatch per (level, dim) phase for the whole grid.  The scheduler is
shard-aware in two places: the group cap scales to ``MAX_BATCH_CHUNKS x
mesh size`` (``MAX_BATCH_CHUNKS`` stays the *per-device* working-set
bound), and ragged groups are padded up to a mesh multiple at the sharded
kernel entry points (all-zero pad problems, outputs sliced off).  Sharding
never changes bytes: per-chunk metadata, escapes and blobs are still
derived per chunk on the host, so sharded archives are byte-identical to
single-device ones.
"""
from __future__ import annotations

import zlib
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ... import trace
from .. import arith, bitplane, container, interpolation
from . import backends, spec
from .spec import ExecPolicy

# historical import site — tests and callers import the ``shard=`` policy
# from here; the logic itself lives with ExecPolicy in ``spec.py``
resolve_exec_mesh = spec.resolve_exec_mesh


def encode_array(x: np.ndarray, eb: float,
                 interp: str = interpolation.CUBIC, relative: bool = False,
                 chunk_elems: Optional[int] = None,
                 policy: Optional[ExecPolicy] = None,
                 version: Optional[int] = None) -> bytes:
    """Compress ``x`` with point-wise error bound ``eb`` (native entry).

    This is the policy-native encoder under ``repro.api.Codec.compress``:
    (eb, interp, relative, chunk_elems, version) are the *bytes-affecting*
    spec — the :class:`~.spec.ExecPolicy` only selects how the work
    executes (backend substrate, chunk batching, mesh sharding) and never
    changes the archive bytes.  ``relative=True`` interprets eb as a
    fraction of the value range.  ``chunk_elems`` switches to a chunked
    container with ~chunk_elems-sized independent slabs.

    ``version`` selects the container framing: 1 (plain v1, the unchunked
    default), 2 (chunk-major v2, the chunked default), or 3 (plane-major
    v3 — chunked compression laid out in retrieval-ladder order, see
    ``docs/format.md`` §3).  Compression itself is version-independent:
    v3 archives hold the exact per-chunk streams a v2 archive would,
    regrouped — only the byte layout (and thus the read access pattern)
    differs.  ``version=3`` without ``chunk_elems`` frames the whole
    array as one chunk.
    """
    policy = spec.DEFAULT_POLICY if policy is None else policy
    if version is None:
        version = 1 if chunk_elems is None else 2
    if version not in (1, 2, 3):
        raise ValueError(f"unknown container version {version!r}; "
                         "expected 1, 2 or 3")
    if version == 1 and chunk_elems is not None:
        raise ValueError("version=1 cannot hold chunks; "
                         "drop chunk_elems or use version 2 or 3")
    if version == 2 and chunk_elems is None:
        raise ValueError("version=2 is the chunked container; "
                         "pass chunk_elems (or use version=1)")
    with trace.request("encode"):
        x = np.asarray(x)
        trace.count("field_bytes", x.nbytes)
        return _encode(x, eb, interp, relative, chunk_elems, policy,
                       version)


def _encode(x: np.ndarray, eb: float, interp: str, relative: bool,
            chunk_elems: Optional[int], policy: ExecPolicy,
            version: int) -> bytes:
    """:func:`encode_array`'s work, inside its ``encode`` span."""
    with trace.span("encode.prepare", stage="container"):
        if relative:
            eb = eb * (float(x.max()) - float(x.min()) or 1.0)
        if eb <= 0:
            raise ValueError("error bound must be positive")
        # rejects bounds the field's arithmetic cannot honour
        arith.consts(eb, x.dtype)
        ctx = policy.bind(chunked=version != 1, encode=True)
        if version != 1:
            bounds = chunk_bounds(x.shape, chunk_elems
                                  if chunk_elems is not None
                                  else max(1, int(x.size)))
            groups = shape_groups([b - a for a, b in bounds],
                                  max_group=group_cap(ctx.mesh))
    if version == 1:
        return _compress_single(x, eb, interp, ctx.bk)
    bufs: List[Optional[bytes]] = [None] * len(bounds)
    for idxs in groups:
        if ctx.batch_encode and len(idxs) > 1:
            with trace.span("encode.prepare", stage="container"):
                xs = np.stack([x[bounds[i][0]: bounds[i][1]] for i in idxs])
            for i, buf in zip(idxs, _compress_batch(xs, eb, interp, ctx)):
                bufs[i] = buf
        else:
            for i in idxs:
                a, b = bounds[i]
                bufs[i] = _compress_single(x[a:b], eb, interp, ctx.bk)
    writer = (container.write_v3_archive if version == 3
              else container.write_chunked_archive)
    with trace.span("encode.container", stage="container"):
        return writer(x.shape, x.dtype, eb, interp, bounds, bufs)


def compress(x: np.ndarray, eb: float, interp: str = interpolation.CUBIC,
             relative: bool = False, backend: Optional[str] = "numpy",
             chunk_elems: Optional[int] = None,
             batch_chunks: Optional[bool] = None,
             shard=None) -> bytes:
    """Legacy free function; shim over :func:`encode_array`.

    Prefer ``repro.api.Codec(eb, ...).compress(x, policy=ExecPolicy(...))``
    — the kwargs map 1:1: (eb, interp, relative, chunk_elems) are the
    :class:`~repro.api.Codec` spec, (backend, batch_chunks, shard) the
    :class:`~.spec.ExecPolicy`.  Behavior and bytes are unchanged.
    """
    spec.warn_legacy("compress()", "Codec(eb, ...).compress(x, policy=...)")
    return encode_array(x, eb, interp=interp, relative=relative,
                        chunk_elems=chunk_elems,
                        policy=ExecPolicy(backend=backend,
                                          batch_chunks=batch_chunks,
                                          shard=shard))


def group_cap(mesh) -> int:
    """Chunks per scheduled stack: ``MAX_BATCH_CHUNKS`` per device.

    Unsharded that is the plain batch cap; on a mesh the stack is split
    across ``n`` devices, so an ``n``-times-larger group still bounds each
    device's working set at ``MAX_BATCH_CHUNKS`` chunk problems.
    """
    if mesh is None:
        return MAX_BATCH_CHUNKS
    from ...parallel import codec_mesh

    return MAX_BATCH_CHUNKS * codec_mesh.shard_count(mesh)


def chunk_bounds(shape, chunk_elems: int) -> List[Tuple[int, int]]:
    """Split axis 0 into slabs of ~chunk_elems elements (>=1 row each)."""
    if chunk_elems <= 0:
        raise ValueError("chunk_elems must be positive")
    if len(shape) == 0:
        raise ValueError("chunked compression needs at least one axis; "
                         "got a 0-d array")
    if int(np.prod(shape)) == 0:
        raise ValueError("cannot chunk an empty array of shape "
                         f"{tuple(shape)}")
    row_elems = int(np.prod(shape[1:])) if len(shape) > 1 else 1
    rows = max(1, chunk_elems // max(row_elems, 1))
    return [(a, min(a + rows, shape[0])) for a in range(0, shape[0], rows)]


#: chunks stacked per batched dispatch.  Chunking exists to bound codec
#: working memory, and a batch materializes its whole group as one array —
#: so groups are split into runs of at most this many chunks: memory stays
#: O(MAX_BATCH_CHUNKS x chunk), while the dispatch count still drops by up
#: to that factor.
MAX_BATCH_CHUNKS = 16


def shape_groups(row_counts: Sequence[int],
                 max_group: Optional[int] = MAX_BATCH_CHUNKS,
                 ) -> List[List[int]]:
    """Chunk indices grouped by identical row count (= identical slab shape).

    ``chunk_bounds`` makes every interior slab the same height, so this is
    typically one big group plus a singleton ragged tail; grouping by the
    actual count keeps the scheduler correct for any bounds list.  Groups
    larger than ``max_group`` are split into consecutive runs so a batched
    executor never stacks more than that many chunks at once (None = no
    cap).  Groups keep first-occurrence order and indices stay ascending,
    so iteration order — and thus every side effect, e.g. reader byte
    accounting — is deterministic.
    """
    groups: dict = {}
    for i, rc in enumerate(row_counts):
        groups.setdefault(rc, []).append(i)
    if max_group is None:
        return list(groups.values())
    return [g[a: a + max_group] for g in groups.values()
            for a in range(0, len(g), max_group)]


def _compress_single(x: np.ndarray, eb: float, interp: str,
                     bk: backends.CodecBackend) -> bytes:
    """One (chunk-sized) array -> one v1 archive, via the chosen backend."""
    shape, dtype = x.shape, x.dtype
    L = interpolation.num_levels(shape)
    _, qs, escs, anchors = bk.decorrelate(x, eb, interp)

    level_blobs, level_meta, esc_blobs = [], [], []
    for li in range(L):
        q = qs[li]
        blobs, nbits, delta = bk.encode_level(q, eb)
        level_blobs.append(blobs)
        level_meta.append(dict(level=L - li, n=int(q.size), nbits=nbits,
                               delta_table=delta.tolist()))
        esc_blobs.append(_pack_escapes(escs[li]))
    with trace.span("encode.container", stage="container"):
        return container.write_archive(shape, dtype, eb, interp, L, anchors,
                                       level_blobs, level_meta, esc_blobs,
                                       vmax=_vmax(x))


def _compress_batch(xs: np.ndarray, eb: float, interp: str,
                    ctx: spec.ExecContext) -> List[bytes]:
    """B equal-shape chunks (stacked on axis 0) -> B v1 archives.

    Exactly ``_compress_single`` per chunk, but the sweep and the per-level
    pack each run ONCE for the whole stack through the backend's batched
    primitives — or, with ``mesh``, through its *sharded* primitives, which
    split the stack across the mesh devices (each device then runs the
    batched kernels on its local chunk shard).  Per-chunk metadata (nbits,
    delta tables, escapes) is still derived from that chunk's own streams,
    so the archives are byte-identical to the per-chunk loop either way.
    """
    bk, mesh = ctx.bk, ctx.mesh
    B = xs.shape[0]
    shape, dtype = xs.shape[1:], xs.dtype
    L = interpolation.num_levels(shape)
    if mesh is not None:
        results = bk.decorrelate_sharded(xs, eb, interp, mesh)
    else:
        results = bk.decorrelate_batch(xs, eb, interp)

    blobs_pc: List[List[List[bytes]]] = [[] for _ in range(B)]
    meta_pc: List[List[dict]] = [[] for _ in range(B)]
    escb_pc: List[List[bytes]] = [[] for _ in range(B)]
    for li in range(L):
        with trace.span("pack.negabinary", stage="negabinary"):
            # stored bins lie within +-arith.QMAX: stack them as the
            # pack's int32 operand directly
            q2 = np.stack([results[b][1][li] for b in range(B)],
                          dtype=np.int32)
        if mesh is not None:
            enc = bk.encode_level_sharded(q2, eb, mesh)
        else:
            enc = bk.encode_level_batch(q2, eb)
        for b in range(B):
            blobs, nbits, delta = enc[b]
            blobs_pc[b].append(blobs)
            meta_pc[b].append(dict(level=L - li, n=int(q2.shape[1]),
                                   nbits=nbits, delta_table=delta.tolist()))
            escb_pc[b].append(_pack_escapes(results[b][2][li]))
    with trace.span("encode.container", stage="container"):
        return [container.write_archive(shape, dtype, eb, interp, L,
                                        results[b][3], blobs_pc[b],
                                        meta_pc[b], escb_pc[b],
                                        vmax=_vmax(xs[b]))
                for b in range(B)]


def _vmax(x: np.ndarray) -> Optional[float]:
    """Largest finite |x| of a float32 field (the header field the
    reader's rounding allowance needs); None under the float64 contract."""
    if arith.work_dtype(x.dtype) != np.float32:
        return None
    a = np.abs(x[np.isfinite(x)])
    return float(a.max()) if a.size else 0.0


def _pack_escapes(phase_escs) -> bytes:
    """Escape records (level-global flat idx, exact residuals) -> one blob."""
    with trace.span("pack.zlib", stage="zlib"):
        idx_parts = [i for i, v in phase_escs if i.size]
        val_parts = [v for i, v in phase_escs if i.size]
        if not idx_parts:
            return b""
        idx = np.concatenate(idx_parts).astype(np.int64)
        val = np.concatenate(val_parts).astype(np.float64)
        raw = np.int64(idx.size).tobytes() + idx.tobytes() + val.tobytes()
        blob = zlib.compress(raw, bitplane.zlib_level())
        trace.count("zlib_in_bytes", len(raw))
        trace.count("zlib_out_bytes", len(blob))
        return blob
