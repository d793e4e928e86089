"""IPComp archive container: random-access, independently decodable blocks.

v1 layout:  magic "IPC1" | u32 header_len | header JSON | blob section.
The header carries every per-level table the DP loader needs (plane sizes,
truncation-loss tables, escape sizes), so planning a retrieval touches ONLY
the header; the reader then fetches exactly the planned byte ranges —
``bytes_read`` is the retrieval-volume metric of Fig. 6/7.

v2 (chunked) layout:  magic "IPC2" | u32 header_len | header JSON |
concatenated v1 archives, one per fixed-size slab of the array (split along
axis 0).  Chunks are compressed and decoded independently — the unit of
batched/vmapped encoding and, later, of sharded compression — and each
chunk's interior is still the v1 format, so every per-chunk read goes
through the same ``ArchiveReader``.  The v2 header records only the slab
boundaries and byte extents.  ``parse_meta``/``ArchiveReader`` keep
accepting v1 archives unchanged; use ``open_reader`` to dispatch on the
magic when the version is unknown.

v3 (plane-major) layout:  magic "IPC3" | u32 header_len | header JSON |
contiguous *segments*.  Where v2 is chunk-major (a coarse read of N
chunks does N scattered reads and every refine re-seeks every chunk), v3
groups bytes across the chunk grid: first a base region (all chunks'
anchors, then all chunks' per-level escape blobs), then one segment per
(level, bitplane) holding every chunk's blob for that plane — segments
ordered by a rate-distortion *ladder* fixed at write time
(``loader.ladder_order``: best error-reduction-per-byte first).  A
fidelity ladder therefore reads monotone contiguous byte ranges of the
container — the access pattern HTTP-range / object-store serving wants
(``docs/format.md`` §3 is the normative spec).  Per-chunk headers ride in
the v3 header with absolute offsets, so each chunk still decodes through
the ordinary ``ArchiveReader`` over the staged prefix.

All readers sit on the :class:`~.bytesource.ByteSource` seam (in-memory
buffer, mmap-backed file, range-counting test double): ``read(offset,
size, tag)`` never assumes the archive is resident in memory.
"""
from __future__ import annotations

import json
import struct
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from .bytesource import BufferSource, ByteSource, as_source

MAGIC = b"IPC1"
MAGIC2 = b"IPC2"
MAGIC3 = b"IPC3"


class CorruptArchiveError(ValueError):
    """A buffer that is not a well-formed IPComp archive: wrong/unknown
    magic, truncated framing, undecodable header, or declared blob extents
    that fall outside the buffer.  Subclasses :class:`ValueError` so
    pre-existing ``except ValueError`` handling keeps working; raised with
    a message naming what is wrong and where, instead of leaking
    ``struct.unpack`` / ``json`` noise from the middle of the parser."""


def _read_exact(src: ByteSource, offset: int, size: int, what: str) -> bytes:
    """``src.read`` that enforces the no-short-reads contract.

    :class:`~.bytesource.ByteSource.read` declares short reads a contract
    violation, but an implementation over real storage (a truncated file,
    a remote object whose tail was never written) can still return fewer
    bytes than requested.  Every framing/data boundary in this module
    reads through here so that failure surfaces as a
    :class:`CorruptArchiveError` naming the boundary — never as a
    ``struct.error`` / ``json`` exception from the middle of the parser,
    and never as silently-corrupt decoded data.
    """
    data = bytes(src.read(offset, size))
    if len(data) != size:
        raise CorruptArchiveError(
            f"short read of {what}: requested [{offset}, {offset + size}) "
            f"but the source returned {len(data)} of {size} bytes")
    return data


def _magic(src: ByteSource) -> bytes:
    """The 4 magic bytes (empty-safe): the version dispatch token."""
    return bytes(src.read(0, 4))


def _framing(src: ByteSource, what: str):
    """Shared framing checks -> (header_len, decoded header dict).

    Validates, in order, each boundary a truncated buffer can violate:
    the 4-byte magic, the 4-byte header length, the header body, and the
    header being decodable JSON.  The magic itself is checked by the
    caller (it is the version dispatch); everything after it is checked
    here.  Operates on a :class:`~.bytesource.ByteSource`, so parsing a
    file-backed archive touches exactly the framing + header bytes.
    """
    if src.size < 8:
        raise CorruptArchiveError(
            f"truncated {what}: {src.size} bytes, need at least 8 for "
            "magic + header length")
    (hlen,) = struct.unpack(
        "<I", _read_exact(src, 4, 4, f"{what} header length"))
    if 8 + hlen > src.size:
        raise CorruptArchiveError(
            f"truncated {what}: header claims {hlen} bytes but only "
            f"{src.size - 8} follow the framing")
    try:
        header = json.loads(
            _read_exact(src, 8, hlen, f"{what} header").decode())
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        raise CorruptArchiveError(f"undecodable {what} header: {e}") from e
    if not isinstance(header, dict):
        raise CorruptArchiveError(f"malformed {what} header: expected an "
                                  f"object, got {type(header).__name__}")
    return hlen, header


def _check_extent(offset: int, size: int, total: int, what: str) -> None:
    if offset < 0 or size < 0 or offset + size > total:
        raise CorruptArchiveError(
            f"corrupt archive: {what} extent [{offset}, {offset + size}) "
            f"falls outside the {total}-byte buffer")


@dataclass
class LevelMeta:
    level: int                 # L..1 (1 = finest)
    n: int                     # number of quantized scalars in this level
    nbits: int                 # occupied negabinary bits
    plane_sizes: List[int]     # compressed bytes per plane, MSB-first
    plane_offsets: List[int]   # absolute offsets into the archive
    delta_table: List[float]   # truncation loss per #discarded-planes b=0..nbits
    esc_size: int
    esc_offset: int


@dataclass
class ArchiveMeta:
    shape: List[int]
    dtype: str
    eb: float
    interp: str
    L: int
    anchors_offset: int
    anchors_size: int
    anchors_shape: List[int]
    levels: List[LevelMeta]
    header_end: int
    total_size: int
    #: largest finite |x| of the field — float32 archives only, where the
    #: reader's rounding allowance (``loader.rounding_allowance``) needs it
    vmax: Optional[float] = None

    @property
    def n_elements(self) -> int:
        return int(np.prod(self.shape))

    @property
    def work_dtype(self) -> np.dtype:
        """Arithmetic the archive was written under (``docs/format.md``
        §6): float32 for a float32 header that records ``vmax``, float64
        for every other header — float64 fields, and float32 archives
        written before the float32 contract existed."""
        f32 = self.dtype == "float32" and self.vmax is not None
        return np.dtype(np.float32 if f32 else np.float64)


def write_archive(shape, dtype, eb, interp, L, anchors: np.ndarray,
                  level_blobs: List[List[bytes]], level_meta: List[Dict],
                  esc_blobs: List[bytes], vmax: Optional[float] = None,
                  ) -> bytes:
    """Assemble the archive. level index 0 = level L (coarsest).  ``vmax``
    (largest finite |x|) is recorded for float32 fields and required
    there."""
    levels = []
    blobs: List[bytes] = []
    cursor = [0]  # patched after header length known

    def put(b: bytes) -> int:
        off = cursor[0]
        blobs.append(b)
        cursor[0] += len(b)
        return off

    anc_bytes = anchors.astype(np.float64).tobytes()
    anc_off = put(anc_bytes)
    for i, (pl, lm, eb_blob) in enumerate(zip(level_blobs, level_meta, esc_blobs)):
        offs = [put(b) for b in pl]
        eo = put(eb_blob)
        levels.append(dict(
            level=lm["level"], n=lm["n"], nbits=lm["nbits"],
            plane_sizes=[len(b) for b in pl], plane_offsets=offs,
            delta_table=lm["delta_table"], esc_size=len(eb_blob), esc_offset=eo,
        ))

    def render(base: int) -> bytes:
        abs_levels = [dict(lv, plane_offsets=[o + base for o in lv["plane_offsets"]],
                           esc_offset=lv["esc_offset"] + base) for lv in levels]
        header = dict(shape=list(shape), dtype=str(dtype), eb=float(eb),
                      interp=interp, L=int(L), anchors_offset=anc_off + base,
                      anchors_size=len(anc_bytes),
                      anchors_shape=list(anchors.shape), levels=abs_levels)
        if vmax is not None:
            header["vmax"] = float(vmax)
        hj = json.dumps(header, separators=(",", ":")).encode()
        return MAGIC + struct.pack("<I", len(hj)) + hj

    # fixed-point on header length (offsets may gain digits once absolute)
    base = 0
    for _ in range(8):
        prefix = render(base)
        if len(prefix) == base:
            break
        base = len(prefix)
    return prefix + b"".join(blobs)


def _assemble_v1_meta(h: dict, header_end: int, total: int,
                      what: str = "v1 archive") -> ArchiveMeta:
    """Header dict -> validated :class:`ArchiveMeta` (shared by the v1
    parser and the v3 per-chunk headers): structural consistency plus
    per-blob extent bounds against the ``total``-byte buffer."""
    try:
        levels = [LevelMeta(**lv) for lv in h["levels"]]
        meta = ArchiveMeta(shape=h["shape"], dtype=h["dtype"], eb=h["eb"],
                           interp=h["interp"], L=h["L"],
                           anchors_offset=h["anchors_offset"],
                           anchors_size=h["anchors_size"],
                           anchors_shape=h["anchors_shape"], levels=levels,
                           header_end=header_end, total_size=total,
                           vmax=h.get("vmax"))
    except (KeyError, TypeError) as e:
        raise CorruptArchiveError(f"malformed {what} header: {e}") from e
    if meta.vmax is not None and not isinstance(meta.vmax, (int, float)):
        raise CorruptArchiveError(
            f"malformed {what} header: 'vmax' must be a number, got "
            f"{meta.vmax!r}")
    _check_extent(meta.anchors_offset, meta.anchors_size, total, "anchors")
    if meta.anchors_size != 8 * int(np.prod(meta.anchors_shape)):
        raise CorruptArchiveError(
            f"corrupt archive: anchors_size {meta.anchors_size} does not "
            f"match anchors_shape {tuple(meta.anchors_shape)} "
            "(8 bytes/element)")
    for li, lv in enumerate(meta.levels):
        # internal consistency, so a header-corrupt buffer fails HERE and
        # not as an IndexError when a plan first touches the bad level
        if not (len(lv.plane_offsets) == len(lv.plane_sizes) == lv.nbits
                and len(lv.delta_table) == lv.nbits + 1):
            raise CorruptArchiveError(
                f"corrupt archive: level {li} declares nbits={lv.nbits} "
                f"but carries {len(lv.plane_offsets)} plane offsets / "
                f"{len(lv.plane_sizes)} sizes / "
                f"{len(lv.delta_table)}-entry delta table")
        for pi, (off, size) in enumerate(zip(lv.plane_offsets,
                                             lv.plane_sizes)):
            _check_extent(off, size, total, f"level {li} plane {pi}")
        _check_extent(lv.esc_offset, lv.esc_size, total,
                      f"level {li} escapes")
    return meta


def _check_v1_blob_order(meta: ArchiveMeta) -> None:
    """Reject overlapping or out-of-order v1 blob extents.

    ``write_archive`` lays blobs out strictly in order — anchors, then per
    level its planes MSB-first then its escapes — with no overlap, and
    ``docs/format.md`` §1 makes that order normative.  Bounds checks alone
    accept headers whose extents alias each other (two planes sharing
    bytes, an escape blob inside the anchors) — structurally valid JSON
    that no writer produces and that silently decodes garbage.  Zero-size
    blobs carry no bytes and are exempt from the ordering (their recorded
    offset is meaningless).
    """
    cursor = meta.header_end

    def step(off: int, size: int, what: str) -> None:
        nonlocal cursor
        if size == 0:
            return
        if off < cursor:
            raise CorruptArchiveError(
                f"corrupt archive: {what} extent [{off}, {off + size}) "
                f"overlaps or precedes the preceding blob (expected "
                f"offset >= {cursor})")
        cursor = off + size

    step(meta.anchors_offset, meta.anchors_size, "anchors")
    for li, lv in enumerate(meta.levels):
        for pi, (off, size) in enumerate(zip(lv.plane_offsets,
                                             lv.plane_sizes)):
            step(off, size, f"level {li} plane {pi}")
        step(lv.esc_offset, lv.esc_size, f"level {li} escapes")


def parse_meta(buf) -> ArchiveMeta:
    """Parse a v1 header (accepts bytes, a zero-copy memoryview, or a
    :class:`~.bytesource.ByteSource`).

    Truncated / undecodable buffers raise :class:`CorruptArchiveError`
    with the failing boundary named; declared blob extents are checked
    against the buffer — bounds, overlap, and write order — so a
    truncated or aliased *data* section fails here, at parse time,
    instead of as a short read deep inside a retrieval.
    """
    src = as_source(buf)
    magic = _magic(src)
    if magic in (MAGIC2, MAGIC3):
        raise ValueError(
            f"{'chunked (v2)' if magic == MAGIC2 else 'plane-major (v3)'} "
            "archive: use "
            f"{'parse_chunked_meta' if magic == MAGIC2 else 'parse_v3_meta'}"
            " / open_reader, or the top-level retrieve()")
    if magic != MAGIC:
        raise CorruptArchiveError(
            "not an IPComp archive: expected magic "
            f"{MAGIC!r}, {MAGIC2!r} or {MAGIC3!r}, got {magic!r}")
    hlen, h = _framing(src, "v1 archive")
    meta = _assemble_v1_meta(h, 8 + hlen, src.size)
    _check_v1_blob_order(meta)
    return meta


class ArchiveReader:
    """Byte-range reader with retrieval-volume accounting.

    Mirrors object-store / parallel-FS partial reads: the header is always
    resident (it is the index), data blobs are fetched on demand and
    counted.  Backed by a :class:`~.bytesource.ByteSource` (any bytes-like
    object coerces to an in-memory source), so the same reader serves
    in-memory buffers, mmap-backed files, and range-accounting doubles.
    """

    def __init__(self, buf, meta: Optional[ArchiveMeta] = None):
        self.src = as_source(buf)
        # meta is immutable once parsed: callers that already validated the
        # buffer (repro.api.Archive) pass it in so a new reader — a fresh
        # bytes_read accounting scope — does not re-parse the header
        self.meta = parse_meta(self.src) if meta is None else meta
        self.bytes_read = 0          # data-blob bytes fetched so far
        self._fetched: set = set()
        #: opaque hashable token identifying *which archive bytes* this
        #: reader serves, for cross-session plane-cache keying (None =
        #: never cached).  Set by the session/server that owns the reader;
        #: equal tokens MUST mean identical underlying archive bytes.
        self.cache_scope = None

    def read(self, offset: int, size: int, tag: str) -> bytes:
        # fetch and validate BEFORE accounting: a failing/short read (a
        # remote source out of retries, a truncated file) must not mark
        # the tag fetched — a successful retry then still counts its bytes
        data = _read_exact(self.src, offset, size, f"blob {tag!r}") \
            if size else b""
        if size and tag not in self._fetched:
            self._fetched.add(tag)
            self.bytes_read += size
        return data

    def plane_fetched(self, level_idx: int, plane_idx: int) -> bool:
        """Has this reader (= this accounting scope) already fetched the
        given plane blob?  Used by the plane cache to credit exactly the
        fetch bytes a cache hit avoids."""
        return f"L{level_idx}P{plane_idx}" in self._fetched

    def fork(self) -> "ArchiveReader":
        """An independent accounting branch of this reader: same bytes and
        meta, same fetched-range history and cumulative ``bytes_read`` at
        the fork point — after which the two readers count independently.
        This is how a refine that branches off a shared session keeps its
        own retrieval-volume ledger (cumulative over its whole ancestry)
        without sibling branches bleeding fetches into each other."""
        dup = ArchiveReader(self.src, meta=self.meta)
        dup.bytes_read = self.bytes_read
        dup._fetched = set(self._fetched)
        dup.cache_scope = self.cache_scope
        return dup

    def anchors(self) -> np.ndarray:
        m = self.meta
        raw = self.read(m.anchors_offset, m.anchors_size, "anchors")
        return np.frombuffer(raw, np.float64).reshape(m.anchors_shape)

    def plane(self, level_idx: int, plane_idx: int) -> bytes:
        lv = self.meta.levels[level_idx]
        return self.read(lv.plane_offsets[plane_idx], lv.plane_sizes[plane_idx],
                         f"L{level_idx}P{plane_idx}")

    def escapes(self, level_idx: int) -> bytes:
        lv = self.meta.levels[level_idx]
        return self.read(lv.esc_offset, lv.esc_size, f"L{level_idx}E")


# ------------------------------------------------------------- v2 (chunked)

@dataclass
class ChunkMeta:
    start: int                 # slab [start, stop) along axis 0
    stop: int
    offset: int                # absolute byte offset of the chunk's archive
    size: int                  # byte length of the chunk's archive


@dataclass
class ChunkedMeta:
    shape: List[int]
    dtype: str
    eb: float
    interp: str
    chunks: List[ChunkMeta]
    header_end: int
    total_size: int

    @property
    def n_elements(self) -> int:
        return int(np.prod(self.shape))


def write_chunked_archive(shape, dtype, eb, interp,
                          bounds: List, chunk_bufs: List[bytes]) -> bytes:
    """Frame independently compressed slab archives into one v2 container.

    ``bounds[i] = (start, stop)`` is chunk i's row range along axis 0;
    ``chunk_bufs[i]`` is its complete v1 archive.  The header deliberately
    carries no record of the producing backend: numpy- and jax-written
    archives are byte-identical, which the parity tests pin down.
    """
    sizes = [len(b) for b in chunk_bufs]
    rel = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)

    def render(base: int) -> bytes:
        chunks = [dict(start=int(a), stop=int(b), offset=int(rel[i]) + base,
                       size=sizes[i]) for i, (a, b) in enumerate(bounds)]
        header = dict(version=2, shape=list(shape), dtype=str(dtype),
                      eb=float(eb), interp=interp, chunks=chunks)
        hj = json.dumps(header, separators=(",", ":")).encode()
        return MAGIC2 + struct.pack("<I", len(hj)) + hj

    base = 0
    for _ in range(8):  # fixed-point on header length (offsets gain digits)
        prefix = render(base)
        if len(prefix) == base:
            break
        base = len(prefix)
    return prefix + b"".join(chunk_bufs)


def parse_chunked_meta(buf) -> ChunkedMeta:
    """Parse a v2 header; see :func:`parse_meta` for the error contract.

    Chunk extents are checked for bounds AND for the normative write
    order — ascending, non-overlapping, starting at or after the header
    end — so a header whose chunks alias each other's bytes (decoding
    garbage) or run backward (defeating streamed reads) is rejected here.
    """
    src = as_source(buf)
    if _magic(src) != MAGIC2:
        raise CorruptArchiveError(
            "not a chunked (v2) IPComp archive: expected magic "
            f"{MAGIC2!r}, got {_magic(src)!r}")
    hlen, h = _framing(src, "v2 archive")
    try:
        chunks = [ChunkMeta(**c) for c in h["chunks"]]
        meta = ChunkedMeta(shape=h["shape"], dtype=h["dtype"], eb=h["eb"],
                           interp=h["interp"], chunks=chunks,
                           header_end=8 + hlen, total_size=src.size)
    except (KeyError, TypeError) as e:
        raise CorruptArchiveError(f"malformed v2 archive header: {e}") from e
    cursor = meta.header_end
    for i, cm in enumerate(meta.chunks):
        _check_extent(cm.offset, cm.size, src.size, f"chunk {i}")
        if cm.offset < cursor:
            raise CorruptArchiveError(
                f"corrupt archive: chunk {i} extent "
                f"[{cm.offset}, {cm.offset + cm.size}) overlaps or "
                f"precedes the preceding chunk (expected offset >= "
                f"{cursor})")
        cursor = cm.offset + cm.size
        if not 0 <= cm.start <= cm.stop:
            raise CorruptArchiveError(
                f"corrupt archive: chunk {i} claims slab rows "
                f"[{cm.start}, {cm.stop})")
    return meta


class ChunkedArchiveReader:
    """Per-chunk ``ArchiveReader``s sharing one retrieval-volume counter.

    Sub-readers are created lazily and cached, so refinement re-reads of a
    chunk hit the same fetched-range set and ``bytes_read`` stays the true
    cumulative retrieval volume across progressive calls.
    """

    def __init__(self, buf, meta: Optional[ChunkedMeta] = None):
        self.src = as_source(buf)
        self.meta = parse_chunked_meta(self.src) if meta is None else meta
        self._readers: Dict[int, ArchiveReader] = {}
        #: see :attr:`ArchiveReader.cache_scope`; chunk sub-readers derive
        #: ``(cache_scope, chunk_index)`` so every chunk keys independently
        self.cache_scope = None

    def chunk_reader(self, i: int) -> ArchiveReader:
        if i not in self._readers:
            cm = self.meta.chunks[i]
            # a window, not a slice: sub-reader offsets are chunk-relative
            # but the reads land on the shared source at absolute container
            # positions, so range accounting sees real archive offsets
            self._readers[i] = ArchiveReader(
                self.src.window(cm.offset, cm.size))
        sub = self._readers[i]
        if self.cache_scope is not None and sub.cache_scope is None:
            sub.cache_scope = (self.cache_scope, i)
        return sub

    def fork(self) -> "ChunkedArchiveReader":
        """Independent accounting branch (see :meth:`ArchiveReader.fork`):
        every already-opened chunk sub-reader is forked with its fetch
        history, so the branch's aggregated ``bytes_read`` starts at the
        fork point and diverges independently."""
        dup = ChunkedArchiveReader(self.src, meta=self.meta)
        dup.cache_scope = self.cache_scope
        dup._readers = {i: r.fork() for i, r in self._readers.items()}
        return dup

    @property
    def bytes_read(self) -> int:
        return sum(r.bytes_read for r in self._readers.values())


# --------------------------------------------------------- v3 (plane-major)

@dataclass
class SlabMeta:
    """Chunk i's row range along axis 0 (v3 carries no per-chunk byte
    extent — chunk bytes are scattered across the plane-major segments;
    the per-chunk headers hold the absolute blob offsets)."""
    start: int
    stop: int


@dataclass
class SegmentMeta:
    """One contiguous v3 segment: every chunk's blob for one archive
    component, concatenated in chunk order.

    ``kind`` is ``"anchors"`` (level/plane = -1), ``"escapes"`` (one per
    level, plane = -1), or ``"planes"`` (one per (level, bitplane)).
    Segments tile the data section contiguously in ladder order.
    """
    kind: str
    level: int
    plane: int
    offset: int
    size: int


@dataclass
class V3Meta:
    shape: List[int]
    dtype: str
    eb: float
    interp: str
    chunks: List[SlabMeta]
    chunk_metas: List[ArchiveMeta]     # per-chunk v1 headers, absolute offsets
    segments: List[SegmentMeta]        # contiguous, ladder order
    header_end: int
    total_size: int
    # derived at parse time:
    plane_segments: List[SegmentMeta] = field(default_factory=list)
    base_end: int = 0                  # end of the anchors+escapes region
    cum_bytes: List[int] = field(default_factory=list)

    @property
    def n_elements(self) -> int:
        return int(np.prod(self.shape))

    def ladder_keeps(self, t: int) -> List[List[int]]:
        """Per-chunk MSB-first keep counts implied by the first ``t``
        plane segments of the ladder.  Within a level, segments appear in
        ascending plane order (enforced at parse), so the count of level-l
        segments in the prefix IS chunk c's loaded-plane prefix for level
        l (clamped to the chunk's own nbits — a ragged tail chunk may
        occupy fewer bits than the grid maximum)."""
        counts: Dict[int, int] = {}
        for s in self.plane_segments[:t]:
            counts[s.level] = counts.get(s.level, 0) + 1
        return [[min(counts.get(li, 0), lv.nbits)
                 for li, lv in enumerate(m.levels)]
                for m in self.chunk_metas]


def write_v3_archive(shape, dtype, eb, interp,
                     bounds: List, chunk_bufs: List[bytes]) -> bytes:
    """Re-lay per-slab v1 archives into one plane-major v3 container.

    Takes exactly the inputs of :func:`write_chunked_archive` — so any v2
    producer (and any existing v2 archive, via its chunk extents) can emit
    v3 — but instead of concatenating the chunk archives whole, their
    blobs are regrouped across the chunk grid: anchors segment, per-level
    escapes segments, then one segment per (level, bitplane) in the greedy
    rate-distortion ladder order (``loader.ladder_order``: most error
    reduction per byte first, SAFE propagation, deterministic
    tie-breaks).  The layout IS the retrieval schedule: a fidelity ladder
    reads a monotonically growing contiguous prefix of the data section.
    """
    from . import loader  # function-level: loader imports this module

    metas = [parse_meta(b) for b in chunk_bufs]
    order = loader.ladder_order(metas)
    nlev = max(len(m.levels) for m in metas)

    blobs: List[bytes] = []
    cursor = [0]                       # relative to the data section
    segments: List[dict] = []

    def put(buf_i: int, off: int, size: int) -> int:
        pos = cursor[0]
        blobs.append(bytes(chunk_bufs[buf_i][off: off + size]))
        cursor[0] += size
        return pos

    def seg(kind: str, level: int, plane: int, members) -> None:
        start = cursor[0]
        for c, off, size in members:
            rel_offsets[c][kind, level, plane] = put(c, off, size)
        segments.append(dict(kind=kind, level=level, plane=plane,
                             offset=start, size=cursor[0] - start))

    rel_offsets: List[Dict[tuple, int]] = [{} for _ in metas]
    seg("anchors", -1, -1,
        [(c, m.anchors_offset, m.anchors_size) for c, m in enumerate(metas)])
    for li in range(nlev):
        seg("escapes", li, -1,
            [(c, m.levels[li].esc_offset, m.levels[li].esc_size)
             for c, m in enumerate(metas) if li < len(m.levels)])
    for li, k in order:
        seg("planes", li, k,
            [(c, m.levels[li].plane_offsets[k], m.levels[li].plane_sizes[k])
             for c, m in enumerate(metas)
             if li < len(m.levels) and k < m.levels[li].nbits])

    def render(base: int) -> bytes:
        chunk_headers = []
        for c, m in enumerate(metas):
            rel = rel_offsets[c]
            levels = [dict(
                level=lv.level, n=lv.n, nbits=lv.nbits,
                plane_sizes=list(lv.plane_sizes),
                plane_offsets=[rel["planes", li, k] + base
                               for k in range(lv.nbits)],
                delta_table=list(lv.delta_table), esc_size=lv.esc_size,
                esc_offset=rel["escapes", li, -1] + base,
            ) for li, lv in enumerate(m.levels)]
            chunk_headers.append(dict(
                shape=list(m.shape), dtype=m.dtype, eb=m.eb,
                interp=m.interp, L=m.L,
                anchors_offset=rel["anchors", -1, -1] + base,
                anchors_size=m.anchors_size,
                anchors_shape=list(m.anchors_shape), levels=levels))
            if m.vmax is not None:
                chunk_headers[-1]["vmax"] = m.vmax
        header = dict(
            version=3, shape=list(shape), dtype=str(dtype), eb=float(eb),
            interp=interp,
            chunks=[dict(start=int(a), stop=int(b)) for a, b in bounds],
            chunk_headers=chunk_headers,
            segments=[dict(s, offset=s["offset"] + base) for s in segments])
        hj = json.dumps(header, separators=(",", ":")).encode()
        return MAGIC3 + struct.pack("<I", len(hj)) + hj

    base = 0
    for _ in range(8):  # fixed-point on header length (offsets gain digits)
        prefix = render(base)
        if len(prefix) == base:
            break
        base = len(prefix)
    return prefix + b"".join(blobs)


def parse_v3_meta(buf) -> V3Meta:
    """Parse + validate a v3 header; see :func:`parse_meta` for the error
    contract.

    Beyond framing and per-blob bounds, the segment directory is held to
    the format's structural promises — they are what make the streaming
    access pattern provable, so violations are corruption, not style:

    * segments tile ``[header_end, total_size)`` contiguously, in order;
    * all base segments (anchors, escapes) precede all plane segments,
      and within a level plane segments appear MSB-first (ascending);
    * every chunk blob lies inside its matching segment, blobs sit in
      chunk order, and each segment's size is exactly its blobs' sum.
    """
    src = as_source(buf)
    if _magic(src) != MAGIC3:
        raise CorruptArchiveError(
            "not a plane-major (v3) IPComp archive: expected magic "
            f"{MAGIC3!r}, got {_magic(src)!r}")
    hlen, h = _framing(src, "v3 archive")
    total = src.size
    header_end = 8 + hlen
    try:
        if h.get("version") != 3:
            raise CorruptArchiveError(
                f"corrupt archive: v3 magic but header version "
                f"{h.get('version')!r}")
        slabs = [SlabMeta(start=int(c["start"]), stop=int(c["stop"]))
                 for c in h["chunks"]]
        segments = [SegmentMeta(kind=s["kind"], level=int(s["level"]),
                                plane=int(s["plane"]), offset=int(s["offset"]),
                                size=int(s["size"])) for s in h["segments"]]
        chunk_metas = [_assemble_v1_meta(ch, header_end, total,
                                         what=f"v3 chunk {c}")
                       for c, ch in enumerate(h["chunk_headers"])]
        if len(slabs) != len(chunk_metas):
            raise CorruptArchiveError(
                f"corrupt archive: {len(slabs)} chunk slabs but "
                f"{len(chunk_metas)} chunk headers")
        meta = V3Meta(shape=h["shape"], dtype=h["dtype"], eb=h["eb"],
                      interp=h["interp"], chunks=slabs,
                      chunk_metas=chunk_metas, segments=segments,
                      header_end=header_end, total_size=total)
    except (KeyError, TypeError) as e:
        raise CorruptArchiveError(f"malformed v3 archive header: {e}") from e
    for i, cm in enumerate(meta.chunks):
        if not 0 <= cm.start <= cm.stop:
            raise CorruptArchiveError(
                f"corrupt archive: chunk {i} claims slab rows "
                f"[{cm.start}, {cm.stop})")

    # -- segment directory: contiguity, ordering, and a (kind, level,
    #    plane) index for the blob containment pass below
    seg_index: Dict[tuple, SegmentMeta] = {}
    cursor = header_end
    seen_planes = False
    last_plane: Dict[int, int] = {}
    for si, s in enumerate(meta.segments):
        if s.kind not in ("anchors", "escapes", "planes"):
            raise CorruptArchiveError(
                f"corrupt archive: segment {si} has unknown kind "
                f"{s.kind!r}")
        _check_extent(s.offset, s.size, total, f"segment {si}")
        if s.offset != cursor:
            raise CorruptArchiveError(
                f"corrupt archive: segment {si} ({s.kind}) starts at "
                f"{s.offset}, expected {cursor} — v3 segments must tile "
                "the data section contiguously in ladder order")
        cursor = s.offset + s.size
        if s.kind == "planes":
            seen_planes = True
            prev = last_plane.get(s.level, -1)
            if s.plane != prev + 1:
                raise CorruptArchiveError(
                    f"corrupt archive: level {s.level} plane segment "
                    f"{s.plane} follows plane {prev} — within a level, "
                    "plane segments must appear MSB-first (ascending)")
            last_plane[s.level] = s.plane
        elif seen_planes:
            raise CorruptArchiveError(
                f"corrupt archive: base segment {si} ({s.kind}) after the "
                "first plane segment — anchors and escapes must precede "
                "the ladder")
        key = (s.kind, s.level, s.plane)
        if key in seg_index:
            raise CorruptArchiveError(
                f"corrupt archive: duplicate segment {key}")
        seg_index[key] = s
    if cursor != total:
        raise CorruptArchiveError(
            f"corrupt archive: v3 segments end at {cursor} but the buffer "
            f"is {total} bytes")

    # -- every chunk blob inside its matching segment, in chunk order,
    #    sizes summing exactly to the segment size (no gaps, no aliasing)
    sums: Dict[tuple, int] = {k: 0 for k in seg_index}
    seg_cursor: Dict[tuple, int] = {k: s.offset for k, s in seg_index.items()}

    def member(key: tuple, off: int, size: int, what: str) -> None:
        s = seg_index.get(key)
        if s is None:
            raise CorruptArchiveError(
                f"corrupt archive: {what} has no segment {key}")
        if size and not (s.offset <= off and off + size <= s.offset + s.size):
            raise CorruptArchiveError(
                f"corrupt archive: {what} extent [{off}, {off + size}) "
                f"falls outside its segment "
                f"[{s.offset}, {s.offset + s.size})")
        if size and off < seg_cursor[key]:
            raise CorruptArchiveError(
                f"corrupt archive: {what} extent [{off}, {off + size}) "
                "overlaps or precedes the preceding chunk's blob in its "
                "segment")
        if size:
            seg_cursor[key] = off + size
        sums[key] += size

    for c, m in enumerate(meta.chunk_metas):
        member(("anchors", -1, -1), m.anchors_offset, m.anchors_size,
               f"chunk {c} anchors")
        for li, lv in enumerate(m.levels):
            member(("escapes", li, -1), lv.esc_offset, lv.esc_size,
                   f"chunk {c} level {li} escapes")
            for k in range(lv.nbits):
                member(("planes", li, k), lv.plane_offsets[k],
                       lv.plane_sizes[k], f"chunk {c} level {li} plane {k}")
    for key, s in seg_index.items():
        if sums[key] != s.size:
            raise CorruptArchiveError(
                f"corrupt archive: segment {key} declares {s.size} bytes "
                f"but its chunk blobs sum to {sums[key]}")

    # -- derived plan tables: the ladder prefix <-> byte cost map
    meta.plane_segments = [s for s in meta.segments if s.kind == "planes"]
    meta.base_end = (meta.plane_segments[0].offset if meta.plane_segments
                     else total)
    esc_total = sum(s.size for s in meta.segments if s.kind == "escapes")
    cum = [esc_total]  # plan floor: escapes always load (anchors excluded,
    for s in meta.plane_segments:  # matching v1/v2 loaded_bytes semantics)
        cum.append(cum[-1] + s.size)
    meta.cum_bytes = cum
    return meta


class _Stage:
    """The staged contiguous prefix of a v3 data section, shared by
    reference across reader forks (archive bytes are immutable, so
    branches can pool their transport buffer while keeping independent
    fetch accounting)."""

    def __init__(self, start: int):
        self.start = start
        self.buf = bytearray()

    @property
    def end(self) -> int:
        return self.start + len(self.buf)


class _StagedSource(ByteSource):
    """Chunk-blob reads of a :class:`V3ArchiveReader` resolve here: ranges
    inside the staged prefix are served from memory (bytes copies — small
    blobs — so the growable stage is never pinned by exported views);
    anything not yet staged falls through to the underlying source.  The
    fall-through keeps direct ``chunk_reader`` use correct without
    ``ensure_prefix``; planned retrievals always stage first, so their
    source sees exactly one contiguous range per ladder step."""

    def __init__(self, owner: "V3ArchiveReader"):
        self._owner = owner

    def read(self, offset: int, size: int):
        st = self._owner._stage
        if offset >= st.start and offset + size <= st.end:
            lo = offset - st.start
            return bytes(st.buf[lo: lo + size])
        return self._owner.src.read(offset, size)

    @property
    def size(self) -> int:
        return self._owner.src.size


class V3ArchiveReader:
    """Plane-major reader: per-chunk ``ArchiveReader``s over one staged
    contiguous prefix of the data section.

    The retrieval contract of the v3 layout: :meth:`ensure_prefix` grows
    the staged region to cover the first ``t`` ladder segments with ONE
    contiguous source read — successive calls with non-decreasing ``t``
    issue monotonically increasing, gap-free ranges (the property
    ``tests/test_v3_format.py`` pins through a counting source).  Chunk
    decodes then read their blobs from the stage with the usual per-tag
    ``bytes_read`` accounting, so retrieval-volume semantics match v1/v2
    exactly.
    """

    def __init__(self, buf, meta: Optional[V3Meta] = None):
        self.src = as_source(buf)
        self.meta = parse_v3_meta(self.src) if meta is None else meta
        self._stage = _Stage(self.meta.header_end)
        self._readers: Dict[int, ArchiveReader] = {}
        #: see :attr:`ArchiveReader.cache_scope`; chunk sub-readers derive
        #: ``(cache_scope, chunk_index)`` — with the level/prefix the state
        #: layer appends, cache keys align 1:1 with v3 segment-prefix ids
        self.cache_scope = None

    def ensure_prefix(self, t: int) -> None:
        """Stage the base region plus the first ``t`` plane segments.

        Issues at most one source read: the contiguous gap between the
        current staged end and the prefix's end.  Shrinking ``t`` is a
        no-op (the stage only grows, like loaded planes)."""
        m = self.meta
        t = max(0, min(int(t), len(m.plane_segments)))
        target = m.base_end if t == 0 else (
            m.plane_segments[t - 1].offset + m.plane_segments[t - 1].size)
        st = self._stage
        if target > st.end:
            # validated before appending: a short staged read would shift
            # every downstream blob offset and decode garbage silently
            st.buf += _read_exact(self.src, st.end, target - st.end,
                                  f"v3 ladder prefix t={t}")

    def chunk_reader(self, i: int) -> ArchiveReader:
        if i not in self._readers:
            self._readers[i] = ArchiveReader(
                _StagedSource(self), meta=self.meta.chunk_metas[i])
        sub = self._readers[i]
        if self.cache_scope is not None and sub.cache_scope is None:
            sub.cache_scope = (self.cache_scope, i)
        return sub

    def fork(self) -> "V3ArchiveReader":
        """Independent accounting branch (see :meth:`ArchiveReader.fork`).
        The staged prefix is shared by reference — it is a transport cache
        of immutable bytes, not accounting state — so sibling branches
        never re-fetch ranges either already staged."""
        dup = V3ArchiveReader(self.src, meta=self.meta)
        dup._stage = self._stage
        dup.cache_scope = self.cache_scope
        for i, r in self._readers.items():
            sub = ArchiveReader(_StagedSource(dup), meta=r.meta)
            sub.bytes_read = r.bytes_read
            sub._fetched = set(r._fetched)
            sub.cache_scope = r.cache_scope
            dup._readers[i] = sub
        return dup

    @property
    def bytes_read(self) -> int:
        return sum(r.bytes_read for r in self._readers.values())


def open_reader(buf, meta=None):
    """Version dispatch: v1 -> ArchiveReader, v2 -> ChunkedArchiveReader,
    v3 -> V3ArchiveReader.

    Anything that is not a well-formed archive of a known version —
    unknown magic, truncated framing or data section, undecodable header
    — raises :class:`CorruptArchiveError` here rather than failing later
    inside a retrieval.  ``meta`` skips the re-parse when the caller holds
    the already-validated header of this exact buffer (a new reader is a
    fresh ``bytes_read`` accounting scope, not a fresh parse).  Accepts
    bytes-like buffers or any :class:`~.bytesource.ByteSource`.
    """
    if meta is not None:
        if isinstance(meta, V3Meta):
            cls = V3ArchiveReader
        elif isinstance(meta, ChunkedMeta):
            cls = ChunkedArchiveReader
        else:
            cls = ArchiveReader
        return cls(buf, meta=meta)
    src = as_source(buf)
    magic = _magic(src)
    if magic == MAGIC3:
        return V3ArchiveReader(src)
    if magic == MAGIC2:
        return ChunkedArchiveReader(src)
    if magic != MAGIC:
        raise CorruptArchiveError(
            "not an IPComp archive: expected magic "
            f"{MAGIC!r}, {MAGIC2!r} or {MAGIC3!r}, got {magic!r}")
    return ArchiveReader(src)
