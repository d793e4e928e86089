"""IPComp first-class object API: Codec / Archive / Fidelity / ExecPolicy.

The paper's value proposition is the *progressive session* (§4,
Algorithm 2): open an archive coarse, then incrementally refine toward a
stated fidelity, paying only for the bitplanes each step adds.  This
module is that interaction model as objects::

    from repro import Codec, Archive, Fidelity, ExecPolicy

    codec = Codec(eb=1e-6, chunk_elems=1 << 20)      # bytes-affecting spec
    archive = codec.compress(x)                      # -> Archive
    archive.save("field.ipc")

    session = Archive.load("field.ipc").open(ExecPolicy(backend="jax"))
    coarse = session.read(Fidelity.error_bound(1e-2))
    finer = session.refine(Fidelity.error_bound(1e-5))   # only new planes
    session.bytes_read, session.achieved_bound           # live accounting

The four types split the old kwarg-threaded surface along its real
seams:

* :class:`Codec` — everything that *changes archive bytes* (error bound,
  interpolator, relative scaling, chunking).
* :class:`ExecPolicy` — everything that *never* changes bytes or bits
  (backend substrate, chunk batching, mesh sharding), validated once at
  construction.  ``tests/test_policy_matrix.py`` pins the invariance.
* :class:`Fidelity` — the retrieval target as a sum type
  (``error_bound`` / ``max_bytes`` / ``bitrate`` / ``full``); exactly one
  alternative per instance, so over-specification is unrepresentable.
* :class:`Archive` + :class:`ProgressiveReader` — the bytes and the
  session.  The session owns the progressive state the legacy API made
  callers hand-carry between ``retrieve``/``refine`` calls.

The legacy free functions (``compress`` / ``retrieve`` / ``refine`` /
``decompress``) remain as one-screen shims over these objects — same
bytes, same bits, one :class:`IPCompDeprecationWarning` per call — so
every existing archive and call site keeps working.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Iterable, Iterator, Optional, Tuple, Union

import numpy as np

from . import trace
from .core import container, interpolation, loader
from .core.bytesource import ByteSource, FileSource, as_source
from .core.container import CorruptArchiveError
from .core.pipeline import decode, encode
from .core.pipeline.spec import (DEFAULT_POLICY, ExecContext, ExecPolicy,
                                 Fidelity, IPCompDeprecationWarning)
from .core.pipeline.state import ChunkedRetrievalState, RetrievalState

# legacy free functions, re-exported so ``repro`` is a one-stop import for
# both generations of the API (each emits one IPCompDeprecationWarning)
from .core.pipeline.decode import (decompress, open_archive, refine,
                                   retrieve)
from .core.pipeline.encode import compress

__all__ = [
    "Codec", "Archive", "ProgressiveReader", "Fidelity", "ExecPolicy",
    "ExecContext", "DEFAULT_POLICY", "CorruptArchiveError",
    "IPCompDeprecationWarning",
    "compress", "decompress", "retrieve", "refine", "open_archive",
    "RetrievalState", "ChunkedRetrievalState",
]


@dataclass(frozen=True)
class Codec:
    """The bytes-affecting compression spec (paper Fig. 2 pipeline).

    Two arrays compressed with equal :class:`Codec`s yield comparable
    archives no matter which :class:`ExecPolicy` runs the work; change
    any field here and the bytes change.  Frozen + hashable, so a Codec
    can key caches and be shared freely.

    ``eb``
        Point-wise error bound (> 0).  With ``relative=True`` it is a
        fraction of each array's value range instead of an absolute bound.
    ``interp``
        Interpolation predictor: ``"cubic"`` (default) or ``"linear"``.
    ``chunk_elems``
        None = single v1 archive; N = chunked container of independent
        ~N-element slabs (the unit of batched and sharded execution).
    ``version``
        Container framing: 1 (plain), 2 (chunk-major), 3 (plane-major —
        the streaming/range-read layout, ``docs/format.md`` §3).  None
        picks the historical default from ``chunk_elems`` (1 unchunked /
        2 chunked).  The framing regroups identical per-chunk streams, so
        v2 and v3 archives of one array reconstruct bit-identically.
    """
    eb: float
    interp: str = interpolation.CUBIC
    relative: bool = False
    chunk_elems: Optional[int] = None
    version: Optional[int] = None

    def __post_init__(self):
        if not self.eb > 0:
            raise ValueError(f"error bound must be positive, got {self.eb}")
        if self.interp not in (interpolation.LINEAR, interpolation.CUBIC):
            raise ValueError(
                f"unknown interpolator {self.interp!r}; use "
                f"{interpolation.LINEAR!r} or {interpolation.CUBIC!r}")
        if self.chunk_elems is not None and self.chunk_elems <= 0:
            raise ValueError("chunk_elems must be positive, got "
                             f"{self.chunk_elems}")
        if self.version is not None:
            if self.version not in (1, 2, 3):
                raise ValueError(f"unknown container version "
                                 f"{self.version!r}; expected 1, 2 or 3")
            if self.version == 1 and self.chunk_elems is not None:
                raise ValueError("version=1 cannot hold chunks; drop "
                                 "chunk_elems or use version 2 or 3")
            if self.version == 2 and self.chunk_elems is None:
                raise ValueError("version=2 is the chunked container; "
                                 "pass chunk_elems (or use version=1)")

    def compress(self, x: np.ndarray,
                 policy: Optional[ExecPolicy] = None) -> "Archive":
        """Compress ``x`` under this spec -> :class:`Archive`.

        ``policy`` selects the execution substrate only; archives are
        byte-identical across policies.
        """
        # one request: the archive's validation ends its ``encode`` span
        with trace.request("encode"):
            buf = encode.encode_array(
                x, self.eb, interp=self.interp, relative=self.relative,
                chunk_elems=self.chunk_elems, policy=policy,
                version=self.version)
            with trace.span("encode.container", stage="container"):
                return Archive(buf)


class Archive:
    """An IPComp archive: an immutable byte source plus the parsed header.

    Wraps any container version (v1 plain / v2 chunk-major / v3
    plane-major) behind one type; construction validates the buffer
    (:class:`CorruptArchiveError` on unknown magic, truncation, or
    undecodable headers), so an Archive in hand is known-well-formed.
    Round-trips losslessly through :meth:`tobytes` / :meth:`frombytes`
    and :meth:`save` / :meth:`load`.

    The backing storage is a pluggable
    :class:`~repro.core.bytesource.ByteSource`: in-memory bytes (the
    default), a file opened by :meth:`load` (mmap-backed — header and
    planned blob ranges are the only bytes ever touched, never a full
    read), or any caller-provided source via :meth:`from_source` (e.g. a
    ``CountingSource`` for range accounting).

    Reading is a *session*: :meth:`open` returns a
    :class:`ProgressiveReader` owning its own retrieval state and byte
    accounting, so several sessions can progress through one Archive
    independently.
    """

    def __init__(self, data: Union[bytes, bytearray, memoryview,
                                   ByteSource]):
        self._src = as_source(data)
        self._meta = container.open_reader(self._src).meta  # validates

    # ---- construction / serialization

    @classmethod
    def frombytes(cls, data: Union[bytes, bytearray, memoryview]
                  ) -> "Archive":
        """Wrap serialized archive bytes (the :meth:`tobytes` inverse)."""
        return cls(data)

    @classmethod
    def from_source(cls, src: ByteSource) -> "Archive":
        """Open an archive over an explicit byte source — a
        ``FileSource``, a ``CountingSource`` wrapper, or any custom
        range-read transport satisfying the ``ByteSource`` contract."""
        return cls(src)

    def tobytes(self) -> bytes:
        """The raw archive bytes, materialized (``IPC1``/``IPC2``/``IPC3``
        container).  On a file-backed archive this reads the whole file —
        use :meth:`save` to copy without keeping it in memory."""
        return bytes(self._src.read(0, self._src.size))

    #: streaming block size for save/compare — large enough to amortize
    #: syscalls, small enough to never matter for memory
    _BLOCK = 1 << 20

    @classmethod
    def load(cls, path: Union[str, "os.PathLike"]) -> "Archive":
        """Open an archive file written by :meth:`save` (or any producer
        of the container format).  Accepts ``str`` or ``pathlib.Path``.
        The file is opened through a mmap-backed ``FileSource``, NOT read
        into memory: a session over a loaded archive touches only the
        header and the byte ranges its fidelity plans actually need."""
        return cls(FileSource(path))

    def save(self, path: Union[str, "os.PathLike"]) -> None:
        """Write the archive bytes to ``path`` (``str`` or
        ``pathlib.Path``), streaming in blocks — a file-backed archive is
        copied without ever materializing in memory."""
        with open(os.fspath(path), "wb") as f:
            for off in range(0, self._src.size, self._BLOCK):
                f.write(self._src.read(
                    off, min(self._BLOCK, self._src.size - off)))

    # ---- parsed-header views

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(self._meta.shape)

    @property
    def dtype(self) -> np.dtype:
        return np.dtype(self._meta.dtype)

    @property
    def eb(self) -> float:
        """The point-wise error bound the archive was written with
        (absolute — ``Codec.relative`` is resolved at compression time)."""
        return float(self._meta.eb)

    @property
    def interp(self) -> str:
        return self._meta.interp

    @property
    def nbytes(self) -> int:
        """Total serialized size (the compressed-ratio denominator)."""
        return self._src.size

    @property
    def version(self) -> int:
        """Container version of the underlying bytes (1, 2 or 3)."""
        if isinstance(self._meta, container.V3Meta):
            return 3
        if isinstance(self._meta, container.ChunkedMeta):
            return 2
        return 1

    @property
    def n_chunks(self) -> int:
        """Independent slabs: 1 for a v1 archive, the chunk-grid size for
        v2/v3."""
        return len(getattr(self._meta, "chunks", ())) or 1

    @property
    def chunked(self) -> bool:
        return hasattr(self._meta, "chunks")

    def __len__(self) -> int:
        return self._src.size

    def __eq__(self, other) -> bool:
        """Content equality, compared block-wise — file-backed archives
        compare without materializing (identity and size short-circuit
        first).  Equality is what makes an Archive a sound plane-cache
        scope: equal keys imply equal bytes."""
        if not isinstance(other, Archive):
            return NotImplemented
        if self is other or self._src is other._src:
            return True
        if self._src.size != other._src.size:
            return False
        for off in range(0, self._src.size, self._BLOCK):
            n = min(self._BLOCK, self._src.size - off)
            if bytes(self._src.read(off, n)) != \
                    bytes(other._src.read(off, n)):
                return False
        return True

    def __hash__(self) -> int:
        # size + header prefix: cheap, stable, and consistent with __eq__
        # (equal bytes always collide onto the same hash)
        return hash((self._src.size, bytes(self._src.read(
            0, min(4096, self._src.size)))))

    def __repr__(self) -> str:
        kind = (f"v{self.version}[{self.n_chunks} chunks]" if self.chunked
                else "v1")
        return (f"Archive({kind}, shape={self.shape}, dtype={self.dtype}, "
                f"eb={self.eb:g}, {self.nbytes} bytes)")

    # ---- reading

    def new_reader(self, cache_scope=None):
        """A fresh low-level container reader over this archive's byte
        source (``ArchiveReader`` / ``ChunkedArchiveReader`` /
        ``V3ArchiveReader``) with independent fetched-range accounting.

        ``cache_scope`` opts the reader into shared plane-cache keying
        (see ``pipeline.state``); equal scopes MUST mean identical
        archive bytes.  The serving tier uses its registry id; sessions
        opened with a ``plane_cache`` use the Archive itself (Archives
        compare by content, so equal keys imply equal bytes).
        """
        reader = container.open_reader(self._src, meta=self._meta)
        reader.cache_scope = cache_scope
        return reader

    def open(self, policy: Optional[ExecPolicy] = None,
             propagation: str = loader.SAFE,
             plane_cache=None) -> "ProgressiveReader":
        """Start a progressive session -> :class:`ProgressiveReader`.

        Each call returns an independent session with fresh byte
        accounting; ``policy`` is the session's initial execution policy
        (swap it mid-session via :attr:`ProgressiveReader.policy` — the
        state is policy-agnostic by design).  ``propagation`` picks the
        error-propagation model of the DP planner (``loader.SAFE``
        default / ``loader.PAPER``).  ``plane_cache`` attaches a shared
        ``repro.serving.PlaneCache``: sessions over equal archives then
        reuse each other's decoded plane prefixes (bits never change;
        ``bytes_read`` may shrink on cache hits).
        """
        return ProgressiveReader(self, policy=policy,
                                 propagation=propagation,
                                 plane_cache=plane_cache)


class ProgressiveReader:
    """A progressive retrieval session over one :class:`Archive`.

    Owns what the legacy API made callers hand-carry: the container
    reader (with its fetched-range accounting) and the
    :class:`RetrievalState` of Algorithm 2.  Every :meth:`read` /
    :meth:`refine` fetches only the bitplanes the new
    :class:`Fidelity` adds on top of what the session already holds and
    pushes a linear delta cascade — never a from-scratch decode.

    The session's :attr:`policy` may be swapped between calls (backend,
    batching, mesh): reconstruction bits never depend on it, so a
    retrieval started on one substrate can be refined on another.
    """

    def __init__(self, archive: Archive,
                 policy: Optional[ExecPolicy] = None,
                 propagation: str = loader.SAFE,
                 plane_cache=None):
        self._archive = archive
        # with a shared plane cache the content-equal Archive is the cache
        # scope: equal scope keys then imply equal archive bytes, so two
        # sessions over the same data reuse each other's decoded prefixes
        self._reader = archive.new_reader(
            cache_scope=archive if plane_cache is not None else None)
        self._cache = plane_cache
        self._propagation = propagation
        self._state: Optional[RetrievalState] = None
        self._data: Optional[np.ndarray] = None
        self.policy = policy if policy is not None else DEFAULT_POLICY

    # ---- policy (swappable mid-session)

    @property
    def policy(self) -> ExecPolicy:
        """The session's execution policy.  Assignable mid-session; never
        changes reconstruction bits."""
        return self._policy

    @policy.setter
    def policy(self, policy: ExecPolicy) -> None:
        if not isinstance(policy, ExecPolicy):
            raise TypeError("policy must be an ExecPolicy, got "
                            f"{type(policy).__name__}")
        self._policy = policy

    # ---- progressive reads

    def read(self, fidelity: Optional[Fidelity] = None) -> np.ndarray:
        """Advance the session to (at least) ``fidelity`` and return the
        reconstruction.

        Default: :meth:`Fidelity.full`.  Refinement never drops planes,
        so a looser target than the session already satisfies is a no-op
        returning the current data.
        """
        if fidelity is not None and not isinstance(fidelity, Fidelity):
            raise TypeError(
                f"fidelity must be a Fidelity, got {fidelity!r} — e.g. "
                "Fidelity.error_bound(E), .max_bytes(n), .bitrate(b), or "
                ".full()")
        out, self._state = decode.read_archive(
            self._reader, fidelity, self._policy,
            propagation=self._propagation, state=self._state,
            cache=self._cache)
        self._data = out
        return out

    def refine(self, fidelity: Optional[Fidelity] = None) -> np.ndarray:
        """Alias of :meth:`read`, named for the Algorithm 2 reading: on a
        session with loaded planes, only the *additional* planes the
        target needs are fetched and cascaded."""
        return self.read(fidelity)

    def ladder(self, fidelities: Iterable[Fidelity]
               ) -> Iterator[Tuple[Fidelity, np.ndarray]]:
        """Iterate a fidelity ladder: yield ``(fidelity, data)`` after
        refining to each rung in turn.

        Lazy — each rung's planes are fetched when the iterator reaches
        it, so breaking out early reads no more than was consumed::

            for fid, out in session.ladder(map(Fidelity.error_bound,
                                               (1e-2, 1e-4, 1e-6))):
                if analysis_converged(out):
                    break
        """
        for fid in fidelities:
            yield fid, self.read(fid)

    # ---- session introspection

    @property
    def archive(self) -> Archive:
        return self._archive

    @property
    def data(self) -> Optional[np.ndarray]:
        """The latest reconstruction (None before the first read)."""
        return self._data

    @property
    def bytes_read(self) -> int:
        """Cumulative data bytes this session fetched (the retrieval-
        volume metric of paper Figs. 6/7; header bytes excluded)."""
        return self._reader.bytes_read

    @property
    def achieved_bound(self) -> float:
        """Guaranteed L_inf bound of the current reconstruction (inf
        before the first read)."""
        return self._state.err_bound if self._state is not None \
            else float("inf")

    def __repr__(self) -> str:
        bound = self.achieved_bound
        return (f"ProgressiveReader({self._archive!r}, "
                f"bytes_read={self.bytes_read}, "
                f"achieved_bound={bound:g})")
