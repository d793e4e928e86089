"""Bitplane split + predictive XOR coding (paper §4.4.1) + lossless backend.

Each level's negabinary integers are sliced into bitplanes (bit k of every
integer forms plane k).  Planes are stored MSB-first so progressively loading
a *prefix* of planes refines precision.  Cross-bitplane correlation is
recovered with 2-bit-prefix predictive coding:

    enc_k = b_{k+2} ^ b_{k+1} ^ b_k        (prefix = two more-significant bits)

which the paper's Table 2 shows minimizes entropy.  Encoded planes are
bit-packed and zlib-compressed independently, so any prefix of planes is
independently decodable (the "blocks" of Fig. 2).
"""
from __future__ import annotations

import os
import threading
import zlib
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Iterable, List, NamedTuple, Optional, Tuple, Union

import numpy as np

from .. import trace

#: default zlib compression level; override per process with
#: ``IPCOMP_ZLIB_LEVEL`` (0–9).  Both backends read the same knob, so the
#: byte-identical-archive invariant holds at every setting.
ZLEVEL = 6

ZLEVEL_ENV = "IPCOMP_ZLIB_LEVEL"


def zlib_level() -> int:
    """Resolve the encode-side zlib level (env knob, default :data:`ZLEVEL`).

    Read per call so tests and long-lived servers can flip the knob without
    reimporting; an out-of-range or non-integer value is an error, not a
    silent fallback.
    """
    v = os.environ.get(ZLEVEL_ENV)
    if v is None:
        return ZLEVEL
    lvl = int(v)
    if not 0 <= lvl <= 9:
        raise ValueError(f"{ZLEVEL_ENV} must be in 0..9, got {lvl}")
    return lvl


class Raw(bytes):
    """In-memory marker: a plane payload that is ALREADY the raw packed-bit
    stream, not a zlib blob.  The archive format never stores this — it
    exists so cache layers and tests can hand pre-inflated payloads to the
    decoders and :func:`inflate` can skip the decompressobj round-trip.
    """
    __slots__ = ()


def inflate(blob) -> bytes:
    """Shared blob -> raw packed-bit stream helper for every decode path.

    Falsy (``b''`` all-zero convention / None) -> ``b''``; :class:`Raw`
    payloads pass through without touching zlib; anything else is a stored
    zlib blob and is decompressed.
    """
    if not blob:
        return b""
    if isinstance(blob, Raw):
        return bytes(blob)
    return zlib.decompress(blob)


def split_planes(nb: np.ndarray, nbits: int) -> List[np.ndarray]:
    """uint32 negabinary -> list of uint8 bit arrays, index k = bit k."""
    return [((nb >> np.uint32(k)) & np.uint32(1)).astype(np.uint8)
            for k in range(nbits)]


def join_planes(planes: List[Optional[np.ndarray]], n: int) -> np.ndarray:
    """Inverse of split_planes; missing (None) planes contribute 0."""
    nb = np.zeros(n, np.uint32)
    for k, p in enumerate(planes):
        if p is not None:
            nb |= p.astype(np.uint32) << np.uint32(k)
    return nb


def xor_encode(planes: List[np.ndarray]) -> List[np.ndarray]:
    """enc_k = b_k ^ b_{k+1} ^ b_{k+2} (more-significant planes are prefix)."""
    nb = len(planes)
    out = []
    for k in range(nb):
        e = planes[k]
        if k + 1 < nb:
            e = e ^ planes[k + 1]
        if k + 2 < nb:
            e = e ^ planes[k + 2]
        out.append(e)
    return out


def xor_decode_plane(enc_k: np.ndarray, b_k1: Optional[np.ndarray],
                     b_k2: Optional[np.ndarray]) -> np.ndarray:
    """Decode plane k given already-loaded planes k+1, k+2 (None if absent)."""
    b = enc_k
    if b_k1 is not None:
        b = b ^ b_k1
    if b_k2 is not None:
        b = b ^ b_k2
    return b


def decompress_plane(blob: bytes, n: int) -> np.ndarray:
    if not blob:
        return np.zeros(n, np.uint8)
    raw = np.frombuffer(inflate(blob), np.uint8)
    return np.unpackbits(raw, count=n)


class Deflate(NamedTuple):
    """A blob being deflated on the shared pool (:func:`wait` gives its
    bytes): the job's future and the size of the stream it deflates."""
    future: Future
    n_in: int


#: a level's blob as the encoders return it: ``b''`` for an all-zero
#: plane or an empty escape set, else a :class:`Deflate` in flight
Blob = Union[bytes, Deflate]

_POOL: Optional[ThreadPoolExecutor] = None
_POOL_LOCK = threading.Lock()


def _pool() -> ThreadPoolExecutor:
    """The encode path's deflate workers, one per CPU this process may
    run on, made on first use and shared by every caller.  A job only
    deflates and never waits on another, so callers that are themselves
    pool threads (the checkpoint bundle's per-shard savers) cannot
    deadlock it."""
    global _POOL
    with _POOL_LOCK:
        if _POOL is None:
            _POOL = ThreadPoolExecutor(
                max_workers=len(os.sched_getaffinity(0)),
                thread_name_prefix="ipcomp-deflate")
        return _POOL


def _forget_pool() -> None:
    # a forked child has none of its parent's workers
    global _POOL, _POOL_LOCK
    _POOL, _POOL_LOCK = None, threading.Lock()


os.register_at_fork(after_in_child=_forget_pool)


def _deflate(level: int, make, *args) -> bytes:
    return zlib.compress(make(*args), level)


def submit(level: int, n_in: int, make, *args) -> Deflate:
    """Deflate ``make(*args)`` (``n_in`` bytes) at zlib ``level`` on the
    shared pool.  ``make`` runs on the worker too, so the stream is built
    off the calling thread; it must only read ``args``."""
    return Deflate(_pool().submit(_deflate, level, make, *args), n_in)


def wait(blobs: Iterable[Blob]) -> List[bytes]:
    """``blobs`` as bytes, in order, waiting for each deflate in flight.
    Counts the deflates' bytes in and out and the jobs (``zlib_jobs``)
    on the calling thread's open span."""
    out, n_in, jobs = [], 0, 0
    for b in blobs:
        if isinstance(b, Deflate):
            n_in += b.n_in
            jobs += 1
            b = b.future.result()
        out.append(b)
    trace.count("zlib_in_bytes", n_in)
    trace.count("zlib_out_bytes", sum(len(b) for b in out))
    trace.count("zlib_jobs", jobs)
    return out


def encode_level(nb: np.ndarray) -> Tuple[List[Blob], int]:
    """negabinary ints -> (blobs MSB-first, nbits). blobs[i] is plane
    nbits-1-i: bit-packed and deflated on the shared pool, ``b''`` when
    the plane is all zero (:func:`wait` gives the bytes)."""
    with trace.span("pack.kernel", stage="kernel_io"):
        nbits = int(nb.max()).bit_length() if nb.size else 0
        if nbits == 0:
            return [], 0
        enc = xor_encode(split_planes(nb, nbits))
    with trace.span("pack.zlib", stage="zlib"):
        level, nbytes = zlib_level(), (nb.size + 7) // 8
        return [submit(level, nbytes, np.packbits, enc[k]) if enc[k].any()
                else b"" for k in range(nbits - 1, -1, -1)], nbits


def _plane_bytes(words: np.ndarray, nbytes: int) -> np.ndarray:
    """One packed plane's first ``nbytes`` bytes, words big-endian."""
    return words.astype(">u4").reshape(-1).view(np.uint8)[:nbytes]


def blobs_from_packed(packed: np.ndarray, n: int) -> Tuple[List[Blob], int]:
    """Pre-packed XOR-coded plane words -> (blobs MSB-first, nbits).

    ``packed`` is the (32, R, W) uint32 output of the ``bitplane_pack``
    Pallas kernel *for 1-D input*: plane k = bit k of the XOR-encoded
    negabinary word, each uint32 covering 32 consecutive elements with
    element 0 at the MSB — the same bit order ``np.packbits`` emits.  Only
    the first ``n`` elements are real; the 1-D wrapper appends its pad at
    the END of the flat stream and pad words are all-zero (q=0 -> nb=0 ->
    enc=0), so truncating the big-endian byte stream to ceil(n/8) bytes
    reproduces :func:`encode_level`'s stream byte-for-byte.  (The
    wrapper's 2-D path pads columns mid-stream instead — callers must
    flatten first, as ``jax_backend.encode_level`` does.)  Both backends
    therefore write one archive format, and a mixed read path cannot
    exist.  Each occupied plane is deflated on the shared pool, as in
    :func:`encode_level`.
    """
    with trace.span("pack.zlib", stage="zlib"):
        occupied = [bool(packed[k].any()) for k in range(packed.shape[0])]
        nbits = max((k + 1 for k, nz in enumerate(occupied) if nz),
                    default=0)
        if nbits == 0:
            return [], 0
        level, nbytes = zlib_level(), (n + 7) // 8
        return [submit(level, nbytes, _plane_bytes, packed[k], nbytes)
                if occupied[k] else b""
                for k in range(nbits - 1, -1, -1)], nbits


def decode_level(blobs: List[Optional[bytes]], nbits: int, n: int) -> np.ndarray:
    """Prefix of MSB-first blobs (None = not loaded) -> truncated negabinary."""
    planes: List[Optional[np.ndarray]] = [None] * nbits
    for i, blob in enumerate(blobs):
        k = nbits - 1 - i
        if blob is None:
            break  # prefix property: once a plane is missing, rest are too
        enc_k = decompress_plane(blob, n)
        planes[k] = xor_decode_plane(
            enc_k,
            planes[k + 1] if k + 1 < nbits else None,
            planes[k + 2] if k + 2 < nbits else None,
        )
    return join_planes(planes, n)
