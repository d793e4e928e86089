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
import zlib
from typing import List, Optional, Tuple

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


def compress_plane(bits: np.ndarray) -> bytes:
    """Pack a 0/1 uint8 array and zlib it. All-zero planes compress to b''."""
    if bits.size == 0 or not bits.any():
        return b""
    return zlib.compress(np.packbits(bits).tobytes(), zlib_level())


def decompress_plane(blob: bytes, n: int) -> np.ndarray:
    if not blob:
        return np.zeros(n, np.uint8)
    raw = np.frombuffer(inflate(blob), np.uint8)
    return np.unpackbits(raw, count=n)


def encode_level(nb: np.ndarray) -> Tuple[List[bytes], int]:
    """negabinary ints -> (blobs MSB-first, nbits). blobs[i] is plane nbits-1-i."""
    with trace.span("pack.kernel", stage="kernel_io"):
        nbits = int(nb.max()).bit_length() if nb.size else 0
        if nbits == 0:
            return [], 0
        enc = xor_encode(split_planes(nb, nbits))
    with trace.span("pack.zlib", stage="zlib"):
        blobs = [compress_plane(enc[k]) for k in range(nbits - 1, -1, -1)]
        _count_zlib(blobs, (nb.size + 7) // 8)
    return blobs, nbits


def blobs_from_packed(packed: np.ndarray, n: int) -> Tuple[List[bytes], int]:
    """Pre-packed XOR-coded plane words -> (blobs MSB-first, nbits).

    ``packed`` is the (32, R, W) uint32 output of the ``bitplane_pack``
    Pallas kernel *for 1-D input*: plane k = bit k of the XOR-encoded
    negabinary word, each uint32 covering 32 consecutive elements with
    element 0 at the MSB — the same bit order ``np.packbits`` emits.  Only
    the first ``n`` elements are real; the 1-D wrapper appends its pad at
    the END of the flat stream and pad words are all-zero (q=0 -> nb=0 ->
    enc=0), so truncating the big-endian byte stream to ceil(n/8) bytes
    reproduces ``compress_plane``'s output byte-for-byte.  (The wrapper's
    2-D path pads columns mid-stream instead — callers must flatten first,
    as ``jax_backend.encode_level`` does.)  Both backends therefore write
    one archive format, and a mixed read path cannot exist.
    """
    with trace.span("pack.zlib", stage="zlib"):
        occupied = [bool(packed[k].any()) for k in range(packed.shape[0])]
        nbits = max((k + 1 for k, nz in enumerate(occupied) if nz),
                    default=0)
        if nbits == 0:
            return [], 0
        nbytes = (n + 7) // 8
        blobs = []
        for k in range(nbits - 1, -1, -1):
            if not occupied[k]:
                # all-zero plane: same convention as compress_plane
                blobs.append(b"")
                continue
            raw = packed[k].astype(">u4").tobytes()[:nbytes]
            blobs.append(zlib.compress(raw, zlib_level()))
        _count_zlib(blobs, nbytes)
        return blobs, nbits


def _count_zlib(blobs: List[bytes], nbytes: int) -> None:
    """Count one level's zlib bytes on the open span (an all-zero plane
    is stored as ``b''`` without zlib)."""
    trace.count("zlib_in_bytes", nbytes * sum(1 for b in blobs if b))
    trace.count("zlib_out_bytes", sum(len(b) for b in blobs))


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
