"""Plain reference of the codec's semantics: an IPC3 decoder for float32.

Written from the normative format (``docs/format.md`` §1, §3 and §6) and
importing nothing of the program: it parses the plane-major container,
inflates every plane, undoes the XOR and negabinary coding, dequantizes,
and runs the interpolation sweep in the float32 arithmetic the format
states, with the escape overwrites.  A full read by it reproduces the
encoder's verified surface, so every element lies within the archive's
``eb`` of the field; the benchmark holds the program's archives to the
bound it asked for through this decoder, and its read answers to their
bounds against the field directly (:func:`max_error`).
"""
from __future__ import annotations

import json
import struct
import zlib

import numpy as np

F32 = np.float32
TINY = F32(2.0 ** -126)
#: negabinary mask: bits at the negative powers of -2
M = np.uint32(0xAAAAAAAA)


def flush(v):
    """Subnormal magnitudes -> +0 (the format's float32 flush)."""
    return np.where(np.abs(v) < TINY, F32(0), v)


def header(buf) -> dict:
    if bytes(buf[:4]) != b"IPC3":
        raise ValueError(f"not an IPC3 archive (magic {bytes(buf[:4])!r})")
    (n,) = struct.unpack("<I", bytes(buf[4:8]))
    return json.loads(bytes(buf[8:8 + n]))


def decode(buf) -> np.ndarray:
    """Full-precision read of a float32 IPC3 archive."""
    buf = memoryview(buf)
    h = header(buf)
    out = np.empty(h["shape"], F32)
    for c, hc in zip(h["chunks"], h["chunk_headers"]):
        out[c["start"]:c["stop"]] = decode_chunk(buf, hc)
    return out


def bins(buf, lv: dict) -> np.ndarray:
    """A level's quantization bins from its MSB-first encoded planes."""
    n, nbits = lv["n"], lv["nbits"]
    nb = np.zeros(n, np.uint32)
    above = [np.zeros(n, np.uint8), np.zeros(n, np.uint8)]  # b_{k+1}, b_{k+2}
    for i in range(nbits):
        k = nbits - 1 - i
        off, size = lv["plane_offsets"][i], lv["plane_sizes"][i]
        if size:
            raw = np.frombuffer(zlib.decompress(buf[off:off + size]),
                                np.uint8)
            enc = np.unpackbits(raw, count=n)
        else:
            enc = np.zeros(n, np.uint8)
        b = enc ^ above[0] ^ above[1]
        nb |= b.astype(np.uint32) << np.uint32(k)
        above = [b, above[0]]
    return ((nb ^ M) - M).view(np.int32)


def escapes(buf, lv: dict):
    """(level-global stream indices, exact values) of a level's escapes."""
    if not lv["esc_size"]:
        return np.zeros(0, np.int64), np.zeros(0, F32)
    off = lv["esc_offset"]
    raw = zlib.decompress(buf[off:off + lv["esc_size"]])
    (count,) = struct.unpack("<q", raw[:8])
    idx = np.frombuffer(raw, "<i8", count, 8)
    val = np.frombuffer(raw, "<f8", count, 8 + 8 * count)
    return idx, val.astype(F32)


def predict(view, axis, idx, s, n, cubic):
    """The format's prediction (§6.1) of the targets ``idx`` along
    ``axis`` from the known points of ``view``."""
    shp = [1] * view.ndim
    shp[axis] = idx.size
    r_ok = (idx + s <= n - 1).reshape(shp)
    l1 = flush(np.take(view, idx - s, axis=axis))
    r1 = flush(np.take(view, np.minimum(idx + s, n - 1), axis=axis))
    lin = flush(flush(l1 + r1) * F32(0.5))
    if not cubic:
        return np.where(r_ok, lin, l1)
    cub_ok = ((idx - 3 * s >= 0) & (idx + 3 * s <= n - 1)).reshape(shp) \
        & r_ok
    l3 = flush(np.take(view, np.maximum(idx - 3 * s, 0), axis=axis))
    r3 = flush(np.take(view, np.minimum(idx + 3 * s, n - 1), axis=axis))
    t = flush(-l3 + (F32(8) * l1 + l1))
    t = flush(t + (F32(8) * r1 + r1))
    t = flush(t - r3)
    cub = flush(t * F32(0.0625))
    return np.where(cub_ok, cub, np.where(r_ok, lin, l1))


def decode_chunk(buf, h: dict) -> np.ndarray:
    if h["dtype"] != "float32" or "vmax" not in h:
        raise ValueError("the reference reads float32 archives written "
                         "under the float32 contract only")
    shape, L = tuple(h["shape"]), h["L"]
    cubic = h["interp"] == "cubic"
    w = F32(2.0 * h["eb"])
    a_off = h["anchors_offset"]
    anchors = np.frombuffer(buf, "<f8", int(np.prod(h["anchors_shape"])),
                            a_off).reshape(h["anchors_shape"])
    res = [bins(buf, lv).astype(F32) * w for lv in h["levels"]]
    esc = [escapes(buf, lv) for lv in h["levels"]]
    x = np.zeros(shape, F32)
    x[tuple(slice(0, None, 1 << L) for _ in shape)] = anchors
    used = [0] * L
    for level in range(L, 0, -1):
        s, li = 1 << (level - 1), L - level
        for d in range(len(shape)):
            idx = np.arange(s, shape[d], 2 * s)
            if not idx.size:
                continue
            view = tuple(slice(0, None, s) if e < d else
                         slice(None) if e == d else slice(0, None, 2 * s)
                         for e in range(len(shape)))
            known = x[view]
            pred = predict(known, d, idx, s, shape[d], cubic)
            lo, cnt = used[li], pred.size
            block = flush(pred + res[li][lo:lo + cnt].reshape(pred.shape))
            eidx, evals = esc[li]
            sel = (eidx >= lo) & (eidx < lo + cnt)
            block.reshape(-1)[eidx[sel] - lo] = evals[sel]
            used[li] += cnt
            sl = [slice(None)] * len(shape)
            sl[d] = idx
            known[tuple(sl)] = block
    for li, lv in enumerate(h["levels"]):
        if used[li] != lv["n"]:
            raise ValueError(f"level {li}: the sweep used {used[li]} "
                             f"residuals, the header holds {lv['n']}")
    return x


def max_error(y: np.ndarray, x: np.ndarray) -> float:
    """Largest |y - x| (infinite for a wrong shape or a non-finite
    difference)."""
    if y.shape != x.shape:
        return float("inf")
    d = float(np.max(np.abs(y.astype(np.float64) - x.astype(np.float64))))
    return d if np.isfinite(d) else float("inf")
