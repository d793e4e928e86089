"""PMGARD-like: multilevel hierarchical-basis progressive compressor.

Simplified MGARD stand-in (see DESIGN.md §7): linear-interpolation
hierarchical-basis *transform* computed from the ORIGINAL data top-down
(a transform model — coefficient errors amplify through levels, Eq. 3),
with per-level negabinary bitplane coding for progressive retrieval.
The coefficient bound is eb / sum_l(amp_l), which is what costs MGARD-style
codecs compression ratio relative to prediction models — the comparison the
paper draws in §4.2 and §6.

Retrieval: greedy MSB-first plane loading, steepest error-reduction per byte
(real PMGARD orders by L2 impact; same spirit).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from .. import bitplane, interpolation, negabinary
from . import common

_P = 1.0  # linear hierarchical basis


def _amp_factor(level: int, ndim: int) -> float:
    geo = sum(_P ** k for k in range(ndim))
    return geo * _P ** (ndim * (level - 1))


class PMGARD:
    name = "pmgard"

    def compress(self, x: np.ndarray, eb: float) -> bytes:
        x = np.asarray(x)
        x64 = x.astype(np.float64)
        shape = x.shape
        L = interpolation.num_levels(shape)
        ndim = x.ndim
        amp_total = sum(_amp_factor(l, ndim) for l in range(1, L + 1))
        eb_c = eb / amp_total
        # transform mode: predict every level from the ORIGINAL data
        coeffs: List[List[np.ndarray]] = [[] for _ in range(L)]
        for ph in interpolation.iter_phases(shape, L):
            xv = x64[ph.view]
            pred = interpolation.predict_block(xv, ph.dim, ph.targets,
                                               ph.stride, ph.n_dim, interpolation.LINEAR)
            tvals = np.take(xv, ph.targets, axis=ph.dim)
            coeffs[L - ph.level].append((tvals - pred).ravel())
        anchors = x64[interpolation.anchor_slices(shape, L)]
        sections = [anchors.tobytes()]
        lvl_meta = []
        for li in range(L):
            y = np.concatenate(coeffs[li]) if coeffs[li] else np.zeros(0)
            q = np.rint(y / (2.0 * eb_c)).astype(np.int64)
            q = np.clip(q, -(1 << 30), 1 << 30)  # baseline: no escape channel
            nb = negabinary.to_negabinary(q)
            blobs, nbits = bitplane.encode_level(nb)
            blobs = bitplane.wait(blobs)
            delta = negabinary.truncation_loss_table(nb, nbits, eb_c)
            lvl_meta.append(dict(n=int(q.size), nbits=nbits,
                                 sizes=[len(b) for b in blobs],
                                 delta=delta.tolist(), level=L - li))
            sections.extend(blobs)
        meta = dict(shape=list(shape), dtype=str(x.dtype), eb=eb, eb_c=eb_c,
                    L=L, anc=list(anchors.shape), levels=lvl_meta)
        return common.pack_sections(meta, sections)

    def decompress(self, buf: bytes) -> np.ndarray:
        out, _, _ = self.retrieve(buf)
        return out

    def retrieve(self, buf: bytes, error_bound: Optional[float] = None,
                 max_bytes: Optional[int] = None
                 ) -> Tuple[np.ndarray, int, int]:
        meta, secs = common.unpack_sections(buf)
        L, ndim = meta["L"], len(meta["shape"])
        eb_c = meta["eb_c"]
        anchors = np.frombuffer(secs[0], np.float64).reshape(meta["anc"])
        # per (level, plane): propagated error drop and byte cost
        entries = []  # (level_idx, plane_idx, err_drop, bytes)
        sec_idx = 1
        level_secs = []
        for li, lv in enumerate(meta["levels"]):
            level_secs.append(secs[sec_idx:sec_idx + lv["nbits"]])
            sec_idx += lv["nbits"]
            amp = _amp_factor(lv["level"], ndim)
            d = lv["delta"]
            for pi in range(lv["nbits"]):
                drop = (d[lv["nbits"] - pi] - d[lv["nbits"] - pi - 1]) * amp
                entries.append((li, pi, drop, lv["sizes"][pi]))
        base_err = meta["eb"] + sum(
            lv["delta"][lv["nbits"]] * _amp_factor(lv["level"], ndim)
            for lv in meta["levels"])
        # greedy: best error reduction per byte, but planes of a level must be
        # loaded MSB-first -> process in (level, plane) prefix order per level
        keep = [0] * L
        cur_err = base_err
        cur_bytes = 0
        while True:
            best = None
            for li, lv in enumerate(meta["levels"]):
                pi = keep[li]
                if pi >= lv["nbits"]:
                    continue
                amp = _amp_factor(lv["level"], ndim)
                drop = (lv["delta"][lv["nbits"] - pi]
                        - lv["delta"][lv["nbits"] - pi - 1]) * amp
                cost = max(1, lv["sizes"][pi])
                score = drop / cost
                if best is None or score > best[0]:
                    best = (score, li, drop, lv["sizes"][pi])
            if best is None:
                break
            _, li, drop, cost = best
            if error_bound is not None:
                if cur_err <= error_bound:
                    break
            elif max_bytes is not None:
                if cur_bytes + cost > max_bytes:
                    break
            else:
                pass  # full retrieval
            keep[li] += 1
            cur_err -= drop
            cur_bytes += cost
        if error_bound is not None and cur_err > error_bound:
            pass  # loaded everything; eb floor reached
        # reconstruct
        yhat = []
        bytes_read = len(secs[0])
        for li, lv in enumerate(meta["levels"]):
            blobs = [level_secs[li][i] for i in range(keep[li])]
            bytes_read += sum(lv["sizes"][: keep[li]])
            nb = bitplane.decode_level(
                list(blobs) + [None] * (lv["nbits"] - keep[li]),
                lv["nbits"], lv["n"])
            yhat.append(negabinary.from_negabinary(nb).astype(np.float64)
                        * 2.0 * eb_c)
        out = interpolation.reconstruct(meta["shape"], interpolation.LINEAR,
                                        anchors, yhat)
        return out.astype(np.dtype(meta["dtype"])), bytes_read, 1
