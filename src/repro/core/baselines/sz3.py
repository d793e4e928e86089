"""SZ3-like non-progressive interpolation compressor.

Same interpolation decorrelation + linear-scale quantization as IPComp, but
the quantized stream is entropy-coded monolithically (no bitplanes): a
single fidelity level per archive, decompress-all-or-nothing.  This is the
"leading non-progressive" reference of the paper.
"""
from __future__ import annotations

import numpy as np

from .. import arith, interpolation
from . import common


class SZ3:
    name = "sz3"

    def __init__(self, interp: str = interpolation.CUBIC):
        self.interp = interp

    def compress(self, x: np.ndarray, eb: float) -> bytes:
        x = np.asarray(x)
        L = interpolation.num_levels(x.shape)

        _, qs, escs, anchors = interpolation.decorrelate(
            x.astype(np.float64), eb, self.interp)
        q_all = np.concatenate(qs) if qs else np.zeros(0, np.int64)
        lvl_sizes = [int(q.size) for q in qs]
        esc_idx, esc_val, base = [], [], 0
        for li, recs in enumerate(escs):
            for idx, vals in recs:
                if idx.size:
                    esc_idx.append(idx + base)
                    esc_val.append(vals)
            base += lvl_sizes[li]
        ei = np.concatenate(esc_idx) if esc_idx else np.zeros(0, np.int64)
        ev = np.concatenate(esc_val) if esc_val else np.zeros(0, np.float64)
        sections = [common.byteplane_encode(q_all),
                    anchors.astype(np.float64).tobytes(),
                    ei.tobytes(), ev.tobytes()]
        meta = dict(shape=list(x.shape), dtype=str(x.dtype), eb=eb,
                    interp=self.interp, L=L, lvl=lvl_sizes,
                    anc=list(anchors.shape), nesc=int(ei.size))
        return common.pack_sections(meta, sections)

    def decompress(self, buf: bytes) -> np.ndarray:
        meta, secs = common.unpack_sections(buf)
        q_all, _ = common.byteplane_decode(secs[0])
        anchors = np.frombuffer(secs[1], np.float64).reshape(meta["anc"])
        ei = np.frombuffer(secs[2], np.int64)
        ev = np.frombuffer(secs[3], np.float64)
        yhat, overrides, off = [], [], 0
        for n in meta["lvl"]:
            y = arith.dequantize(q_all[off:off + n],
                                 arith.consts(meta["eb"], np.float64))
            sel = (ei >= off) & (ei < off + n)
            overrides.append((ei[sel] - off, ev[sel]))
            yhat.append(y)
            off += n
        out = interpolation.reconstruct(meta["shape"], meta["interp"], anchors,
                                        yhat, overrides=overrides)
        return out.astype(np.dtype(meta["dtype"]))
