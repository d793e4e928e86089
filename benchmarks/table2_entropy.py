"""Table 2: predictive bitplane coding reduces bit entropy (0/1/2/3-bit
prefix XOR); 2-bit prefix is the best — the design choice of §4.4.1."""
from __future__ import annotations

import numpy as np

from .common import csv_row, datasets, timed
from repro.core import interpolation, negabinary


def _bit_entropy(bits: np.ndarray) -> float:
    p = bits.mean()
    if p in (0.0, 1.0):
        return 0.0
    return float(-p * np.log2(p) - (1 - p) * np.log2(1 - p))


def _mean_plane_entropy(nb: np.ndarray, prefix: int) -> float:
    nbits = int(nb.max()).bit_length()
    if nbits == 0:
        return 0.0
    enc = nb.copy()
    if prefix >= 1:
        enc = enc ^ (nb >> np.uint32(1))
    if prefix >= 2:
        enc = enc ^ (nb >> np.uint32(2))
    if prefix >= 3:
        enc = enc ^ (nb >> np.uint32(3))
    es = []
    for k in range(nbits):
        es.append(_bit_entropy(((enc >> np.uint32(k)) & 1).astype(np.uint8)))
    return float(np.mean(es))


def run(scale=None):
    rows, checks = [], []
    for name, x in list(datasets(scale).items())[:3]:
        eb = 1e-6 * float(x.max() - x.min())

        _, qs, _, _ = interpolation.decorrelate(
            x.astype(np.float64), eb, interpolation.CUBIC)
        nb = negabinary.to_negabinary(np.concatenate(qs))
        ents = {p: _mean_plane_entropy(nb, p) for p in (0, 1, 2, 3)}
        rows.append(csv_row(
            f"table2/{name}", 0.0,
            ";".join(f"p{p}={e:.4f}" for p, e in ents.items())))
        checks.append(("prefix2_reduces_entropy", name, "",
                       ents[2] <= ents[0]))
    return rows, checks
