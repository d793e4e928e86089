"""The chip's peaks and the least bytes the codec's sweeps need.

Peaks are looked up by ``jax.Device.device_kind``; a kind that is not in
the table is an error, never a default.

The byte counts take the algorithm's work from the shape alone, whatever
implements it: one interpolation sweep over a chunk visits every
(level, dimension) phase once, and a phase with ``T`` targets and ``K``
known points needs at least ``4 (K + 2 T)`` bytes of float32 traffic —
encoding reads the targets and the known points and writes the bins;
reconstruction reads the known points and the residuals and writes the
targets.  Padding, layout copies and extra outputs are the
implementation's, and count against its share of the roofline.
"""
from __future__ import annotations

from typing import List, Sequence

#: published HBM bandwidth per device kind, GB/s.  Source: Google Cloud
#: documentation, "TPU v5e": 16 GB of HBM2 at 819 GB/s per chip.  JAX
#: reports a v5e chip as "TPU v5 lite".  (Copied from the program's
#: ``benchmarks/roofline_report.py``.)
PEAK_HBM_GBS = {
    "TPU v5 lite": 819.0,
    "TPU v5e": 819.0,
}

WORD = 4  # bytes of a float32 value or an int32 bin


def peak_gbs(device_kind: str) -> float:
    try:
        return PEAK_HBM_GBS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published HBM peak for device kind {device_kind!r}; "
            f"known: {sorted(PEAK_HBM_GBS)}") from None


def chunk_rows(shape: Sequence[int], chunk_elems: int) -> List[int]:
    """Rows of each slab of the chunk grid: equal slabs of
    ``max(1, chunk_elems // row_elems)`` rows along axis 0, then the rest."""
    row = 1
    for s in shape[1:]:
        row *= s
    rows = max(1, chunk_elems // row)
    return [min(rows, shape[0] - a) for a in range(0, shape[0], rows)]


def levels(shape: Sequence[int]) -> int:
    """Interpolation levels: the least L >= 1 with 2**L >= max(shape)."""
    L = 1
    while (1 << L) < max(shape):
        L += 1
    return L


def _on_grid(n: int, step: int) -> int:
    """Indices 0, step, 2 step, ... below n."""
    return (n + step - 1) // step


def sweep_bytes(shape: Sequence[int]) -> int:
    """Least bytes of one interpolation sweep over an array of ``shape``."""
    total = 0
    for level in range(levels(shape), 0, -1):
        s = 1 << (level - 1)
        for d, n in enumerate(shape):
            targets = _on_grid(n, s) - _on_grid(n, 2 * s)
            if not targets:
                continue
            cross = 1
            for e, m in enumerate(shape):
                if e != d:
                    cross *= _on_grid(m, s if e < d else 2 * s)
            total += WORD * cross * (_on_grid(n, 2 * s) + 2 * targets)
    return total


def field_sweep_bytes(config: dict) -> int:
    """Least bytes of one sweep over a configuration's whole field, chunk
    by chunk."""
    shape = config["field"]["shape"]
    rows = chunk_rows(shape, config["codec"]["chunk_elems"]
                      or 1 << 62)
    return sum(sweep_bytes([r] + list(shape[1:])) for r in rows)
