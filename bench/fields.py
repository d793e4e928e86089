"""Seeded synthetic scientific fields, as the benchmark makes them.

A copy of the program's Table 3 generator (``repro.configs.paper``), kept
here so that the benchmark's inputs cannot change with the program.  It is
bit-identical to the original for every kind and shape
(``bench/tests/test_bench_fields.py``), but separable: each mode's
product of sines is built from one 1-D vector per axis, broadcast in the
original's multiplication order, instead of from full meshgrids.
"""
from __future__ import annotations

import zlib

import numpy as np

#: (modes, spectral decay, noise amplitude) per spectral profile
KINDS = {
    "turbulence": (8, 1.6, 3e-3),
    "seismic": (5, 1.2, 1e-3),
    "weather": (4, 2.0, 1e-3),
    "combustion": (6, 1.8, 5e-4),
}


def generate(name: str, kind: str, shape, seed: int) -> np.ndarray:
    """The float32 field ``name`` of profile ``kind`` at ``shape``.

    ``name`` enters the seed (crc32), as in the original; ``seed`` is any
    non-negative integer.
    """
    shape = tuple(int(s) for s in shape)
    rng = np.random.default_rng([int(seed), zlib.crc32(name.encode())])
    axes = [np.linspace(0, 2 * np.pi, s) for s in shape]
    nd = len(shape)
    n_modes, decay, noise = KINDS[kind]
    x = np.zeros(shape)
    term = np.empty(shape)
    for m in range(1, n_modes + 1):
        amp = m ** (-decay)
        phase = rng.uniform(0, 2 * np.pi, nd)
        # ((1 * v0) * v1) * v2 ...: the original's order (1 * v0 is v0);
        # the last factor is multiplied straight into ``term``
        vs = [np.sin(m * g * rng.uniform(0.5, 1.5) + ph)
              for g, ph in zip(axes, phase)]
        part = vs[0]
        for v in vs[1:-1]:
            part = part[..., None] * v
        if nd > 1:
            np.multiply(part[..., None], vs[-1], out=term)
        else:
            term[...] = part
        term *= amp
        x += term
    noise_part = rng.standard_normal(shape)
    noise_part *= noise
    x += noise_part
    return x.astype(np.float32)

