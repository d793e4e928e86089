"""The paper's own experimental config: six SDRBench-like fields (Table 3).

Offline container: synthetic seeded generators with the paper's shapes
(scaled down by `scale` for CPU benchmarking; 1.0 = full shape).  The
fields are float32, like the SDRBench originals, and repeat exactly across
processes for a given (dataset, scale, seed).
"""
import zlib
from dataclasses import dataclass
from typing import Tuple

import numpy as np


@dataclass(frozen=True)
class Dataset:
    name: str
    shape: Tuple[int, ...]
    kind: str     # spectral profile


TABLE3 = [
    Dataset("Density", (256, 384, 384), "turbulence"),
    Dataset("Pressure", (256, 384, 384), "turbulence"),
    Dataset("VelocityX", (256, 384, 384), "turbulence"),
    Dataset("Wave", (1008, 1008, 352), "seismic"),
    Dataset("SpeedX", (100, 500, 500), "weather"),
    Dataset("CH4", (500, 500, 500), "combustion"),
]

ERROR_BOUNDS = [1e-6, 1e-9]     # relative (Fig. 5)


def generate(ds: Dataset, scale: float = 0.25, seed: int = 0) -> np.ndarray:
    """Seeded synthetic float32 field with a domain-flavoured spectrum."""
    shape = tuple(max(16, int(s * scale)) for s in ds.shape)
    rng = np.random.default_rng([seed, zlib.crc32(ds.name.encode())])
    grids = np.meshgrid(*[np.linspace(0, 2 * np.pi, s) for s in shape],
                        indexing="ij")
    x = np.zeros(shape)
    n_modes, decay, noise = dict(
        turbulence=(8, 1.6, 3e-3), seismic=(5, 1.2, 1e-3),
        weather=(4, 2.0, 1e-3), combustion=(6, 1.8, 5e-4))[ds.kind]
    for m in range(1, n_modes + 1):
        amp = m ** (-decay)
        phase = rng.uniform(0, 2 * np.pi, len(shape))
        term = np.ones(shape)
        for g, ph in zip(grids, phase):
            term = term * np.sin(m * g * rng.uniform(0.5, 1.5) + ph)
        x += amp * term
    x += noise * rng.standard_normal(shape)
    return x.astype(np.float32)
