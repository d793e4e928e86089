"""IPComp legacy surface: compress / retrieve / refine (paper Algorithms 1 & 2).

Compatibility shim twice over: the implementation lives in the
``core/pipeline`` package (``spec`` / ``encode`` / ``decode`` / ``state``
/ ``backends`` — see its docstring for the module map), and the
*supported* public surface is the object API in :mod:`repro.api`
(``Codec`` / ``Archive`` / ``Fidelity`` / ``ExecPolicy`` /
``ProgressiveReader``).  This module re-exports the historical
``core.ipcomp`` names so existing imports keep working unchanged; the
free functions emit one ``IPCompDeprecationWarning`` per call.

Compression pipeline (Fig. 2):
  x --interpolation predictor--> residuals y_l --quantize--> q_l
    --negabinary--> nb_l --bitplanes + XOR predictive coding--> blobs
    --container--> archive bytes

Retrieval: the DP loader (§5) plans the minimum bitplane set for the
requested error bound / bitrate; a single reconstruction pass produces the
output.  ``refine`` implements Algorithm 2: it loads only the *additional*
bitplanes and pushes a linear delta cascade on top of the previous
reconstruction.

Both directions run on interchangeable backends (``backend="numpy"`` |
``"jax"`` | ``"auto"``): the jax path routes the phase sweeps and bitplane
coding through the Pallas kernel pairs (``interp_quant``/``interp_recon``,
``bitplane_pack``/``bitplane_unpack``), emitting byte-identical archives
and bit-identical reconstructions.  ``chunk_elems=N`` compresses to the
chunked v2 container; retrieval accepts both versions transparently.
"""
from __future__ import annotations

from .pipeline.backends import CodecBackend, get as get_backend
from .pipeline.decode import (_retrieve_chunked, decompress, open_archive,
                              read_archive, refine, retrieve, split_budget)
from .pipeline.encode import chunk_bounds, compress, encode_array
from .pipeline.spec import ExecPolicy, Fidelity, IPCompDeprecationWarning
from .pipeline.state import (ChunkedRetrievalState, RetrievalState,
                             _unpack_escapes, initial_state)


def _initial_state(reader) -> RetrievalState:
    """Historical one-arg helper: initial state via the numpy backend."""
    return initial_state(reader, get_backend("numpy"))


__all__ = [
    "compress", "chunk_bounds", "decompress", "retrieve", "refine",
    "open_archive", "split_budget", "RetrievalState",
    "ChunkedRetrievalState", "CodecBackend",
    "encode_array", "read_archive", "Fidelity", "ExecPolicy",
    "IPCompDeprecationWarning",
]
