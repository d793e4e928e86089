"""IPComp core: interpolation-based progressive error-bounded lossy compression.

The supported public surface is the object API in :mod:`repro.api`
(``Codec`` / ``Archive`` / ``Fidelity`` / ``ExecPolicy`` /
``ProgressiveReader``); what this package exports directly is the legacy
free-function generation, kept as compatibility shims:

    compress(x, eb, interp, backend="numpy"|"jax"|"auto" (jax on TPU),
             chunk_elems=None)         -> archive bytes (v1; v2 if chunked)
    decompress(buf, backend=...)       -> full-precision array
    retrieve(buf, error_bound=|max_bytes=|bitrate=, backend=...)
                                       -> (array, RetrievalState)
    retrieve(reader, ..., state=state) -> incremental refinement (Algorithm 2)
    refine(state, error_bound=..., backend=...) -> same, as a first-class call

Each shim delegates to the policy-native pipeline entries
(``pipeline.encode.encode_array`` / ``pipeline.decode.read_archive``)
with unchanged behavior, bytes, and bits, and emits one
``IPCompDeprecationWarning`` per call.

Both directions are backend-pluggable (see ``pipeline.backends``): the
"jax" backend runs the predict+quantize / predict+reconstruct sweeps and
the bitplane pack/unpack through the Pallas kernels (interpret mode on
CPU), emitting archives byte-identical — and reconstructions bit-identical
— to the numpy reference.  Chunked (v2) archives are scheduled in
equal-shape groups and, where the backend ships batched primitives, each
group runs through ``jax.vmap``-ed kernel launches (``batch_chunks=``
opts out); ``shard="auto"``/a device count/a 1-D mesh additionally
splits each group across local devices via shard_map
(``parallel.codec_mesh``).  Bytes and
bits never depend on the execution mode — see docs/format.md and
docs/architecture.md.
"""
from .ipcomp import (compress, decompress, retrieve, refine, open_archive,
                     RetrievalState, ChunkedRetrievalState, chunk_bounds,
                     Fidelity, ExecPolicy, IPCompDeprecationWarning)
from .container import CorruptArchiveError
from .interpolation import LINEAR, CUBIC
from . import jax_backend, metrics, pipeline

__all__ = ["compress", "decompress", "retrieve", "refine", "open_archive",
           "RetrievalState", "ChunkedRetrievalState", "chunk_bounds",
           "Fidelity", "ExecPolicy", "IPCompDeprecationWarning",
           "CorruptArchiveError",
           "LINEAR", "CUBIC", "jax_backend", "metrics", "pipeline"]
