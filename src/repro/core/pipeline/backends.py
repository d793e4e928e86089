"""Codec backend registry: the numpy reference and the jax/Pallas kernels.

Replaces the ad-hoc ``if bk == jax_backend.JAX:`` string checks that used to
live inside ``ipcomp``: each :class:`CodecBackend` bundles the four hot-path
primitives both directions of the codec need, ``encode.py`` / ``decode.py``
call through the resolved backend object, and neither ever tests a backend
name again.  Registering a third backend (a future GPU path, a vmapped
chunk-batch path, ...) is one :func:`register` call — the pipeline code does
not change.

Primitive contracts (all bit-identical across backends — the parity test
suites pin this down):

  decorrelate(x, eb, interp) -> (xhat, qs, escs, anchors)
      compression-side sweep in the field's arithmetic (``core.arith``):
      per-level int64 bin streams + escape records with level-global
      indices (see ``interpolation.decorrelate_batch``).
  encode_level(q_int64, nb_uint32) -> (blobs MSB-first, nbits)
      negabinary + XOR-predictive bitplane packing of one level stream;
      both representations of the same values are passed so each substrate
      starts from whichever it prefers (numpy from the host-precomputed
      negabinary words, the kernel from the raw bins it converts on-device)
      without a redundant O(n) conversion.
  decode_level(blobs, nbits, n) -> uint32 truncated negabinary
      inverse of encode_level for a loaded MSB-first blob prefix
      (None = not loaded; b'' = loaded, all-zero encoded plane).
  reconstruct(shape, interp, anchors, yhat_per_level, overrides=, out_dtype=,
              dtype=)
      decompression-side sweep (Algorithm 1 core) in working dtype
      ``dtype``; linear in (anchors, yhat) up to rounding, which
      Algorithm 2's zero-anchor delta cascade relies on.

Each primitive may also ship an OPTIONAL batched twin (``*_batch``) that
processes a stack of equal-shaped chunk problems in one kernel dispatch —
the unit the v2 chunk scheduler feeds (see ``encode``/``decode`` shape-group
scheduling and ``docs/architecture.md`` for the full dataflow):

  decorrelate_batch(xs (B, *shape), eb, interp) -> B-list of the
      scalar tuples;
  encode_level_batch(q2 (B, n), nb2 (B, n)) -> B-list of (blobs, nbits);
  decode_level_batch(B blob-prefix lists w/ equal nbits AND equal loaded
      prefix, nbits, n) -> B-list of truncated negabinary arrays;
  reconstruct_batch(shape, interp, anchors (B, ...), yhat [(B, n_l)],
      overrides=per-item list, out_dtype=, dtype=) -> (B, *shape).

And each batched twin may ship an OPTIONAL *sharded* twin (``*_sharded``)
— identical contract plus one trailing required argument, a 1-D device
mesh (``parallel.codec_mesh``), over which the stack axis is split so
every mesh device executes the batched primitive on its local chunks:

  decorrelate_sharded(xs, eb, interp, mesh)        -> as decorrelate_batch
  encode_level_sharded(q2, nb2, mesh)              -> as encode_level_batch
  decode_level_sharded(blob_lists, nbits, n, mesh) -> as decode_level_batch
  reconstruct_sharded(shape, interp, anchors, yhat, mesh, overrides=,
      out_dtype=, dtype=)                          -> as reconstruct_batch

Decode-side FUSED slots (all optional, adopted by the progressive session
scheduler in ``pipeline/state.py`` when present):

  inflate_level(blobs, nbits, n) -> ((32, ceil(n/32)) uint32 words, want)
      host-side zlib inflate + word packing of one level's loaded blob
      prefix — the CPU half the scheduler can overlap with device work;
  inflate_level_batch(blob_lists, nbits, n) -> ((B, 32, nw) words, wants)
  decode_level_fused(blobs, nbits, n, nb_old, eb, words=, dtype=) ->
      (nb_new uint32, out): ONE launch fusing plane-unpack + negabinary
      dequantize; ``out`` is, for a float64 field, the Algorithm 2 delta
      against the session's previous truncation ``nb_old`` (delta =
      (q_new - q_old) * 2 * eb) and, for a float32 field, the level's
      full float32 residual — either bit-identical to the host
      arithmetic; ``words=`` accepts a prefetched ``inflate_level``
      result so the zlib work can run ahead of time;
  decode_level_fused_batch(blob_lists, nbits, n, nb_olds, ebs, words=,
      dtype=) -> B-list of (nb_new, out) with PER-CHUNK loaded prefixes
      and per-chunk error bounds (mixed prefixes in one dispatch);
  decode_level_fused_sharded(..., mesh=) — same over the 1-D codec mesh.

``dynamic_low_zero=True`` declares that the batched decode paths accept
*mixed* loaded-plane prefixes in one dispatch (the truncation mask is a
runtime operand, not a trace constant) — the scheduler then groups chunk
jobs by ``(nbits,)`` instead of ``(nbits, prefix)``, collapsing what used
to be one dispatch per distinct prefix into one per level.

``None`` slots mean "no batched/sharded form": the pipeline falls back to
the next-simpler execution (sharded -> batched -> per-chunk loop over the
scalar primitive), so the numpy reference needs no batch code and
third-party backends can adopt batching/sharding incrementally.  The
capability properties (:attr:`CodecBackend.batches_encode` /
``batches_decode`` / ``shards_encode`` / ``shards_decode``) are what the
schedulers consult — pipeline code never tests a backend name.  Batched
AND sharded results must be bit-identical to the loop: the batch axis and
the mesh are execution details, never a format change (the chunk-batching
and sharded-codec test suites pin this).

Selection: ``"numpy"`` | ``"jax"`` | ``"jax_unfused"`` | ``"auto"``/None.
"auto" picks jax only where the kernels actually compile (TPU); on GPU/CPU
they would run in the (slow) Pallas interpreter — valid for parity testing,
so request it explicitly with ``backend="jax"`` rather than have "auto"
silently emulate.  ``"jax_unfused"`` is the pre-fusion jax path (separate
unpack launch + host dequantize, per-prefix decode grouping, no fused
decode slots), kept registered as the benchmark baseline the fused path is
measured against.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from .. import bitplane, interpolation, jax_backend
# single source for the backend-name constants (the reverse import would be
# circular: jax_backend.resolve delegates here function-locally)
from ..jax_backend import AUTO, JAX, JAX_UNFUSED, NUMPY


@dataclass(frozen=True)
class CodecBackend:
    """The four codec primitives one execution substrate provides, plus
    optional batched twins over stacks of equal-shaped chunk problems and
    optional sharded twins over (stack, 1-D device mesh) — None slots mean
    the pipeline falls back to the next-simpler execution (sharded ->
    batched -> per-chunk scalar loop)."""
    name: str
    decorrelate: Callable
    encode_level: Callable
    decode_level: Callable
    reconstruct: Callable
    decorrelate_batch: Optional[Callable] = None
    encode_level_batch: Optional[Callable] = None
    decode_level_batch: Optional[Callable] = None
    reconstruct_batch: Optional[Callable] = None
    decorrelate_sharded: Optional[Callable] = None
    encode_level_sharded: Optional[Callable] = None
    decode_level_sharded: Optional[Callable] = None
    reconstruct_sharded: Optional[Callable] = None
    # fused decode megakernel family (see module docstring): one launch per
    # level fusing plane-unpack + dequantize + the Algorithm 2 delta, plus
    # the host-side inflate half the scheduler overlaps with device work
    decode_level_fused: Optional[Callable] = None
    decode_level_fused_batch: Optional[Callable] = None
    decode_level_fused_sharded: Optional[Callable] = None
    inflate_level: Optional[Callable] = None
    inflate_level_batch: Optional[Callable] = None
    #: batched decode accepts mixed loaded-plane prefixes in one dispatch
    #: (truncation mask is a runtime operand) -> scheduler groups by
    #: ``(nbits,)`` instead of ``(nbits, prefix)``
    dynamic_low_zero: bool = False

    @property
    def batches_encode(self) -> bool:
        return (self.decorrelate_batch is not None
                and self.encode_level_batch is not None)

    @property
    def batches_decode(self) -> bool:
        return (self.decode_level_batch is not None
                and self.reconstruct_batch is not None)

    @property
    def shards_encode(self) -> bool:
        return (self.decorrelate_sharded is not None
                and self.encode_level_sharded is not None)

    @property
    def shards_decode(self) -> bool:
        return (self.decode_level_sharded is not None
                and self.reconstruct_sharded is not None)


_REGISTRY: Dict[str, CodecBackend] = {}


def register(backend: CodecBackend) -> CodecBackend:
    """Add (or replace) a backend under ``backend.name``."""
    _REGISTRY[backend.name] = backend
    return backend


def names() -> List[str]:
    return sorted(_REGISTRY)


def resolve_name(choice) -> str:
    """Map a user-facing backend choice to a registered backend name.

    "auto"/None picks jax only where the kernels compile to native code
    (TPU); everywhere else the numpy reference wins on speed.
    """
    if choice in (None, AUTO):
        import jax
        return JAX if jax.default_backend() == "tpu" else NUMPY
    if choice not in _REGISTRY:
        opts = "|".join(names() + [AUTO])
        raise ValueError(f"unknown backend {choice!r}; use {opts}")
    return choice


def get(choice) -> CodecBackend:
    """Resolve a backend choice ("numpy" | "jax" | "auto"/None) to its
    registered :class:`CodecBackend`."""
    return _REGISTRY[resolve_name(choice)]


# ---------------------------------------------------------- numpy reference

def _numpy_encode_level(q: np.ndarray, nb: np.ndarray) -> Tuple[List[bytes], int]:
    return bitplane.encode_level(nb)


def _jax_encode_level(q: np.ndarray, nb: np.ndarray) -> Tuple[List[bytes], int]:
    return jax_backend.encode_level(q)


def _jax_encode_level_batch(q2: np.ndarray, nb2: np.ndarray,
                            ) -> List[Tuple[List[bytes], int]]:
    return jax_backend.encode_level_batch(q2)


def _jax_encode_level_sharded(q2: np.ndarray, nb2: np.ndarray, mesh,
                              ) -> List[Tuple[List[bytes], int]]:
    return jax_backend.encode_level_sharded(q2, mesh)


register(CodecBackend(
    name=NUMPY,
    decorrelate=interpolation.decorrelate,
    encode_level=_numpy_encode_level,
    decode_level=bitplane.decode_level,
    reconstruct=interpolation.reconstruct,
    # no batch slots: the reference stays a per-chunk loop by construction
))

register(CodecBackend(
    name=JAX,
    decorrelate=jax_backend.decorrelate,
    encode_level=_jax_encode_level,
    decode_level=jax_backend.decode_level,
    reconstruct=jax_backend.reconstruct,
    decorrelate_batch=jax_backend.decorrelate_batch,
    encode_level_batch=_jax_encode_level_batch,
    decode_level_batch=jax_backend.decode_level_batch,
    reconstruct_batch=jax_backend.reconstruct_batch,
    decorrelate_sharded=jax_backend.decorrelate_sharded,
    encode_level_sharded=_jax_encode_level_sharded,
    decode_level_sharded=jax_backend.decode_level_sharded,
    reconstruct_sharded=jax_backend.reconstruct_sharded,
    decode_level_fused=jax_backend.decode_level_fused,
    decode_level_fused_batch=jax_backend.decode_level_fused_batch,
    decode_level_fused_sharded=jax_backend.decode_level_fused_sharded,
    inflate_level=jax_backend.inflate_level,
    inflate_level_batch=jax_backend.inflate_level_batch,
    dynamic_low_zero=True,
))

# the pre-fusion jax path: identical encode side, archives and sweep, but
# decode runs the separate unpack launch + host dequantize with per-prefix
# dispatch grouping.  Kept registered (and so selectable through
# ExecPolicy) as the measured baseline for the fused megakernel benchmarks.
register(CodecBackend(
    name=JAX_UNFUSED,
    decorrelate=jax_backend.decorrelate,
    encode_level=_jax_encode_level,
    decode_level=jax_backend.decode_level,
    reconstruct=jax_backend.reconstruct,
    decorrelate_batch=jax_backend.decorrelate_batch,
    encode_level_batch=_jax_encode_level_batch,
    decode_level_batch=jax_backend.decode_level_batch,
    reconstruct_batch=jax_backend.reconstruct_batch,
    decorrelate_sharded=jax_backend.decorrelate_sharded,
    encode_level_sharded=_jax_encode_level_sharded,
    decode_level_sharded=jax_backend.decode_level_sharded,
    reconstruct_sharded=jax_backend.reconstruct_sharded,
))
