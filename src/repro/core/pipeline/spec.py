"""Typed specification objects for the codec pipeline: Fidelity + ExecPolicy.

These two small value types are the vocabulary of the first-class API
(``repro.api``) and the *native* currency of the pipeline internals —
``encode.py`` / ``decode.py`` / ``state.py`` accept them directly instead
of re-threading ``backend=`` / ``batch_chunks=`` / ``shard=`` kwargs and
the mutually-exclusive retrieval-target trio through every call:

:class:`Fidelity`
    A sum type over the four retrieval targets the DP loader (paper §5)
    plans for — ``error_bound`` / ``max_bytes`` / ``bitrate`` / ``full``.
    Exactly one alternative exists per instance, so the historical
    over-specification bug class ("pass two targets, one silently wins")
    is unrepresentable; the legacy kwarg trio is coerced through
    :meth:`Fidelity.from_targets`, which raises on over-specification.

:class:`ExecPolicy`
    The bits-invariant execution knobs — ``backend``, ``batch_chunks``,
    ``shard`` — validated ONCE at construction instead of per call.  The
    structural guarantee (pinned by ``tests/test_policy_matrix.py``): no
    policy ever changes archive bytes or reconstruction bits; policies
    select *how* the same work runs, never *what* it computes.  The
    ``shard=`` resolution rules that used to live in
    ``encode.resolve_exec_mesh`` live here (:func:`resolve_exec_mesh` /
    :meth:`ExecPolicy.resolve_mesh`).

:class:`ExecContext`
    An :class:`ExecPolicy` bound for one call — resolved
    :class:`~.backends.CodecBackend`, resolved mesh (or None), and the
    batching decision for each codec direction.  This is what the
    shape-group schedulers and the ``state.py`` batch helpers consume.

:class:`IPCompDeprecationWarning` is the category every legacy free
function (``compress`` / ``retrieve`` / ``refine`` / ``decompress``)
emits exactly once per call; the CI deprecation lane runs the new-API
suites with ``-W error::repro.api.IPCompDeprecationWarning`` to prove the
object API never routes through a shim.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, replace
from typing import Any, Optional

from . import backends


class IPCompDeprecationWarning(DeprecationWarning):
    """Category for the legacy free-function shims (``compress`` /
    ``retrieve`` / ``refine`` / ``decompress``).  Each shim emits exactly
    one of these per call; the new object API emits none — the CI
    deprecation lane pins both."""


def warn_legacy(old: str, new: str) -> None:
    """One deprecation warning per legacy entry-point call.

    ``stacklevel=3`` points at the *caller* of the legacy function (shim
    body -> legacy function -> caller)."""
    warnings.warn(f"{old} is a compatibility shim; use {new} "
                  "(see repro.api)", IPCompDeprecationWarning, stacklevel=3)


# ------------------------------------------------------------------ Fidelity

#: the four Fidelity alternatives
FULL = "full"
ERROR_BOUND = "error_bound"
MAX_BYTES = "max_bytes"
BITRATE = "bitrate"

_KINDS = (FULL, ERROR_BOUND, MAX_BYTES, BITRATE)


@dataclass(frozen=True)
class Fidelity:
    """One retrieval target: what a progressive read must achieve.

    A sum type — construct through the named alternatives, never by
    juggling mutually-exclusive kwargs::

        Fidelity.error_bound(1e-4)   # point-wise L_inf bound
        Fidelity.max_bytes(1 << 20)  # retrieval-volume budget (data bytes)
        Fidelity.bitrate(2.0)        # bits per point, = max_bytes(b*n/8)
        Fidelity.full()              # every plane: error <= eb everywhere

    The DP loader plans the minimum plane set for the target
    (``loader.plan_error_mode`` / ``plan_bitrate_mode`` / ``plan_full``);
    byte-denominated targets convert through :meth:`target_bytes`.
    Instances are frozen, hashable, and safe to reuse across archives.
    """
    kind: str
    value: Optional[float] = None

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(f"unknown fidelity kind {self.kind!r}; "
                             f"use one of {'/'.join(_KINDS)}")
        if self.kind == FULL:
            if self.value is not None:
                raise ValueError("Fidelity.full() carries no value")
            return
        if self.value is None:
            raise ValueError(f"Fidelity kind {self.kind!r} needs a value")
        v = float(self.value)
        if self.kind == MAX_BYTES:
            if v < 0 or v != int(v):
                raise ValueError("max_bytes must be a non-negative integer "
                                 f"byte count, got {self.value!r}")
            object.__setattr__(self, "value", int(v))  # normalize 64.0 -> 64
        elif v <= 0:
            raise ValueError(f"{self.kind} must be positive, "
                             f"got {self.value!r}")

    # ---- named constructors (the canonical spelling)

    @classmethod
    def error_bound(cls, eb: float) -> "Fidelity":
        """Target a point-wise L_inf error bound."""
        return cls(ERROR_BOUND, float(eb))

    @classmethod
    def max_bytes(cls, n: int) -> "Fidelity":
        """Target a retrieval-volume budget in data bytes (validation in
        ``__post_init__`` — a fractional byte count raises rather than
        silently truncating)."""
        return cls(MAX_BYTES, n)

    @classmethod
    def bitrate(cls, bits_per_point: float) -> "Fidelity":
        """Target a loaded bitrate in bits per point."""
        return cls(BITRATE, float(bits_per_point))

    @classmethod
    def full(cls) -> "Fidelity":
        """Full precision: load every plane (error <= eb everywhere)."""
        return cls(FULL)

    @classmethod
    def from_targets(cls, error_bound: Optional[float] = None,
                     max_bytes: Optional[int] = None,
                     bitrate: Optional[float] = None) -> "Fidelity":
        """Coerce the legacy kwarg trio; over-specification raises.

        This is the one place the historical "exactly one of" contract is
        policed — the message matches the old ``_check_one_target`` so
        callers (and tests) pinned to it keep working.  ``max_bytes`` is
        floored like the old code path tolerated (the legacy planner took
        float budgets); only the canonical :meth:`max_bytes` constructor
        rejects fractional byte counts.
        """
        given = [name for name, v in ((ERROR_BOUND, error_bound),
                                      (MAX_BYTES, max_bytes),
                                      (BITRATE, bitrate)) if v is not None]
        if len(given) > 1:
            raise ValueError("pass at most one of error_bound/max_bytes/"
                             f"bitrate (got {', '.join(given)})")
        if error_bound is not None:
            return cls.error_bound(error_bound)
        if max_bytes is not None:
            return cls.max_bytes(int(max_bytes))
        if bitrate is not None:
            return cls.bitrate(bitrate)
        return cls.full()

    # ---- planning helpers

    def target_bytes(self, n_elements: int) -> Optional[int]:
        """Byte budget for byte-denominated targets, else None.

        ``bitrate`` converts exactly as the legacy path did:
        ``int(bits_per_point * n / 8)``.
        """
        if self.kind == MAX_BYTES:
            return int(self.value)
        if self.kind == BITRATE:
            return int(self.value * n_elements / 8)
        return None

    def __repr__(self) -> str:
        if self.kind == FULL:
            return "Fidelity.full()"
        v = int(self.value) if self.kind == MAX_BYTES else self.value
        return f"Fidelity.{self.kind}({v!r})"


# ---------------------------------------------------------------- ExecPolicy

def resolve_exec_mesh(shard, backend_shards: bool, *, chunked: bool,
                      batch_chunks: Optional[bool]):
    """``shard=`` policy shared by both codec directions -> mesh or None.

    Delegates mesh resolution to ``parallel.codec_mesh.resolve_shard``
    ("auto" -> all local devices when >1, n -> the first n devices, Mesh
    -> validated 1-D), then
    applies the pipeline rules: sharding needs a chunk grid and the
    stacked scheduler, so an *explicit* mesh combined with an unchunked
    archive or ``batch_chunks=False`` is a contradiction and raises, while
    ``"auto"`` quietly stays unsharded in those cases.  A backend without
    sharded primitives (the numpy reference) always falls back to its
    unsharded path — mirroring how missing ``*_batch`` slots fall back to
    the per-chunk loop.
    """
    if shard is None or shard is False:
        return None
    from ...parallel import codec_mesh

    mesh = codec_mesh.resolve_shard(shard)
    if mesh is None:
        return None
    explicit = shard != codec_mesh.AUTO
    if not chunked:
        if explicit:
            raise ValueError("sharded execution runs over the chunk grid: "
                             "pass chunk_elems= (v1 archives have no "
                             "chunks to place on the mesh)")
        return None
    if batch_chunks is False:
        if explicit:
            raise ValueError("shard= needs the stacked shape-group "
                             "scheduler; it cannot be combined with "
                             "batch_chunks=False")
        return None
    return mesh if backend_shards else None


@dataclass(frozen=True)
class ExecPolicy:
    """How the codec executes — never what it computes.

    Bundles the three bits-invariant execution knobs:

    ``backend``
        "numpy" | "jax" | "auto"/None ("auto" = jax only where the Pallas
        kernels compile natively, i.e. TPU).
    ``batch_chunks``
        Equal-shape chunk batching for v2 archives: None/True = batch when
        the backend ships batched primitives, False = per-chunk loop.
    ``shard``
        None | "auto" | a device count ``n`` | an explicit 1-D
        ``jax.sharding.Mesh`` — the chunk grid is split across the mesh
        (``n``: ``codec_mesh(n)``, the first ``n`` local devices) and each
        device runs its local shard.  "auto" degrades quietly (no mesh on
        a single device, no mesh for v1 archives); a count or an explicit
        mesh is a hard request and raises where it cannot apply (a count
        outside ``1..jax.device_count()`` at construction).

    Validation happens ONCE here: unknown backends, malformed ``shard``
    values, and the explicit-mesh + ``batch_chunks=False`` contradiction
    all raise at construction.  Only the archive-dependent rule (an
    explicit mesh needs a chunk grid) waits for :meth:`bind`, because it
    depends on what is being read or written.

    The structural guarantee — enforced by the pipeline design (per-chunk
    metadata, escapes and accounting are always derived per chunk on the
    host) and pinned by the policy-invariance matrix — is that **no policy
    changes archive bytes or reconstruction bits**.  Writer and readers
    may therefore use different policies freely, including mid-session.
    """
    backend: Optional[str] = "numpy"
    batch_chunks: Optional[bool] = None
    shard: Any = None

    def __post_init__(self):
        if self.backend not in (None, backends.AUTO):
            backends.resolve_name(self.backend)  # raises on unknown names
        if self.batch_chunks not in (None, True, False):
            raise ValueError("batch_chunks must be None, True or False, "
                             f"got {self.batch_chunks!r}")
        if self.shard is not None and self.shard is not False:
            from ...parallel import codec_mesh
            if self.shard != codec_mesh.AUTO:
                codec_mesh.resolve_shard(self.shard)  # form + 1-D check
                if self.batch_chunks is False:
                    raise ValueError("shard= needs the stacked shape-group "
                                     "scheduler; it cannot be combined "
                                     "with batch_chunks=False")

    def resolve_mesh(self, backend_shards: bool, *, chunked: bool):
        """Apply the ``shard=`` rules for one call (see
        :func:`resolve_exec_mesh`)."""
        return resolve_exec_mesh(self.shard, backend_shards,
                                 chunked=chunked,
                                 batch_chunks=self.batch_chunks)

    def bind(self, *, chunked: bool, encode: bool) -> "ExecContext":
        """Resolve this policy for one call -> :class:`ExecContext`.

        ``chunked`` is the archive's property (v2 chunk grid or not);
        ``encode`` picks which direction's sharded capability gates the
        mesh.  Raises where an explicit mesh cannot apply (v1 archive).
        """
        bk = backends.get(self.backend)
        shards = bk.shards_encode if encode else bk.shards_decode
        mesh = self.resolve_mesh(shards, chunked=chunked)
        return ExecContext(bk=bk, mesh=mesh, batch_chunks=self.batch_chunks)

    def unsharded(self) -> "ExecPolicy":
        """This policy without the mesh (per-chunk scalar sub-calls)."""
        return replace(self, shard=None) if self.shard is not None else self


@dataclass(frozen=True)
class ExecContext:
    """An :class:`ExecPolicy` bound for one call: resolved backend,
    resolved mesh (or None), and the per-direction batching decision.
    This — not loose (bk, mesh) pairs — is what the shape-group
    schedulers and the ``state.py`` batch helpers consume."""
    bk: backends.CodecBackend
    mesh: Any = None
    batch_chunks: Optional[bool] = None

    @property
    def batch_encode(self) -> bool:
        """Schedule encode-side shape groups through the batched stack?"""
        return self.batch_chunks is not False and (
            self.bk.batches_encode or self.mesh is not None)

    @property
    def batch_decode(self) -> bool:
        """Schedule decode-side shape groups through the batched stack?"""
        return self.batch_chunks is not False and (
            self.bk.batches_decode or self.mesh is not None)


#: the default policy: numpy reference, batching decided by the backend,
#: no mesh.  Module-level singleton so hot paths need not rebuild it.
DEFAULT_POLICY = ExecPolicy()
