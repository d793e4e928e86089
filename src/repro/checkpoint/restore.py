"""Progressive restore sessions over checkpoint bundles.

:class:`RestoreSession` is the restart-side consumer of everything the
retrieval stack provides:

* **Grouped decode** — equal-shaped chunk jobs from *different* leaves
  are bucketed together and executed through the shared
  :func:`~repro.core.pipeline.decode.decode_group` batched path, so a
  transformer checkpoint with N identical attention matrices decodes in
  one kernel launch per shape group instead of one per leaf
  (``group_leaves=False`` keeps the per-leaf loop for A/B dispatch
  accounting; bits are identical either way).
* **Refine-reads-only-the-delta** — per-leaf
  :class:`~repro.core.pipeline.state.ChunkedRetrievalState` carries the
  loaded ladder prefix between rounds; a tighter ``weight_error`` (or
  ``None`` = full precision) fetches exactly the missing plane
  segments.  The bundle manifest is parsed once at open and cached on
  the session's :class:`~repro.checkpoint.bundle.Bundle` — no per-round
  manifest re-reads.
* **Restore-while-refine** — :meth:`refine_async` streams the remaining
  planes on a background thread while the trainer steps on the coarse
  weights.  Each round assembles *fresh* output arrays and publishes
  them with one attribute swap under the session lock (double-buffered:
  the tree the trainer holds is never mutated mid-step).
* **Integrity on read** — each leaf's verified prefix (header + anchors
  + escapes; whole blob for raw leaves) is sha-checked the first time
  the leaf is opened, local or remote, raising
  :class:`~repro.core.container.CorruptArchiveError` naming the leaf.
* **Honest accounting** — ``raw`` leaves are read once, cached, and
  report exact-zero error in ``leaf_bounds``; ``bytes_read`` aggregates
  the per-leaf reader ledgers plus the one-time raw reads (integrity
  verification reads are overhead, not retrieval volume, and are not
  counted).

Sessions are framework-free (numpy in, numpy out, keyed by leaf id);
``checkpoint.store`` supplies the pytree ``unflatten`` hook.
"""
from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import numpy as np

from ..core import loader
from ..core.container import (ArchiveReader, CorruptArchiveError,
                              V3ArchiveReader, open_reader)
from ..core.pipeline import spec as pipeline_spec
from ..core.pipeline.decode import decode_group, plan_ladder, plan_retrieval
from ..core.pipeline.encode import group_cap
from ..core.pipeline.spec import ExecPolicy, Fidelity
from ..core.pipeline.state import ChunkedRetrievalState, RetrievalState
from .bundle import Bundle


def read_full(bundle: Bundle, *, verify: bool = True,
              policy: Optional[ExecPolicy] = None) -> Dict[str, np.ndarray]:
    """Full-precision, fully-verified read of every leaf: each blob is
    fetched whole, sha256-checked against the manifest (raising
    :class:`CorruptArchiveError` naming the leaf), then decoded at
    ``Fidelity.full()``.  The non-progressive restore path."""
    from ..api import Archive
    out: Dict[str, np.ndarray] = {}
    for lid in bundle.leaf_order:
        e = bundle.entry(lid)
        blob = bundle.read_leaf_bytes(lid, verify=verify)
        if e["kind"] == "raw":
            arr = np.frombuffer(blob, np.float32).reshape(e["shape"])
        else:
            arr = Archive(blob).open(policy).read(Fidelity.full())
        out[lid] = arr.reshape(e["shape"]).astype(np.dtype(e["dtype"]))
    return out


class RestoreSession:
    """Progressive, refinable restore of one checkpoint bundle.

    ``unflatten`` (optional) maps the session's ``{leaf_id: array}``
    result dict to the caller's tree type; without it, methods return
    the dict itself.  All public methods are thread-safe; decode rounds
    serialize on the session lock (the background refiner and a
    foreground ``restore`` never interleave mid-round).
    """

    def __init__(self, bundle: Union[Bundle, str, bytes], *,
                 policy: Optional[ExecPolicy] = None,
                 propagation: str = loader.SAFE,
                 plane_cache=None, group_leaves: bool = True,
                 verify: bool = True,
                 exact: Optional[Callable[[str], bool]] = None,
                 unflatten: Optional[Callable[[Dict[str, np.ndarray]],
                                              Any]] = None):
        self.bundle = bundle if isinstance(bundle, Bundle) \
            else Bundle.open(bundle)
        self.policy = pipeline_spec.DEFAULT_POLICY if policy is None \
            else policy
        self.propagation = propagation
        self.plane_cache = plane_cache
        self.group_leaves = group_leaves
        self.verify = verify
        #: precision-critical leaf predicate: leaves matching ``exact``
        #: decode at full precision in every round, regardless of the
        #: requested ``weight_error`` (e.g. optimizer second moments,
        #: whose near-zero values flip sign under a range-relative
        #: coarse bound and destabilize the resumed update rule)
        self.exact = exact
        self.unflatten = unflatten
        #: backend-independent primitive counts (``decode_level`` /
        #: ``reconstruct`` / ...) accumulated across rounds — the
        #: dispatch-accounting surface that works on every backend
        self.counters: Dict[str, int] = {}
        #: per-leaf achieved absolute error bound after the last round
        #: (``raw`` leaves: exact 0.0)
        self.leaf_bounds: Dict[str, float] = {}
        self.closed = False
        #: per-leaf reader: V3ArchiveReader for ``ipc`` leaves (plane-
        #: major, contiguous-prefix reads), plain ArchiveReader for the
        #: compact ``ipc1`` leaves (still bitplane-progressive)
        self._readers: Dict[str, Any] = {}
        #: per-leaf decode state: ChunkedRetrievalState for ``ipc``,
        #: RetrievalState (or None before the first round) for ``ipc1``
        self._states: Dict[str, Any] = {}
        self._raw: Dict[str, np.ndarray] = {}
        self._raw_bytes = 0
        self._lock = threading.RLock()
        self._refiner: Optional[threading.Thread] = None
        self._refined: Optional[Tuple[Optional[float], Any]] = None
        self._refine_exc: Optional[BaseException] = None

    # --------------------------------------------------------- properties

    @property
    def manifest(self) -> Dict:
        """The bundle manifest — parsed once at open, cached for every
        refinement round."""
        return self.bundle.manifest

    @property
    def step(self) -> int:
        return self.bundle.step

    @property
    def bytes_read(self) -> int:
        """Retrieval volume so far: per-leaf reader ledgers (anchors +
        escapes + fetched plane blobs) plus one-time raw-leaf reads."""
        with self._lock:
            return sum(r.bytes_read for r in self._readers.values()) \
                + self._raw_bytes

    @property
    def achieved_bound(self) -> float:
        """Max achieved absolute error bound across leaves (0.0 before
        the first round / when every leaf is raw)."""
        with self._lock:
            return max(self.leaf_bounds.values(), default=0.0)

    def leaf_bound(self, lid: str,
                   weight_error: Optional[float]) -> Optional[float]:
        """The absolute per-leaf error bound a relative ``weight_error``
        induces: ``weight_error`` scales each leaf's value range (the
        stored eb is ``rel_eb`` of the range, so the ratio recovers the
        range), floored at the leaf's own eb.  ``None`` = full
        precision; ``raw`` leaves are always exact (0.0); leaves
        matching the session's ``exact`` predicate always restore at
        full precision."""
        if self.bundle.entry(lid)["kind"] == "raw":
            return 0.0
        if weight_error is None or \
                (self.exact is not None and self.exact(lid)):
            return None
        eb = self._reader(lid).meta.eb
        return max(weight_error * eb / self.bundle.rel_eb, eb)

    # ----------------------------------------------------------- plumbing

    def _reader(self, lid: str):
        """The leaf's archive reader, verified on first open.  The
        manifest's ``kind`` must match the stored container (``ipc`` =
        IPC3 plane-major, ``ipc1`` = compact v1) — a mismatch means the
        bundle was rewritten and fails loudly."""
        r = self._readers.get(lid)
        if r is None:
            kind = self.bundle.entry(lid)["kind"]
            if self.verify:
                self.bundle.verify_leaf_prefix(lid)
            r = open_reader(self.bundle.leaf_source(lid))
            want = V3ArchiveReader if kind == "ipc" else ArchiveReader
            if type(r) is not want:
                raise CorruptArchiveError(
                    f"checkpoint leaf {lid!r} is declared {kind!r} in the "
                    f"manifest but its bytes hold a different container "
                    "(rewritten or corrupt bundle)")
            if self.plane_cache is not None:
                r.cache_scope = (self.bundle.manifest_sha, lid)
            self._readers[lid] = r
            self._states[lid] = ChunkedRetrievalState(
                reader=r, chunk_states=[None] * len(r.meta.chunks)) \
                if kind == "ipc" else None
        return r

    def _raw_leaf(self, lid: str) -> np.ndarray:
        arr = self._raw.get(lid)
        if arr is None:
            e = self.bundle.entry(lid)
            blob = self.bundle.read_leaf_bytes(lid, verify=self.verify)
            arr = np.frombuffer(blob, np.float32).reshape(e["shape"]) \
                .astype(np.dtype(e["dtype"]))
            self._raw[lid] = arr
            self._raw_bytes += len(blob)
            self.leaf_bounds[lid] = 0.0   # lossless: honest zero error
        return arr

    # ------------------------------------------------------------ restore

    def restore(self, weight_error: Optional[float] = None):
        """One decode round at ``weight_error`` (relative to each leaf's
        value range; ``None`` = full precision).  Returns fresh arrays —
        previously returned trees are never mutated.  Successive calls
        refine: only the missing plane segments are fetched, and a
        looser request than what is already loaded is a no-op read
        (prefixes never shrink)."""
        with self._lock:
            arrays = self._restore_locked(weight_error)
        return self.unflatten(arrays) if self.unflatten else arrays

    def _restore_locked(self, weight_error: Optional[float]
                        ) -> Dict[str, np.ndarray]:
        if self.closed:
            raise RuntimeError(
                "RestoreSession is closed; open a new session to restore")
        ctx = self.policy.bind(chunked=True, encode=False)
        # plan every compressed leaf first (one ensure_prefix = one
        # contiguous range per plane-major leaf), then bucket chunk jobs
        # ACROSS leaves by chunk shape and arithmetic dtype (one batched
        # sweep computes in one dtype) so equal-shaped leaves share
        # batched kernel launches; an ipc1 leaf is a single job keyed by
        # its own shape, so same-shape v1 leaves batch with each other
        # (and with same-shape v3 chunks — both are plain v1 sub-readers)
        buckets: Dict[Any, List[tuple]] = {}
        round_ts: Dict[str, int] = {}
        for lid in self.bundle.leaf_order:
            e = self.bundle.entry(lid)
            if e["kind"] == "raw":
                self._raw_leaf(lid)
                continue
            reader = self._reader(lid)
            m = reader.meta
            bound = self.leaf_bound(lid, weight_error)
            fid = Fidelity.full() if bound is None \
                else Fidelity.error_bound(bound)
            if e["kind"] == "ipc1":
                keep = plan_retrieval(m, fid, self.propagation).keep_planes
                key = (tuple(m.shape), m.work_dtype) \
                    if self.group_leaves else (lid,)
                buckets.setdefault(key, []).append(
                    (lid, None, reader, self._states[lid], keep))
                continue
            st = self._states[lid]
            t = plan_ladder(m, fid, self.propagation, t_min=st.ladder_pos)
            reader.ensure_prefix(t)
            keeps = m.ladder_keeps(t)
            round_ts[lid] = t
            for ci in range(len(m.chunks)):
                sub = reader.chunk_reader(ci)
                key = (tuple(sub.meta.shape),
                       sub.meta.work_dtype) \
                    if self.group_leaves else (lid, ci)
                buckets.setdefault(key, []).append(
                    (lid, ci, sub, st.chunk_states[ci], keeps[ci]))
        cap = group_cap(ctx.mesh)
        for jobs in buckets.values():
            for lo in range(0, len(jobs), cap):
                grp = jobs[lo:lo + cap]
                sts = decode_group([j[2] for j in grp], [j[3] for j in grp],
                                   [j[4] for j in grp], ctx,
                                   self.propagation, cache=self.plane_cache,
                                   counters=self.counters)
                for (lid, ci, *_), st_new in zip(grp, sts):
                    if ci is None:
                        self._states[lid] = st_new
                    else:
                        self._states[lid].chunk_states[ci] = st_new
        # finalize per-leaf accounting and assemble fresh outputs
        arrays: Dict[str, np.ndarray] = {}
        for lid in self.bundle.leaf_order:
            e = self.bundle.entry(lid)
            if e["kind"] == "raw":
                arrays[lid] = self._raw[lid]
                continue
            reader, st = self._readers[lid], self._states[lid]
            m = reader.meta
            if e["kind"] == "ipc1":
                out = st.xhat
            else:
                st.err_bound = max(cs.err_bound for cs in st.chunk_states)
                st.bytes_read = reader.bytes_read
                st.ladder_pos = max(st.ladder_pos, round_ts[lid])
                out = np.empty(m.shape, np.dtype(m.dtype))
                for ci, cm in enumerate(m.chunks):
                    out[cm.start:cm.stop] = \
                        st.chunk_states[ci].xhat.astype(np.dtype(m.dtype))
            arrays[lid] = np.asarray(out).reshape(e["shape"]) \
                .astype(np.dtype(e["dtype"]))
            self.leaf_bounds[lid] = float(st.err_bound)
        return arrays

    # ----------------------------------------------- refine-while-training

    def refine_async(self, weight_error: Optional[float] = None,
                     on_update: Optional[Callable] = None
                     ) -> threading.Thread:
        """Stream the remaining planes to ``weight_error`` (``None`` =
        full precision) on a background daemon thread while the caller
        keeps using the coarse tree.  The refined tree is published
        atomically (:meth:`poll_refined` / :meth:`refined`); ``on_update
        (weight_error, tree)`` fires after publication.  One refiner at
        a time."""
        with self._lock:
            if self.closed:
                raise RuntimeError("RestoreSession is closed")
            if self._refiner is not None and self._refiner.is_alive():
                raise RuntimeError("a background refiner is already running")
            self._refine_exc = None
            self._refiner = threading.Thread(
                target=self._refine_body, args=(weight_error, on_update),
                name=f"ckpt-refine-step{self.step}", daemon=True)
            self._refiner.start()
            return self._refiner

    def _refine_body(self, weight_error, on_update):
        try:
            tree = self.restore(weight_error)
            with self._lock:
                self._refined = (weight_error, tree)
            if on_update is not None:
                on_update(weight_error, tree)
        except BaseException as e:     # surfaced via poll_refined/refined
            self._refine_exc = e

    @property
    def refining(self) -> bool:
        t = self._refiner
        return t is not None and t.is_alive()

    @property
    def done(self) -> bool:
        """No refiner running (either never started or finished)."""
        return not self.refining

    def poll_refined(self):
        """Non-blocking: the latest published refined tree, or ``None``
        if not ready.  Re-raises a failed refiner's exception."""
        with self._lock:
            if self._refine_exc is not None:
                exc, self._refine_exc = self._refine_exc, None
                raise exc
            return None if self._refined is None else self._refined[1]

    def refined(self, timeout: Optional[float] = None):
        """Join the refiner and return the refined tree (``None`` if no
        refiner ran).  Re-raises the refiner's exception on failure."""
        t = self._refiner
        if t is not None:
            t.join(timeout)
        return self.poll_refined()

    # --------------------------------------------------- plan introspection

    def ladder_positions(self) -> Dict[str, int]:
        """Per-leaf loaded ladder-prefix length (plane segments) — only
        plane-major (``ipc``) leaves have a ladder."""
        with self._lock:
            return {lid: st.ladder_pos for lid, st in self._states.items()
                    if isinstance(st, ChunkedRetrievalState)}

    def plane_bytes_between(self, before: Dict[str, int],
                            after: Dict[str, int]) -> int:
        """Exact plane-segment bytes between two :meth:`ladder_positions`
        snapshots — what a refine *should* fetch.  The refine-never-
        rereads gate compares this against the session's ``bytes_read``
        delta."""
        total = 0
        with self._lock:
            for lid, t1 in after.items():
                cum = self._readers[lid].meta.cum_bytes
                total += cum[t1] - cum[before.get(lid, 0)]
        return total

    # ----------------------------------------------------------- lifecycle

    def close(self) -> None:
        """Join any refiner, release the bundle source, and mark the
        session closed (a manager's keep-rotation gc treats the pinned
        step as collectable again)."""
        t = self._refiner
        if t is not None and t.is_alive():
            t.join()
        with self._lock:
            if self.closed:
                return
            self.closed = True
        self.bundle.close()

    def __enter__(self) -> "RestoreSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
