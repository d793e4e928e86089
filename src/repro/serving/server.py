"""Continuous-batching retrieval server over progressive archives.

The production shape of the paper's workload (ROADMAP item 1): many
concurrent readers ask for the *same* archives at *different* fidelities,
and progressive bytes are shared ordered streams — so both the decoded
prefixes and the kernel launches are shareable across requests.  The
server realizes both:

* a request queue of ``(archive_id, Fidelity)`` jobs
  (:meth:`RetrievalServer.submit`), drained in scheduler ticks
  (:meth:`run_tick` / :meth:`drain`) — the structural twin of the model
  decode loop in ``launch.serve``, with bitplane prefixes in place of KV
  caches;
* a shared :class:`~.cache.PlaneCache` (``plane cache``): requests that
  reach a (chunk, prefix) another session already decoded skip the fetch
  *and* the unpack kernel;
* **cross-request coalescing**: each tick, the per-chunk decode jobs of
  *all* runnable requests are grouped by shape signature and executed
  through :func:`~repro.core.pipeline.decode.decode_group` — the same
  batched executor in-session chunk groups use — so one
  ``decode_level_batch`` / ``reconstruct_batch`` launch serves chunks
  from many requests at once (``coalesce=False`` keeps groups
  per-request, for A/B dispatch accounting).

Requests are isolated: a planner error (e.g. an infeasible
``Fidelity.max_bytes``) fails that request with the error message and
the tick goes on.  Transient transport errors (remote sources timing
out, resetting, running out of their own wire retries) consume a
per-request retry budget instead: the request re-queues and re-plans
from its committed progressive state; when the budget runs out it
settles ``partial`` at the last fully decoded rung — a bit-exact
coarser answer with the error recorded — and stays chainable for
children (``docs/architecture.md`` "Remote retrieval").  Reconstruction bits are identical to a private
uncached session per request — caching, dedup, and coalescing are
execution details (pinned by ``tests/test_serve_tier.py`` and the
``benchmarks/serve_bench.py`` parity check).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..api import Archive, ExecPolicy, Fidelity
from ..core import loader
from ..core.container import V3ArchiveReader
from ..core.pipeline import decode, spec
from ..core.pipeline.encode import group_cap
from ..core.pipeline.state import (ChunkedRetrievalState, RetrievalState,
                                   fork_state)
from ..core.remote import RemoteProtocolError
from .cache import PlaneCache

QUEUED = "queued"
RUNNING = "running"
DONE = "done"
FAILED = "failed"
#: retries exhausted mid-refine, but an earlier rung was fully decoded:
#: the request settles with that rung's reconstruction, its achieved
#: ``err_bound``, and the transport error recorded — a degraded answer,
#: not a poisoned session (children may still refine from it)
PARTIAL = "partial"


def _retryable(exc: BaseException) -> bool:
    """Transient transport failures are worth re-planning in a later
    tick: every :class:`OSError` (timeouts, resets, ``RemoteReadError``)
    except a decisive :class:`RemoteProtocolError`.  Anything else —
    ``CorruptArchiveError``, planner rejections — is permanent: the same
    plan would fail the same way."""
    return isinstance(exc, OSError) and not isinstance(exc, RemoteProtocolError)


@dataclass
class ServeRequest:
    """One queued retrieval: target fidelity against a registered archive.

    The server fills in lifecycle fields as the request moves
    ``queued -> done | failed``; ``result`` is the reconstruction,
    ``bytes_read`` / ``err_bound`` the session accounting, ``latency_s``
    wall time from submit to completion.  ``refine_of`` chains onto a
    finished request's progressive state: the child branches a private
    copy of it (forked reader accounting included) and fetches only the
    planes its tighter fidelity adds (Algorithm 2, across requests);
    sibling refinements of one parent are fully independent sessions.

    Transient transport errors re-queue the request for a later tick up
    to its retry budget (``retry_budget``, defaulting to the server's);
    an exhausted budget settles the request ``partial`` at its last
    fully decoded rung — result, achieved ``err_bound``, and the
    transport error all recorded — or ``failed`` if no rung ever
    completed.
    """
    req_id: int
    archive_id: str
    fidelity: Fidelity
    propagation: str = loader.SAFE
    refine_of: Optional["ServeRequest"] = None
    status: str = QUEUED
    result: Optional[np.ndarray] = None
    error: Optional[str] = None
    bytes_read: int = 0
    err_bound: float = float("inf")
    submitted_s: float = field(default_factory=time.perf_counter)
    latency_s: float = 0.0
    retries: int = 0                  # transport retries consumed so far
    retry_budget: Optional[int] = None  # None -> the server's default
    # session internals (reader + progressive state), server-managed
    _reader: object = None
    _state: object = None
    _ladder_t: object = None          # v3: this tick's planned prefix length


@dataclass
class _Job:
    """One chunk decode unit: the coalescer's currency."""
    req: ServeRequest
    chunk_idx: Optional[int]          # None = v1 archive (single slab)
    sub_reader: object
    prior_state: Optional[RetrievalState]
    keep_planes: List[int]
    new_state: Optional[RetrievalState] = None


def _shape_sig(meta) -> tuple:
    """Batch-compatibility signature: jobs with equal signatures may share
    one stacked kernel launch (same contract as ``encode.shape_groups``
    plus the level/anchor structure ``*_batch`` helpers assume)."""
    return (tuple(meta.shape), meta.interp, meta.work_dtype,
            tuple(lv.n for lv in meta.levels),
            tuple(meta.anchors_shape))


class RetrievalServer:
    """Continuous-batching server over a registry of progressive archives.

    ``policy``
        :class:`ExecPolicy` executing every tick (default
        ``spec.DEFAULT_POLICY``); like sessions, the policy never changes
        reconstruction bits — only dispatch counts and speed.
    ``cache``
        A shared :class:`PlaneCache` (None disables prefix reuse).
    ``coalesce``
        True (default) groups decode jobs across requests; False keeps
        each request's jobs in their own groups — the per-request
        baseline the benchmark compares dispatch counts against.
    ``propagation``
        Default error-propagation model for requests that don't pick one.

    Dispatch accounting lives in :attr:`counters`
    (``decode_level`` / ``reconstruct`` / ``dedup_reuse`` primitive
    invocations, backend-independent — see ``pipeline.state``).
    """

    def __init__(self, policy: Optional[ExecPolicy] = None,
                 cache: Optional[PlaneCache] = None, coalesce: bool = True,
                 propagation: str = loader.SAFE, retry_budget: int = 2):
        self.policy = policy if policy is not None else spec.DEFAULT_POLICY
        self.cache = cache
        self.coalesce = coalesce
        self.propagation = propagation
        #: default transport retries per request (re-queue + re-plan in a
        #: later tick) before a request degrades to ``partial``/``failed``
        self.retry_budget = int(retry_budget)
        self.counters: Dict[str, int] = {}
        self.ticks = 0
        self._archives: Dict[str, Archive] = {}
        self._queue: List[ServeRequest] = []
        self._next_id = 0
        self._done = 0
        self._failed = 0
        self._partial = 0
        self._retries = 0               # lifetime re-queues
        self._tick_retries = 0          # re-queues in the latest tick

    # ---- registry / queue

    def add_archive(self, archive_id: str, archive: Archive) -> None:
        """Register ``archive`` under ``archive_id``.

        The id becomes the plane-cache scope for every session the server
        opens on it, so it must be stable: rebinding an id to *different*
        bytes would poison cache keys and is rejected (idempotent
        re-registration of equal bytes is fine).
        """
        prev = self._archives.get(archive_id)
        if prev is not None and prev != archive:
            raise ValueError(
                f"archive_id {archive_id!r} is already bound to different "
                "bytes; cache scopes require a stable id -> bytes mapping")
        self._archives[archive_id] = archive

    def submit(self, archive_id: str, fidelity: Optional[Fidelity] = None,
               propagation: Optional[str] = None,
               refine_of: Optional[ServeRequest] = None,
               retry_budget: Optional[int] = None) -> ServeRequest:
        """Enqueue a retrieval; returns the live :class:`ServeRequest`.

        ``refine_of`` chains onto an earlier request for the same
        archive: once the parent has settled with a result (DONE, or
        PARTIAL after degradation), the child branches a private copy of
        its progressive state and fetches only the additional planes.
        ``retry_budget`` overrides the server's default transport-retry
        allowance for this request alone.
        """
        if archive_id not in self._archives:
            raise KeyError(f"unknown archive_id {archive_id!r}; "
                           "add_archive() it first")
        if refine_of is not None and refine_of.archive_id != archive_id:
            raise ValueError(
                f"refine_of targets archive {refine_of.archive_id!r}, "
                f"not {archive_id!r}")
        req = ServeRequest(
            req_id=self._next_id, archive_id=archive_id,
            fidelity=fidelity if fidelity is not None else Fidelity.full(),
            propagation=propagation if propagation is not None
            else self.propagation,
            refine_of=refine_of, retry_budget=retry_budget)
        self._next_id += 1
        self._queue.append(req)
        return req

    @property
    def pending(self) -> int:
        return len(self._queue)

    # ---- scheduling

    def _runnable(self) -> Tuple[List[ServeRequest], List[ServeRequest]]:
        """Dequeue requests whose refine parent (if any) has settled.

        A PARTIAL parent is chainable: it settled with a complete (if
        coarser) progressive state, so children branch from its achieved
        rung — degradation never poisons the chain.  Returns ``(ready,
        failed)``: runnable requests, plus the children of FAILED
        parents — failed immediately here, and returned so ``run_tick``
        reports them as settled this tick."""
        ready, still, failed = [], [], []
        for req in self._queue:
            parent = req.refine_of
            if parent is None or parent.status in (DONE, PARTIAL):
                ready.append(req)
            elif parent.status == FAILED:
                self._fail(req, f"refine parent request {parent.req_id} "
                           f"failed: {parent.error}")
                failed.append(req)
            else:
                still.append(req)
        self._queue = still
        return ready, failed

    def _fail(self, req: ServeRequest, error: str) -> None:
        req.status = FAILED
        req.error = error
        req.latency_s = time.perf_counter() - req.submitted_s
        self._failed += 1

    def _budget(self, req: ServeRequest) -> int:
        return self.retry_budget if req.retry_budget is None \
            else req.retry_budget

    def _settle_partial(self, req: ServeRequest, error: str) -> bool:
        """Settle ``req`` at its last fully decoded rung, if one exists.

        The committed progressive state (``req._state``) only ever holds
        rungs whose every chunk assembled — failed reads raise before any
        state is merged — so if it is complete, its reconstruction is a
        bit-exact coarser answer.  Returns False when nothing was ever
        achieved (the caller then fails the request outright)."""
        st = req._state
        if st is None or req._reader is None:
            return False
        m = req._reader.meta
        if isinstance(st, ChunkedRetrievalState):
            if any(cs is None for cs in st.chunk_states):
                return False
            out = np.empty(m.shape, np.dtype(m.dtype))
            for i, cm in enumerate(m.chunks):
                out[cm.start:cm.stop] = \
                    st.chunk_states[i].xhat.astype(out.dtype)
            req.result = out
        elif getattr(st, "xhat", None) is not None:
            req.result = st.xhat.astype(np.dtype(m.dtype))
        else:
            return False
        req.err_bound = st.err_bound
        req.bytes_read = req._reader.bytes_read
        req.status = PARTIAL
        req.error = error
        req.latency_s = time.perf_counter() - req.submitted_s
        self._partial += 1
        return True

    def _resolve_failure(self, req: ServeRequest, exc: BaseException,
                         settled: List[ServeRequest]) -> None:
        """Route one request's tick failure: re-queue (transient error,
        budget left), degrade to PARTIAL (budget exhausted, a rung
        achieved), or FAIL (permanent error / nothing achieved)."""
        msg = f"{type(exc).__name__}: {exc}"
        if _retryable(exc):
            if req.retries < self._budget(req):
                req.retries += 1
                req.status = QUEUED
                req._ladder_t = None
                self._retries += 1
                self._tick_retries += 1
                self._queue.append(req)
                return
            if self._settle_partial(
                    req, f"retry budget exhausted "
                    f"({req.retries} retries): {msg}"):
                settled.append(req)
                return
            msg = f"retry budget exhausted ({req.retries} retries): {msg}"
        self._fail(req, msg)
        settled.append(req)

    def _plan_jobs(self, req: ServeRequest) -> List[_Job]:
        """Open/reuse the request's session and plan its chunk jobs.

        Planner errors (infeasible byte targets, bounds below eb) raise —
        the tick isolates them to this request.
        """
        archive = self._archives[req.archive_id]
        if req._reader is None:
            if req.refine_of is not None:
                # branch a PRIVATE session off the parent: siblings that
                # refine the same parent in the same tick must not alias
                # one mutable state/reader, or the later sibling's delta
                # would be computed against the earlier sibling's planes
                # (breaking per-request bit parity with private sessions)
                req._state = fork_state(req.refine_of._state)
                req._reader = req._state.reader
            else:
                req._reader = archive.new_reader(cache_scope=req.archive_id)
        reader, state = req._reader, req._state
        prop = req.propagation
        if not archive.chunked:
            keep = decode.plan_retrieval(reader.meta, req.fidelity,
                                         prop).keep_planes
            return [_Job(req, None, reader, state, keep)]
        if isinstance(reader, V3ArchiveReader):
            # plane-major: one ladder plan for the whole grid, ONE
            # contiguous range staged up front — the per-chunk jobs then
            # decode from the staged prefix, so coalesced ticks keep the
            # v3 monotone-contiguous read pattern (the server is the
            # range-request client the layout was designed for)
            if state is None:
                state = req._state = ChunkedRetrievalState(
                    reader=reader,
                    chunk_states=[None] * len(reader.meta.chunks))
            t = decode.plan_ladder(reader.meta, req.fidelity, prop,
                                   t_min=state.ladder_pos)
            reader.ensure_prefix(t)
            keeps = reader.meta.ladder_keeps(t)
            req._ladder_t = t
            return [_Job(req, i, reader.chunk_reader(i),
                         state.chunk_states[i], keeps[i])
                    for i in range(len(reader.meta.chunks))]
        budgets = decode.chunk_budgets(reader, req.fidelity, state)
        if state is None:
            state = req._state = ChunkedRetrievalState(
                reader=reader,
                chunk_states=[None] * len(reader.meta.chunks))
        jobs = []
        for i in range(len(reader.meta.chunks)):
            sub = reader.chunk_reader(i)
            keep = decode.plan_retrieval(
                sub.meta, decode.sub_fidelity(req.fidelity, budgets, i),
                prop).keep_planes
            jobs.append(_Job(req, i, sub, state.chunk_states[i], keep))
        return jobs

    def run_tick(self) -> List[ServeRequest]:
        """One scheduler tick: plan every runnable request, coalesce the
        chunk jobs into shape groups, execute each group as one batched
        launch sequence, assemble per-request results.  Returns the
        requests that settled (DONE or FAILED) this tick.
        """
        self.ticks += 1
        self._tick_retries = 0
        ready, settled = self._runnable()
        groups: Dict[tuple, List[_Job]] = {}
        by_req: Dict[int, List[_Job]] = {}
        for req in ready:
            req.status = RUNNING
            try:
                jobs = self._plan_jobs(req)
            except Exception as e:
                # planner rejection or a transport error while staging
                # the ladder prefix: isolate to this request — retry,
                # degrade, or fail per _resolve_failure
                self._resolve_failure(req, e, settled)
                continue
            by_req[req.req_id] = jobs
            for job in jobs:
                # v1 slabs never group with v2 chunks: they bind the
                # policy differently (no chunk grid to place on a mesh)
                sig = (job.chunk_idx is not None,) \
                    + _shape_sig(job.sub_reader.meta) + (req.propagation,)
                if not self.coalesce:
                    sig = sig + (req.req_id,)
                groups.setdefault(sig, []).append(job)
        # one bound context per archive kind, mirroring read_archive: v1
        # jobs run under chunked=False (an explicit mesh is rejected there
        # exactly as it is for sessions — isolated to the v1 requests)
        ctxs: Dict[bool, object] = {}
        for sig, jobs in groups.items():
            chunked, prop = sig[0], jobs[0].req.propagation
            try:
                if chunked not in ctxs:
                    ctxs[chunked] = self.policy.bind(chunked=chunked,
                                                     encode=False)
            except Exception as e:
                for job in jobs:
                    if job.req.status == RUNNING:
                        self._fail(job.req, f"{type(e).__name__}: {e}")
                        settled.append(job.req)
                continue
            ctx = ctxs[chunked]
            cap = group_cap(ctx.mesh)
            for lo in range(0, len(jobs), cap):
                # a request resolved by an earlier failing slice drops
                # out of later slices: its jobs will be re-planned (or
                # never run) — decoding them now would waste the launch
                part = [j for j in jobs[lo:lo + cap]
                        if j.req.status == RUNNING]
                if not part:
                    continue
                try:
                    # requests sharing a group share a propagation (in sig)
                    sts = decode.decode_group(
                        [j.sub_reader for j in part],
                        [j.prior_state for j in part],
                        [j.keep_planes for j in part],
                        ctx, prop, cache=self.cache, counters=self.counters)
                except Exception as e:
                    # a mid-group fetch failure aborts the whole slice:
                    # every owning request resolves (retry/degrade/fail)
                    # — committed states are untouched, since failed
                    # reads raise before any accounting or state merge
                    for r in {j.req.req_id: j.req for j in part}.values():
                        if r.status == RUNNING:
                            self._resolve_failure(r, e, settled)
                    continue
                for job, st in zip(part, sts):
                    job.new_state = st
        for req in ready:
            if req.status != RUNNING:
                continue
            self._assemble(req, by_req[req.req_id])
            settled.append(req)
        return settled

    def _assemble(self, req: ServeRequest, jobs: List[_Job]) -> None:
        """Merge a request's finished chunk states into its result and
        session accounting (mirrors ``decode._retrieve_chunked``'s
        epilogue)."""
        reader = req._reader
        m = reader.meta
        if jobs[0].chunk_idx is None:
            st = jobs[0].new_state
            req._state = st
            req.result = st.xhat.astype(np.dtype(m.dtype))
            req.err_bound = st.err_bound
            req.bytes_read = reader.bytes_read
        else:
            state: ChunkedRetrievalState = req._state
            for job in jobs:
                state.chunk_states[job.chunk_idx] = job.new_state
            out = np.empty(m.shape, np.dtype(m.dtype))
            for i, cm in enumerate(m.chunks):
                out[cm.start:cm.stop] = \
                    state.chunk_states[i].xhat.astype(np.dtype(m.dtype))
            state.err_bound = max(cs.err_bound
                                  for cs in state.chunk_states)
            state.bytes_read = reader.bytes_read
            if req._ladder_t is not None:   # v3: record the held prefix
                state.ladder_pos = max(state.ladder_pos, req._ladder_t)
                req._ladder_t = None
            req.result = out
            req.err_bound = state.err_bound
            req.bytes_read = state.bytes_read
        req.status = DONE
        req.latency_s = time.perf_counter() - req.submitted_s
        self._done += 1

    def drain(self, max_ticks: int = 1000) -> List[ServeRequest]:
        """Run ticks until the queue is empty; returns every request that
        settled.  ``max_ticks`` guards against a stuck dependency chain
        (a child whose parent never settles)."""
        settled: List[ServeRequest] = []
        while self._queue:
            if self.ticks >= max_ticks:
                raise RuntimeError(
                    f"drain exceeded {max_ticks} ticks with "
                    f"{len(self._queue)} requests still queued")
            progressed = self.run_tick()
            # a tick that only re-queued transport retries is progress
            # (the budget bounds it); zero settlements AND zero retries
            # with a non-empty queue is a real dependency deadlock
            if not progressed and not self._tick_retries and self._queue:
                raise RuntimeError(
                    "scheduler stalled: queued requests have unsatisfied "
                    "refine dependencies")
            settled.extend(progressed)
        return settled

    # ---- introspection

    def stats(self) -> dict:
        """Lifetime accounting snapshot (JSON-serializable)."""
        out = {
            "ticks": self.ticks,
            "pending": len(self._queue),
            "done": self._done,
            "failed": self._failed,
            "partial": self._partial,
            "retries": self._retries,
            "retry_budget": self.retry_budget,
            "coalesce": self.coalesce,
            "counters": dict(self.counters),
            "archives": len(self._archives),
        }
        if self.cache is not None:
            out["cache"] = self.cache.stats()
        return out

    def __repr__(self) -> str:
        return (f"RetrievalServer({len(self._archives)} archives, "
                f"{len(self._queue)} queued, {self._done} done, "
                f"{self._partial} partial, {self._failed} failed, "
                f"coalesce={self.coalesce})")
