"""Progressive retrieval state + Algorithm 2's delta-cascade logic.

A :class:`RetrievalState` carries everything a later ``retrieve``/``refine``
call needs to load *only* the missing bitplanes and push a linear delta on
top of the previous reconstruction instead of decoding from scratch:

  * ``planes_loaded`` / ``nb_partial`` — per level, how many MSB-first
    planes are in and the truncated negabinary stream they decode to
    (backend-agnostic: uint32 words, whichever backend produced them);
  * ``esc_idx`` — escape stream positions, whose deltas are pinned to zero
    (escaped points are exact from the very first pass);
  * ``xhat`` — the current reconstruction the next delta lands on.

The update (:func:`load_level_deltas` + :func:`push_delta`) follows the
archive's arithmetic contract (``core.arith``):

  * float64 archives run the paper's Algorithm 2 cascade: residual
    *differences* are reconstructed through the same interpolation sweep
    with zero anchors — valid because the sweep is linear in (anchors,
    residuals) — and added to ``xhat``;
  * float32 archives keep the Algorithm 2 delta in the integer domain —
    the loaded planes only ever extend each level's truncated bins — and
    re-sweep from the anchors with the current residuals.  A float32
    delta cascade would accumulate rounding with every refinement; the
    re-sweep makes every rung path-independent, and the full read
    bit-identical to the reconstruction the encoder verified against
    ``eb`` (``arith.screen``).

Both steps take the resolved :class:`~.backends.CodecBackend`, so
refinement runs on the Pallas kernels exactly like a cold retrieval.

:class:`ChunkedRetrievalState` is the v2-archive twin: one per-chunk state
plus aggregated accounting.

Two optional cross-cutting hooks thread through every helper (both are
``None`` by default and cost nothing when absent):

``cache``
    A shared *plane cache* (``repro.serving.PlaneCache`` protocol:
    ``get(key) -> array | None`` / ``put(key, array)`` /
    ``saved_fetch(nbytes)``) keyed ``(reader.cache_scope, level, prefix)``.
    Decoded truncated-negabinary prefixes are deterministic functions of
    the archive bytes, so concurrent sessions at different fidelities can
    reuse each other's decodes: a hit skips both the plane-blob fetches
    and the unpack kernel, never changing reconstruction bits (a session's
    ``bytes_read`` may shrink — that is the serving win, see
    ``docs/architecture.md`` §8).  Readers opt in by carrying a non-None
    ``cache_scope`` (see ``container.ArchiveReader``).

``counters``
    A plain dict accumulating backend-primitive invocation counts
    (``decode_level`` / ``reconstruct`` / ``dedup_reuse``), one unit per
    primitive call whether scalar, batched, or sharded — the
    serving tier's dispatch accounting, backend-independent (the kernel
    layer's ``kernels.dispatch`` only counts Pallas launches).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

from .. import arith, bitplane, loader, negabinary
from ..container import ArchiveReader, ChunkedArchiveReader
from .backends import CodecBackend
from .spec import ExecContext


@dataclass
class RetrievalState:
    """Progressive state carried between retrievals (Algorithm 2)."""
    reader: ArchiveReader
    planes_loaded: List[int]              # per level, MSB-first count
    nb_partial: List[np.ndarray]          # truncated negabinary per level
    esc_idx: List[np.ndarray]             # escape stream positions per level
    xhat: np.ndarray                      # current reconstruction
    err_bound: float
    bytes_read: int = 0
    # float32 archives re-sweep from scratch (module docstring): the exact
    # anchors, per-level escape values and current float32 residuals
    anchors: Optional[np.ndarray] = None
    esc_val: Optional[List[np.ndarray]] = None
    res: Optional[List[np.ndarray]] = None


@dataclass
class ChunkedRetrievalState:
    """Progressive state for a chunked (v2 or v3) archive: one
    RetrievalState per chunk.  ``ladder_pos`` only moves on v3: the
    ladder-prefix length already held, so refinement plans start there
    (the v3 twin of per-level ``planes_loaded`` floors)."""
    reader: ChunkedArchiveReader
    chunk_states: List[Optional[RetrievalState]]
    err_bound: float = float("inf")
    bytes_read: int = 0
    ladder_pos: int = 0


def fork_state(state):
    """Branch an independent progressive session off ``state``.

    Returns a new :class:`RetrievalState` / :class:`ChunkedRetrievalState`
    carrying the same loaded planes, reconstruction, and cumulative byte
    accounting, backed by *forked* readers
    (:meth:`~..container.ArchiveReader.fork`) — so several refinements can
    branch off one finished session concurrently, each fetching only the
    planes its own target adds, without sharing a mutable state or
    ledger.  Cheap: ``nb_partial`` streams are immutable-by-contract
    (replaced, never written in place) and ``xhat``/``res`` entries are
    only ever reassigned, so the arrays themselves are shared.
    """
    if isinstance(state, ChunkedRetrievalState):
        reader = state.reader.fork()
        chunk_states = [
            None if cs is None else RetrievalState(
                reader=reader.chunk_reader(i),
                planes_loaded=list(cs.planes_loaded),
                nb_partial=list(cs.nb_partial),
                esc_idx=list(cs.esc_idx),
                xhat=cs.xhat, err_bound=cs.err_bound,
                bytes_read=cs.bytes_read, anchors=cs.anchors,
                esc_val=cs.esc_val,
                res=None if cs.res is None else list(cs.res))
            for i, cs in enumerate(state.chunk_states)]
        return ChunkedRetrievalState(reader=reader,
                                     chunk_states=chunk_states,
                                     err_bound=state.err_bound,
                                     bytes_read=state.bytes_read,
                                     ladder_pos=state.ladder_pos)
    reader = state.reader.fork()
    return RetrievalState(reader=reader,
                          planes_loaded=list(state.planes_loaded),
                          nb_partial=list(state.nb_partial),
                          esc_idx=list(state.esc_idx),
                          xhat=state.xhat, err_bound=state.err_bound,
                          bytes_read=state.bytes_read, anchors=state.anchors,
                          esc_val=state.esc_val,
                          res=None if state.res is None else list(state.res))


def _count(counters, name: str, k: int = 1) -> None:
    """Accumulate a backend-primitive invocation into ``counters`` (no-op
    when the caller did not ask for accounting)."""
    if counters is not None:
        counters[name] = counters.get(name, 0) + k


def _cache_key(reader, level_idx: int, prefix: int):
    """Plane-cache key for a decoded prefix, or None when the reader is
    not cache-scoped."""
    scope = getattr(reader, "cache_scope", None)
    if scope is None:
        return None
    return (scope, level_idx, prefix)


def _freeze(arr: np.ndarray) -> np.ndarray:
    """Mark a decoded stream immutable before it is shared across
    sessions (cache entries / dedup fan-out).  ``nb_partial`` streams are
    only ever *replaced*, never written in place, so sharing is safe."""
    try:
        arr.flags.writeable = False
    except ValueError:
        pass  # views of external buffers may already be locked
    return arr


def _unpack_escapes(blob: bytes) -> Tuple[np.ndarray, np.ndarray]:
    """Inverse of ``encode._pack_escapes``: blob -> (flat idx, exact values).

    Routed through :func:`~..bitplane.inflate` so pre-inflated
    (:class:`~..bitplane.Raw`) payloads from cache layers skip zlib."""
    if not blob:
        return np.zeros(0, np.int64), np.zeros(0, np.float64)
    raw = bitplane.inflate(blob)
    n = int(np.frombuffer(raw[:8], np.int64)[0])
    idx = np.frombuffer(raw[8:8 + 8 * n], np.int64)
    val = np.frombuffer(raw[8 + 8 * n:], np.float64)
    return idx, val


_INFLATE_POOL = None


def _inflate_pool():
    """Lazy singleton worker for the two-slot inflate prefetch: while the
    device decodes level k, the NEXT level's zlib inflate (pure host work)
    runs here, so the serial host stage hides behind the kernel sweep.
    One worker is enough — there is exactly one level in flight ahead."""
    global _INFLATE_POOL
    if _INFLATE_POOL is None:
        from concurrent.futures import ThreadPoolExecutor
        _INFLATE_POOL = ThreadPoolExecutor(max_workers=1,
                                           thread_name_prefix="ipcomp-inflate")
    return _INFLATE_POOL


def _coarsest_bound(m) -> float:
    """Guaranteed bound with no planes loaded (SAFE propagation)."""
    errs, _ = loader._level_cost_tables(m, loader.SAFE)
    return loader.plan_bound(m, [0] * len(m.levels), errs, loader.SAFE)


def _new_state(reader, anchors, overrides, xhat) -> RetrievalState:
    """State after the coarsest pass (anchors + escapes, zero planes)."""
    m = reader.meta
    f32 = m.work_dtype == np.float32
    return RetrievalState(
        reader=reader, planes_loaded=[0] * len(m.levels),
        nb_partial=[np.zeros(lv.n, np.uint32) for lv in m.levels],
        esc_idx=[o[0] for o in overrides], xhat=xhat,
        err_bound=_coarsest_bound(m), bytes_read=reader.bytes_read,
        anchors=anchors if f32 else None,
        esc_val=[o[1] for o in overrides] if f32 else None,
        res=[np.zeros(lv.n, np.float32) for lv in m.levels] if f32
        else None)


def initial_state(reader: ArchiveReader, bk: CodecBackend,
                  counters=None) -> RetrievalState:
    """Coarsest approximation: anchors + escapes only, zero bitplanes."""
    m = reader.meta
    dt = m.work_dtype
    yhat = [np.zeros(lv.n, dt) for lv in m.levels]
    anchors = reader.anchors()
    overrides = [_unpack_escapes(reader.escapes(li))
                 for li in range(len(m.levels))]
    xhat = bk.reconstruct(m.shape, m.interp, anchors, yhat,
                          overrides=overrides, out_dtype=dt, dtype=dt)
    _count(counters, "reconstruct")
    return _new_state(reader, anchors, overrides, xhat)


def _level_update(m, nb_new: np.ndarray, nb_old: np.ndarray, dy):
    """A level's contribution in the archive's arithmetic: the float64
    Algorithm 2 delta against ``nb_old``, or the float32 residual of
    ``nb_new``.  ``dy`` is the fused kernel's result (same bits), or None
    to compute it on the host."""
    if dy is not None:
        return dy
    if m.work_dtype == np.float32:
        return arith.dequantize(negabinary.from_negabinary(nb_new),
                                arith.consts(m.eb, np.float32))
    dq = negabinary.from_negabinary(nb_new) - \
        negabinary.from_negabinary(nb_old)
    return dq.astype(np.float64) * 2.0 * m.eb


def _apply_level(state: RetrievalState, li: int, dy, delta_y) -> None:
    """File a level's contribution: float32 sessions replace the level's
    residual, float64 sessions queue the delta for the cascade."""
    if state.res is not None:
        state.res[li] = dy
    else:
        delta_y[li] = dy


def load_level_deltas(state: RetrievalState, keep_planes: List[int],
                      bk: CodecBackend, cache=None,
                      counters=None) -> Tuple[List[np.ndarray], bool]:
    """Fetch + decode the planes the plan adds; return residual deltas.

    Per level: refinement never drops planes, so the target is
    ``max(have, plan)``.  XOR decode needs planes k+1, k+2, so the prefix is
    re-decoded from the already-fetched blobs (the reader caches fetched
    ranges; re-reads of the same tag are not double-counted).  The returned
    stream is the *difference* of dequantized residuals — the input of the
    zero-anchor cascade in :func:`push_delta`; float32 sessions instead
    update ``state.res`` in place (their deltas stay zero).

    With a ``cache`` and a cache-scoped reader, the decoded prefix is
    looked up under ``(scope, level, prefix)`` first: a hit skips the
    plane fetches *and* the decode (crediting the avoided fetch bytes to
    the cache accounting); a miss decodes as usual and publishes the
    result for other sessions.

    Backends shipping the fused decode slots get two upgrades here: each
    level's unpack + dequantize runs as ONE ``decode_level_fused`` launch
    (no host negabinary passes), and the next level's zlib inflate
    (``inflate_level``) is prefetched on a worker thread while the current
    level's kernel runs.  Bits are unchanged either way — the fused
    arithmetic is pinned identical to the host spelling by the parity
    suite.
    """
    m = state.reader.meta
    L = len(m.levels)
    dt = m.work_dtype
    delta_y: List[np.ndarray] = [np.zeros(lv.n, dt) for lv in m.levels]
    any_new = False
    fused = bk.decode_level_fused is not None
    djobs: List[Tuple[int, object, int, object, list]] = []
    for li, lv in enumerate(m.levels):
        have = state.planes_loaded[li]
        want = max(have, keep_planes[li])
        if want <= have:
            continue
        any_new = True
        key = _cache_key(state.reader, li, want) \
            if cache is not None else None
        nb_new = cache.get(key) if key is not None else None
        if nb_new is not None:
            cache.saved_fetch(sum(
                lv.plane_sizes[i] for i in range(want)
                if not state.reader.plane_fetched(li, i)))
            _apply_level(state, li, _level_update(
                m, nb_new, state.nb_partial[li], None), delta_y)
            state.nb_partial[li] = nb_new
            state.planes_loaded[li] = want
            continue
        blobs: List[Optional[bytes]] = [None] * lv.nbits
        for i in range(want):
            blobs[i] = state.reader.plane(li, i)
        djobs.append((li, lv, want, key, blobs))
    prefetch = fused and bk.inflate_level is not None and len(djobs) > 1
    fut = None
    for k, (li, lv, want, key, blobs) in enumerate(djobs):
        words = None
        if prefetch:
            words = fut.result() if fut is not None \
                else bk.inflate_level(blobs, lv.nbits, lv.n)
            if k + 1 < len(djobs):
                nli, nlv, _nw, _nk, nblobs = djobs[k + 1]
                fut = _inflate_pool().submit(bk.inflate_level, nblobs,
                                             nlv.nbits, nlv.n)
            else:
                fut = None
        dy = None
        if fused:
            nb_new, dy = bk.decode_level_fused(blobs, lv.nbits, lv.n,
                                               state.nb_partial[li], m.eb,
                                               words=words, dtype=dt)
        else:
            nb_new = bk.decode_level(blobs, lv.nbits, lv.n)
        _count(counters, "decode_level")
        nb_new = np.asarray(nb_new)
        if key is not None:
            cache.put(key, _freeze(nb_new))
        _apply_level(state, li, _level_update(m, nb_new,
                                              state.nb_partial[li], dy),
                     delta_y)
        state.nb_partial[li] = nb_new
        state.planes_loaded[li] = want
    return delta_y, any_new


def push_delta(state: RetrievalState, delta_y: List[np.ndarray],
               bk: CodecBackend, counters=None) -> None:
    """Apply a load step to ``xhat``.  Float64: Algorithm 2 core —
    reconstruct the residual deltas through the sweep with zero anchors
    (linearity) and add onto the previous ``xhat``; escaped points are
    exact from the first pass, so their delta is pinned 0.  Float32:
    re-sweep from the anchors with the current residuals and the exact
    escape values."""
    m = state.reader.meta
    dt = m.work_dtype
    if state.res is not None:
        state.xhat = bk.reconstruct(
            m.shape, m.interp, state.anchors, state.res,
            overrides=list(zip(state.esc_idx, state.esc_val)),
            out_dtype=dt, dtype=dt)
        _count(counters, "reconstruct")
        return
    zero_anchors = np.zeros(m.anchors_shape, np.float64)
    zero_ovr = [(idx, np.zeros(idx.size)) for idx in state.esc_idx]
    delta = bk.reconstruct(m.shape, m.interp, zero_anchors, delta_y,
                           overrides=zero_ovr)
    _count(counters, "reconstruct")
    state.xhat = state.xhat + delta


def update_achieved_bound(state: RetrievalState, propagation: str) -> None:
    """Recompute the guaranteed bound from the *union* of loaded planes."""
    m = state.reader.meta
    errs, _ = loader._level_cost_tables(m, propagation)
    state.err_bound = loader.plan_bound(m, state.planes_loaded, errs,
                                        propagation)
    state.bytes_read = state.reader.bytes_read


# ------------------------------------------------- batched (chunk groups)
#
# The three steps above, over a GROUP of equal-shape chunks at once: the
# scheduler in ``decode._retrieve_group`` stacks the per-chunk inputs and
# the backend's ``*_batch`` primitives run one kernel dispatch per phase /
# per (level, prefix) group instead of one per chunk.  Everything that is
# per-chunk accounting — reader fetches, planes_loaded, nb_partial,
# err_bound — is still computed per chunk, so the resulting states are
# indistinguishable from the per-chunk loop (bit-identical xhat included;
# the batch axis is an execution detail).  Backends without batched slots
# fall back to the scalar loop transparently.
#
# Each helper takes the call's resolved :class:`~.spec.ExecContext` —
# backend + optional 1-D codec mesh: with a mesh, the same stack is run
# through the backend's ``*_sharded`` primitives, which split the group
# across the mesh devices (``parallel.codec_mesh``).  Shard-local results
# come back as ordinary per-chunk streams, so the merge into per-chunk
# ``RetrievalState``s — and from there into ``ChunkedRetrievalState``'s
# aggregated ``bytes_read``/``err_bound`` — is byte-for-byte the
# single-device merge; nothing in the state records which policy (if any)
# produced it, which is what lets a sharded retrieval be refined
# unsharded and vice versa.

def _stack_reconstruct(ctx: ExecContext, shape, interp, anchors, yhat,
                       overrides, dtype):
    """Group reconstruct through the sharded slot when a mesh is active,
    the batched slot otherwise (callers have already ruled out B == 1)."""
    bk = ctx.bk
    if ctx.mesh is not None and bk.reconstruct_sharded is not None:
        return bk.reconstruct_sharded(shape, interp, anchors, yhat,
                                      ctx.mesh, overrides=overrides,
                                      out_dtype=dtype, dtype=dtype)
    return bk.reconstruct_batch(shape, interp, anchors, yhat,
                                overrides=overrides, out_dtype=dtype,
                                dtype=dtype)


def initial_state_batch(readers: List[ArchiveReader],
                        ctx: ExecContext,
                        counters=None) -> List[RetrievalState]:
    """Coarsest approximation for B equal-shape chunks: one batched
    (optionally mesh-sharded) reconstruct builds every initial ``xhat``."""
    bk = ctx.bk
    if ((bk.reconstruct_batch is None and bk.reconstruct_sharded is None)
            or len(readers) == 1):
        return [initial_state(r, bk, counters=counters) for r in readers]
    m0 = readers[0].meta
    dt = m0.work_dtype
    anchors = [r.anchors() for r in readers]
    yhat = [np.zeros((len(readers), lv.n), dt) for lv in m0.levels]
    overrides = [[_unpack_escapes(r.escapes(li))
                  for li in range(len(r.meta.levels))] for r in readers]
    xhat = _stack_reconstruct(ctx, m0.shape, m0.interp, np.stack(anchors),
                              yhat, overrides, dt)
    _count(counters, "reconstruct")
    return [_new_state(r, anchors[b], overrides[b], xhat[b])
            for b, r in enumerate(readers)]


def load_level_deltas_batch(states: List[RetrievalState],
                            keep_planes_list: List[List[int]],
                            ctx: ExecContext, cache=None, counters=None,
                            ) -> Tuple[List[List[np.ndarray]], List[bool]]:
    """Batched :func:`load_level_deltas` over B equal-shape chunk states.

    Plane fetches stay per chunk (each chunk's reader counts its own
    bytes), but the decode itself is grouped and each group runs as one
    batched dispatch (mesh-sharded across devices when the context
    carries a mesh).  The group key depends on the backend: with
    ``dynamic_low_zero`` the loaded-prefix length is a *runtime* operand,
    so jobs group by ``(nbits,)`` alone and chunks at different fidelities
    share one launch; legacy backends group by ``(nbits, prefix)``.
    Backends with the fused slots run each group as one
    ``decode_level_fused_batch`` megakernel launch (per-chunk ``nb_old``
    and ``eb`` ride along as runtime operands), and the next group's zlib
    inflate is prefetched on a worker thread while the current group's
    kernel runs.  Returns per-chunk delta streams and per-chunk any-new
    flags, exactly like B scalar calls.

    Cross-session serving hooks: with a ``cache``, each job first probes
    the shared plane cache (a hit skips the fetch and leaves the batch);
    and jobs from *different sessions over the same archive bytes* (equal
    ``cache_scope``) wanting the same prefix are deduplicated — one leader
    decodes, followers share the immutable result (``dedup_reuse`` in
    ``counters``).  Followers and cache hits host-compute their own delta
    (their ``nb_old`` differs from the leader's), so the fused fast path
    never changes what they see.  Chunks within one session always have
    distinct scopes, so single-request behaviour is unchanged.
    """
    bk, mesh = ctx.bk, ctx.mesh
    m0 = states[0].reader.meta
    B = len(states)
    L = len(m0.levels)
    delta_ys: List[List[Optional[np.ndarray]]] = \
        [[None] * L for _ in range(B)]
    any_new = [False] * B
    dt = m0.work_dtype
    fused = bk.decode_level_fused_batch is not None
    jobs_per_level: List[List[Tuple[int, int]]] = [[] for _ in range(L)]
    resolved: dict = {}        # (level, chunk pos) -> (nb_new, delta|None)
    followers: dict = {}       # (level, leader pos) -> [follower pos]
    calls: list = []           # (level, nbits, [(chunk pos, want)], blobs)
    for li, lv0 in enumerate(m0.levels):
        jobs: List[Tuple[int, int]] = []     # (chunk pos, want)
        for b, st in enumerate(states):
            have = st.planes_loaded[li]
            want = max(have, keep_planes_list[b][li])
            if want > have:
                jobs.append((b, want))
            else:
                delta_ys[b][li] = np.zeros(lv0.n, dt)
        jobs_per_level[li] = jobs
        # resolve cache hits and dedupe same-(scope, prefix) decode jobs
        decode_jobs: List[Tuple[int, int]] = []
        leaders: dict = {}                   # cache key -> leader pos
        for b, want in jobs:
            key = _cache_key(states[b].reader, li, want)
            nb = cache.get(key) if (cache is not None and key is not None) \
                else None
            if nb is not None:
                lv = states[b].reader.meta.levels[li]
                cache.saved_fetch(sum(
                    lv.plane_sizes[i] for i in range(want)
                    if not states[b].reader.plane_fetched(li, i)))
                resolved[(li, b)] = (nb, None)
            elif key is not None and key in leaders:
                followers.setdefault((li, leaders[key]), []).append(b)
                _count(counters, "dedup_reuse")
            else:
                if key is not None:
                    leaders[key] = b
                decode_jobs.append((b, want))
        groups: dict = {}        # (nbits[, want]) -> [(chunk pos, want)]
        for b, want in decode_jobs:
            nbits = states[b].reader.meta.levels[li].nbits
            gk = (nbits,) if bk.dynamic_low_zero else (nbits, want)
            groups.setdefault(gk, []).append((b, want))
        for gk, grp in groups.items():
            blob_lists = []
            for b, want in grp:
                st = states[b]
                blobs: List[Optional[bytes]] = [None] * gk[0]
                for i in range(want):
                    blobs[i] = st.reader.plane(li, i)
                blob_lists.append(blobs)
            calls.append((li, gk[0], grp, blob_lists))

    # execute the collected group dispatches; with the fused slots, the
    # NEXT group's host inflate overlaps the current group's kernel
    prefetch = fused and bk.inflate_level_batch is not None and len(calls) > 1
    fut = None
    for k, (li, nbits, grp, blob_lists) in enumerate(calls):
        n = m0.levels[li].n
        words = None
        if prefetch:
            words = fut.result() if fut is not None \
                else bk.inflate_level_batch(blob_lists, nbits, n)
            if k + 1 < len(calls):
                nli, nnbits, _g, nbl = calls[k + 1]
                fut = _inflate_pool().submit(bk.inflate_level_batch, nbl,
                                             nnbits, m0.levels[nli].n)
            else:
                fut = None
        bs = [b for b, _ in grp]
        if fused:
            nb_olds = [states[b].nb_partial[li] for b in bs]
            ebs = [states[b].reader.meta.eb for b in bs]
            if (mesh is not None and bk.decode_level_fused_sharded is not None
                    and len(bs) > 1):
                outs = bk.decode_level_fused_sharded(blob_lists, nbits, n,
                                                     nb_olds, ebs, mesh,
                                                     words=words, dtype=dt)
            else:
                outs = bk.decode_level_fused_batch(blob_lists, nbits, n,
                                                   nb_olds, ebs, words=words,
                                                   dtype=dt)
            _count(counters, "decode_level")
        elif (mesh is not None and bk.decode_level_sharded is not None
                and len(bs) > 1):
            outs = [(nb, None) for nb in
                    bk.decode_level_sharded(blob_lists, nbits, n, mesh)]
            _count(counters, "decode_level")
        elif bk.decode_level_batch is not None and len(bs) > 1:
            outs = [(nb, None) for nb in
                    bk.decode_level_batch(blob_lists, nbits, n)]
            _count(counters, "decode_level")
        else:
            outs = [(bk.decode_level(bl, nbits, n), None)
                    for bl in blob_lists]
            _count(counters, "decode_level", len(bs))
        for (b, want), (nb_new, dy) in zip(grp, outs):
            nb_new = _freeze(np.asarray(nb_new))
            key = _cache_key(states[b].reader, li, want)
            if cache is not None and key is not None:
                cache.put(key, nb_new)
            resolved[(li, b)] = (nb_new, dy)
            for fb in followers.get((li, b), ()):
                resolved[(li, fb)] = (nb_new, None)

    for li in range(L):
        for b, want in jobs_per_level[li]:
            nb_new, dy = resolved[(li, b)]
            st = states[b]
            dy = _level_update(st.reader.meta, nb_new, st.nb_partial[li], dy)
            if st.res is not None:
                st.res[li] = dy
                dy = np.zeros(dy.size, dt)
            delta_ys[b][li] = dy
            st.nb_partial[li] = nb_new
            st.planes_loaded[li] = want
            any_new[b] = True
    return delta_ys, any_new


def push_delta_batch(states: List[RetrievalState],
                     delta_ys: List[List[np.ndarray]],
                     ctx: ExecContext, counters=None) -> None:
    """Batched :func:`push_delta`: one sweep updates every chunk of the
    stack — the zero-anchor delta cascade (float64, escape deltas pinned 0
    per chunk) or the from-anchors re-sweep (float32) — mesh-sharded when
    the context carries a mesh."""
    bk = ctx.bk
    if ((bk.reconstruct_batch is None and bk.reconstruct_sharded is None)
            or len(states) == 1):
        for st, dy in zip(states, delta_ys):
            push_delta(st, dy, bk, counters=counters)
        return
    m0 = states[0].reader.meta
    dt = m0.work_dtype
    B = len(states)
    nl = len(m0.levels)
    if states[0].res is not None:
        xhat = _stack_reconstruct(
            ctx, m0.shape, m0.interp, np.stack([st.anchors for st in states]),
            [np.stack([st.res[li] for st in states]) for li in range(nl)],
            [list(zip(st.esc_idx, st.esc_val)) for st in states], dt)
        _count(counters, "reconstruct")
        for b, st in enumerate(states):
            st.xhat = xhat[b]
        return
    zero_anchors = np.zeros((B,) + tuple(m0.anchors_shape), np.float64)
    yhat = [np.stack([delta_ys[b][li] for b in range(B)])
            for li in range(nl)]
    overrides = [[(idx, np.zeros(idx.size)) for idx in st.esc_idx]
                 for st in states]
    delta = _stack_reconstruct(ctx, m0.shape, m0.interp, zero_anchors,
                               yhat, overrides, dt)
    _count(counters, "reconstruct")
    for b, st in enumerate(states):
        st.xhat = st.xhat + delta[b]
