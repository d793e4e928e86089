"""Optimized data loading (paper §5): knapsack DP over (level x bitplanes).

Two modes:
  * error-bound mode (§5.2): minimize loaded bytes s.t.
        sum_l p^(l-1) * delta_y_l(b_l) + eb <= E
  * bitrate / fixed-size mode (§5.3): minimize the error bound s.t.
        sum_l LoadedSize(l, b_l) <= S

``b_l`` = number of LSB planes discarded at level l.  delta_y_l(b) is the
exact per-level truncation loss table pre-computed at compression time
(container header), p = L_inf(P) (1.0 linear / 1.25 cubic, Theorem 1).

The DP discretizes the continuous budget into ``NBUCKETS`` units (the paper
normalizes E/eb into [128, 1023]); costs are rounded UP when consuming
budget, so the returned plan is always feasible (conservative).

``propagation="paper"`` uses Theorem 1's p^(l-1).  ``propagation="safe"``
uses p^((l-1+1)*ndim_phases) — an upper bound that also covers within-level
dimension-sequential amplification (see DESIGN.md §3); used by the
adversarial property tests.

Float32 archives compute in float32 (``core.arith``), and every reported
bound of a partial read adds a rigorous rounding allowance
(:func:`plan_bound`); a full read's bound is ``eb`` exactly, because its
bits are the ones the encoder verified element by element.

Chunked (v2) archives run this planner per chunk: error mode passes the
requested bound straight through (per-chunk L_inf <= E implies the global
bound), byte/bitrate budgets are pre-split across chunks proportionally to
element count with largest-remainder rounding, after reserving each
chunk's escape-channel plan floor (see
``pipeline.decode._retrieve_chunked`` / ``refine_budgets``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np

from . import arith
from .container import ArchiveMeta
from .interpolation import PRED_NORM

NBUCKETS = 1024
PAPER = "paper"
SAFE = "safe"

#: rounding error of one float32 prediction, in units of u * M (u = 2**-24,
#: M bounds every value): cubic rounds its two ``8x + x`` terms and three
#: sums, of magnitude at most 9, 9, 10, 19 and 20 M, then scales by 1/16;
#: linear rounds one sum of magnitude 2 M, then halves
PRED_UNITS = {"cubic": 67.0 / 16.0, "linear": 1.0}
#: flush-to-zero events per phase over two sweeps (11 each), rounded up;
#: each moves a value by less than ``arith.TINY32``
FLUSH_UNITS = 32


@dataclass
class LoadPlan:
    keep_planes: List[int]        # planes to load per level (MSB-first count)
    loaded_bytes: int             # data bytes the plan touches (excl. header)
    err_bound: float              # guaranteed L_inf bound of the plan
    mode: str


def _prop_factor(meta: ArchiveMeta, level: int, propagation: str) -> float:
    """Amplification applied to level ``level``'s truncation loss (level 1 = finest).

    PAPER: Theorem 1's p^(l-1).  SAFE: corrected bound that also accounts for
    within-level dimension-sequential propagation.  Per level, a phase-d
    target's delta obeys e_d = p*e_{d-1} + delta_l over ndim phases, so level
    l contributes (sum_{k<ndim} p^k) * p^(ndim*(l-1)) * delta_l.  Empirically
    the paper's factor under-covers cubic 3D by up to ~2.3x (see
    EXPERIMENTS.md §Repro-findings); SAFE is the default so the paper's
    "error guarantee" objective actually holds.
    """
    p = PRED_NORM[meta.interp]
    if propagation == PAPER:
        return p ** (level - 1)
    ndim = len(meta.shape)
    geo = sum(p ** k for k in range(ndim))
    return geo * p ** (ndim * (level - 1))


def _level_cost_tables(meta: ArchiveMeta, propagation: str):
    """Per level: arrays over b (0..nbits) of [propagated error, loaded bytes]."""
    errs, sizes = [], []
    for li, lv in enumerate(meta.levels):
        f = _prop_factor(meta, lv.level, propagation)
        e = np.asarray(lv.delta_table, np.float64) * f          # err(l, b)
        tot = np.cumsum([0] + lv.plane_sizes)                    # prefix sums
        # keeping (nbits - b) MSB planes loads tot[nbits-b] bytes (+escapes)
        s = np.array([tot[lv.nbits - b] for b in range(lv.nbits + 1)], np.int64)
        s += lv.esc_size  # escape channel always loaded with the level
        errs.append(e)
        sizes.append(s)
    return errs, sizes


def rounding_allowance(meta: ArchiveMeta, keep: Sequence[int],
                       T: float) -> float:
    """Bound on how far a float32 partial read can stray from ``eb + T``
    through rounding alone (0 for float64 archives and full plans).

    A partial read re-sweeps from scratch with truncated bins; the full
    read, whose bits the encoder verified to lie within ``eb``, differs
    from it by the propagated truncation ``T`` plus what rounding adds.
    Levels coarser than the coarsest truncated level ``l*`` hold identical
    bins in both sweeps, hence identical bits, so the first phase of
    ``l*`` predicts from identical neighbours.  Every other phase at
    ``l*`` and below can differ by both sweeps' prediction rounding
    (``PRED_UNITS``), their ``pred + res`` roundings, their dequantization
    error against the exact ``q * 2 eb`` (at most 3u of the level's
    largest residual, read off the delta table) and their flushes.  Each
    phase's discrepancy then propagates like truncation loss under the
    SAFE model: by ``p`` per later phase.  ``M`` bounds every value either
    sweep holds — ``vmax + eb + T`` plus the allowance itself, solved in
    closed form.  Infinite when the ``M`` coefficient reaches 1.
    """
    if meta.work_dtype != np.float32:
        return 0.0
    cut = [lv.level for li, lv in enumerate(meta.levels)
           if keep[li] < lv.nbits]
    if not cut:
        return 0.0
    lstar = max(cut)
    p = PRED_NORM[meta.interp]
    nd = len(meta.shape)
    u = arith.U32
    c_m = 0.0     # coefficient of M
    const = 0.0   # absolute part
    for lv in meta.levels:
        if lv.level > lstar:
            continue
        dt = lv.delta_table
        deq = 3.0001 * u * (2.0 * dt[lv.nbits] + max(dt))
        for d in range(nd):
            w = p ** (nd - 1 - d + nd * (lv.level - 1))
            pred = 0.0 if (lv.level == lstar and d == 0) \
                else 2.0 * PRED_UNITS[meta.interp]
            c_m += w * (pred + 2.0) * u
            const += w * (deq + FLUSH_UNITS * arith.TINY32)
    if c_m >= 1.0:
        return math.inf
    M = (float(meta.vmax) + meta.eb + T + const) / (1.0 - c_m)
    return c_m * M + const


def plan_bound(meta: ArchiveMeta, keep: Sequence[int], errs,
               propagation: str) -> float:
    """Guaranteed L_inf bound of reading ``keep[li]`` MSB planes per level
    (``errs`` from :func:`_level_cost_tables` under ``propagation``).

    ``eb`` plus the summed propagated truncation loss; a float32 archive
    adds a relative ``2u`` on the loss (its bin width is ``f32(2 eb)``)
    and the :func:`rounding_allowance`.  The one formula behind every
    planner's reported bound and the session's achieved bound, so the two
    agree to the bit.
    """
    T = sum(float(errs[li][lv.nbits - keep[li]])
            for li, lv in enumerate(meta.levels))
    if meta.work_dtype != np.float32:
        return meta.eb + T
    T *= 1.0 + 2.0 * arith.U32
    return meta.eb + T + rounding_allowance(meta, keep, T)


def _dp_error(meta: ArchiveMeta, errs, sizes, budget: float,
              max_discard: Sequence[int]) -> Optional[List[int]]:
    """Knapsack core of :func:`plan_error_mode`: per-level discard counts
    ``b_l <= max_discard[l]`` with summed ``errs`` within ``budget`` and
    the fewest bytes, or None when nothing fits."""
    nl = len(meta.levels)
    unit = budget / NBUCKETS
    # err in integer units, rounded UP => conservative
    err_units = [np.minimum(np.ceil(e / unit), NBUCKETS + 1).astype(np.int64)
                 for e in errs]
    # DP[u] = min bytes with total err units <= u, processed levels so far
    INF = np.int64(1 << 60)
    dp = np.zeros(NBUCKETS + 1, np.int64)  # zero levels: zero bytes
    choice = np.zeros((nl, NBUCKETS + 1), np.int16)
    for li in range(nl):
        ndp = np.full(NBUCKETS + 1, INF, np.int64)
        nch = np.zeros(NBUCKETS + 1, np.int16)
        for b in range(max_discard[li] + 1):
            eu = int(err_units[li][b])
            if eu > NBUCKETS:
                continue  # this choice alone blows the budget
            cost = sizes[li][b]
            # shifting: state u can take choice b if u >= eu
            cand = np.full(NBUCKETS + 1, INF, np.int64)
            cand[eu:] = dp[: NBUCKETS + 1 - eu] + cost
            upd = cand < ndp
            ndp[upd] = cand[upd]
            nch[upd] = b
        dp = ndp
        choice[li] = nch
    if dp[NBUCKETS] >= INF:
        return None
    # backtrack from the full budget
    u = NBUCKETS
    discard = []
    for li in range(nl - 1, -1, -1):
        b = int(choice[li][u])
        discard.append(b)
        u -= int(err_units[li][b])
    discard.reverse()
    return discard


def plan_error_mode(meta: ArchiveMeta, E: float,
                    propagation: str = PAPER) -> LoadPlan:
    """Minimum-volume plan with guaranteed L_inf error <= E (requires E >= eb).

    Float32 archives pay a rounding allowance that depends on the
    coarsest truncated level (:func:`rounding_allowance`, which grows with
    it), so the knapsack
    runs once per candidate coarsest level — coarser levels kept whole,
    the budget net of that candidate's allowance — and the cheapest
    feasible plan wins; the full plan is always feasible.
    """
    if E < meta.eb:
        raise ValueError(f"requested bound {E} < compression bound {meta.eb}")
    errs, sizes = _level_cost_tables(meta, propagation)
    nl = len(meta.levels)
    full = [lv.nbits for lv in meta.levels]
    budget = E - meta.eb
    if budget <= 0:
        return _finish(meta, full, errs, mode="error", propagation=propagation)
    if meta.work_dtype != np.float32:
        discard = _dp_error(meta, errs, sizes, budget, full)
        keep = [full[i] - discard[i] for i in range(nl)]
        return _finish(meta, keep, errs, mode="error", propagation=propagation)
    best = full
    best_bytes = _loaded_bytes(meta, full)
    scale = 1.0 + 2.0 * arith.U32
    for lstar in sorted({lv.level for lv in meta.levels}):
        keep_one = [lv.nbits - (lv.level <= lstar) for lv in meta.levels]
        rho = rounding_allowance(meta, keep_one, budget)
        room = (budget - rho) / scale
        if not room > 0:
            continue
        cap = [lv.nbits if lv.level <= lstar else 0 for lv in meta.levels]
        discard = _dp_error(meta, errs, sizes, room, cap)
        if discard is None:
            continue
        keep = [full[i] - discard[i] for i in range(nl)]
        nbytes = _loaded_bytes(meta, keep)
        if nbytes < best_bytes:
            best, best_bytes = keep, nbytes
    return _finish(meta, best, errs, mode="error", propagation=propagation)


def plan_bitrate_mode(meta: ArchiveMeta, max_bytes: int,
                      propagation: str = PAPER) -> LoadPlan:
    """Minimum-error plan with loaded bytes <= max_bytes.

    Every plan loads the escape channels (lossless outliers travel with
    their level), so the smallest representable plan costs
    ``sum(esc_size)`` bytes — the *plan floor*.  A ``max_bytes`` below the
    floor is infeasible and raises ``ValueError``: silently returning the
    floor plan (the old behaviour) violated the ``Fidelity.max_bytes``
    contract with no signal, reporting ``loaded_bytes > max_bytes``.
    ``max_bytes`` exactly at the floor is feasible and returns the
    zero-plane plan.
    """
    errs, sizes = _level_cost_tables(meta, propagation)
    nl = len(meta.levels)
    min_bytes = int(sum(int(s[-1]) for s in sizes))  # b = nbits per level
    if max_bytes < min_bytes:
        raise ValueError(
            f"max_bytes={max_bytes} is infeasible: the smallest plan for "
            f"this archive loads {min_bytes} bytes (escape channels are "
            "always loaded with their level); request at least that many "
            "bytes or use an error-bound target")
    budget = max_bytes - min_bytes
    if budget <= 0:  # exactly the escape-channel floor: load the minimum
        return _finish(meta, [0] * nl, errs, mode="bitrate",
                       propagation=propagation)
    # ceil-rounded units guarantee sum(sizes) <= NBUCKETS*unit = budget
    unit = budget / NBUCKETS
    size_units = [np.minimum(np.ceil((s - s[-1]) / unit), NBUCKETS + 1).astype(np.int64)
                  for s in sizes]
    INF = float("inf")
    dp = np.zeros(NBUCKETS + 1, np.float64)
    choice = np.zeros((nl, NBUCKETS + 1), np.int16)
    for li in range(nl):
        ndp = np.full(NBUCKETS + 1, INF, np.float64)
        nch = np.full(NBUCKETS + 1, meta.levels[li].nbits, np.int16)
        for b in range(meta.levels[li].nbits + 1):
            su = int(size_units[li][b])
            if su > NBUCKETS:
                continue
            e = errs[li][b]
            cand = np.full(NBUCKETS + 1, INF, np.float64)
            cand[su:] = dp[: NBUCKETS + 1 - su] + e
            upd = cand < ndp
            ndp[upd] = cand[upd]
            nch[upd] = b
        dp = ndp
        choice[li] = nch
    u = NBUCKETS
    discard = []
    for li in range(nl - 1, -1, -1):
        b = int(choice[li][u])
        discard.append(b)
        u -= int(size_units[li][b])
    discard.reverse()
    keep = [meta.levels[i].nbits - discard[i] for i in range(nl)]
    return _finish(meta, keep, errs, mode="bitrate", propagation=propagation)


def plan_full(meta: ArchiveMeta, propagation: str = PAPER) -> LoadPlan:
    """Full-precision plan: every plane of every level.

    ``propagation`` selects the error-propagation model for the reported
    ``err_bound`` exactly like the other planners — it used to be
    hardcoded to PAPER, so a session planning under SAFE could receive a
    plan whose reported bound was computed under a different (tighter)
    model than the session's own ``update_achieved_bound`` accounting.
    """
    errs, _ = _level_cost_tables(meta, propagation)
    return _finish(meta, [lv.nbits for lv in meta.levels], errs, mode="full",
                   propagation=propagation)


# ------------------------------------------------ v3 ladder (plane-major)
#
# A v3 archive's layout IS its retrieval plan: the writer lays plane
# segments in one global order and every fidelity resolves to a *prefix
# length* ``t`` over that order.  The planners below are the two halves:
# ``ladder_order`` (write time) picks the order, ``ladder_error_mode`` /
# ``ladder_bitrate_mode`` (read time) walk it.  Unlike the per-chunk
# knapsack above, the prefix cannot tailor plane counts per chunk — that
# is the deliberate trade: a slightly less byte-optimal plan in exchange
# for monotone contiguous range reads (docs/format.md §3).

def ladder_order(chunk_metas: Sequence[ArchiveMeta],
                 propagation: str = SAFE) -> List[tuple]:
    """Greedy rate-distortion order of (level index, plane index) over the
    whole chunk grid: at each step, take the plane segment with the best
    summed error reduction per byte.

    Within a level the candidate is always the next MSB-first plane (XOR
    plane coding makes planes order-dependent), so the order interleaves
    *levels*, never planes within a level.  A level's candidate is
    scored with a LOOKAHEAD: the best cumulative gain per byte over any
    *run* of its next planes, and the whole winning run is emitted at
    once.  The lookahead matters because ``delta_table`` need not be
    monotone at the top — keeping only the MSB negabinary digit can
    reconstruct FARTHER from the data than truncating to zero (the
    lone digit overshoots), so plane 0 alone can score a negative gain.
    A per-plane greedy then parks that level's entire ladder at the end
    of the order, and every error-mode prefix through it degenerates to
    a near-total read; the run score sees past the dip (plane 0+1
    together are a large gain for few bytes).  For levels with monotone
    decaying gains the best run is always length 1 and the order —
    hence the archive bytes — is unchanged.  Scores use the SAFE
    propagation model by default — the write-time order must serve
    whichever model retrieval later plans under, and SAFE is the
    conservative one.  Zero-byte segments score infinite (free error
    reduction) and drain first; ties break toward the coarser level
    (lower level index = higher ``LevelMeta.level``), matching the
    knapsack's tendency to fill coarse levels first.  Deterministic:
    depends only on the chunk headers.
    """
    nlev = max(len(m.levels) for m in chunk_metas)
    errs = [_level_cost_tables(m, propagation)[0] for m in chunk_metas]
    nbits_max = [max((m.levels[li].nbits for m in chunk_metas
                      if li < len(m.levels)), default=0)
                 for li in range(nlev)]
    next_k = [0] * nlev
    order: List[tuple] = []

    def best_run(li: int):
        """(score, run length) of the best prefix of level li's
        remaining planes by cumulative gain per cumulative byte."""
        gain, size = 0.0, 0
        best = None
        for k in range(next_k[li], nbits_max[li]):
            for m, e in zip(chunk_metas, errs):
                if li >= len(m.levels) or k >= m.levels[li].nbits:
                    continue
                nb = m.levels[li].nbits
                gain += float(e[li][nb - k] - e[li][nb - k - 1])
                size += m.levels[li].plane_sizes[k]
            score = math.inf if size == 0 else gain / size
            if best is None or score > best[0]:
                best = (score, k - next_k[li] + 1)
            if best[0] == math.inf:
                break          # free prefix: emit now, rescore the rest
        return best

    while True:
        best = None
        for li in range(nlev):
            if next_k[li] >= nbits_max[li]:
                continue
            score, run = best_run(li)
            key = (score, -li)
            if best is None or key > best[0]:
                best = (key, li, run)
        if best is None:
            return order
        _, li, run = best
        for _ in range(run):
            order.append((li, next_k[li]))
            next_k[li] += 1


def ladder_error_mode(meta, E: float, propagation: str = PAPER,
                      t_min: int = 0) -> int:
    """Shortest ladder prefix ``t`` with every chunk's guaranteed L_inf
    bound <= ``E`` (requires ``E >= eb``, like :func:`plan_error_mode`).

    ``meta`` is a :class:`~.container.V3Meta`.  Walks the write-time
    segment order, applying each segment's exact per-chunk error delta
    (from the header delta tables) until the worst chunk meets the bound.
    ``t_min`` floors the result for refinement: a session that already
    holds ``t_min`` segments never plans a shorter prefix (planes are
    never dropped), so a looser follow-up target is a no-op.
    """
    if E < meta.eb:
        raise ValueError(f"requested bound {E} < compression bound {meta.eb}")
    errs = [_level_cost_tables(m, propagation)[0] for m in meta.chunk_metas]
    keep = [[0] * len(m.levels) for m in meta.chunk_metas]
    cur = [plan_bound(m, keep[c], errs[c], propagation)
           for c, m in enumerate(meta.chunk_metas)]
    segs = meta.plane_segments
    t = 0
    while t < len(segs) and (t < t_min or max(cur) > E):
        s = segs[t]
        for c, m in enumerate(meta.chunk_metas):
            if s.level >= len(m.levels) or s.plane >= m.levels[s.level].nbits:
                continue
            keep[c][s.level] = s.plane + 1
            cur[c] = plan_bound(m, keep[c], errs[c], propagation)
        t += 1
    return t


def ladder_bitrate_mode(meta, max_bytes: int, t_min: int = 0) -> int:
    """Longest ladder prefix whose loaded bytes fit ``max_bytes``.

    Byte accounting matches the v1/v2 planners: escapes count (they
    always load — the plan floor), anchors do not.  ``meta.cum_bytes[t]``
    is exactly that cost for prefix ``t``, so this is a table lookup.
    ``t_min`` floors the result for refinement, like
    :func:`ladder_error_mode` (the budget check still applies to the
    *requested* bytes, so a refine below the floor of already-held bytes
    simply no-ops at ``t_min``).
    """
    cum = meta.cum_bytes
    if max_bytes < cum[0]:
        raise ValueError(
            f"max_bytes={max_bytes} is infeasible: the smallest plan for "
            f"this archive loads {cum[0]} bytes (escape channels are "
            "always loaded with their level); request at least that many "
            "bytes or use an error-bound target")
    t = 0
    while t + 1 < len(cum) and cum[t + 1] <= max_bytes:
        t += 1
    return max(t, t_min)


def _loaded_bytes(meta: ArchiveMeta, keep: Sequence[int]) -> int:
    return int(sum(sum(lv.plane_sizes[: keep[li]]) + lv.esc_size
                   for li, lv in enumerate(meta.levels)))


def _finish(meta: ArchiveMeta, keep: List[int], errs, mode: str,
            propagation: str) -> LoadPlan:
    # plan_bound is also state.update_achieved_bound's formula, so the
    # plan's reported bound and the session's achieved bound agree
    return LoadPlan(keep_planes=keep, loaded_bytes=_loaded_bytes(meta, keep),
                    err_bound=float(plan_bound(meta, keep, errs,
                                               propagation)),
                    mode=mode)
