"""Where the device's idle time goes, by the program's host stages.

While the profiler runs, the program records its own spans
(``repro.trace``): one ``encode`` request per compress, with its steps
nested under it (``sweep.layout``, ``pack.zlib``, ...), timed on the
host's ``perf_counter_ns`` clock.  A span names its host stage in its
``stage`` attribute; a span without one belongs to its nearest staged
ancestor.  The program owns that assignment: this module knows no span
names but the request's.  The harness's ``compress`` span around each
call sits in the trace on the device's clock.  This module

1. keeps the program's ``encode`` requests (the profiler runs only
   around the window, so they are the window's compresses) and pairs the
   i-th with the i-th harness ``compress`` span;
2. shifts each request's spans by (harness start - request start), one
   anchor per request, and notes how far each request's end then lies
   from its harness span's end (the clock check);
3. walks chip 0's idle intervals in the window (``tracereduce.gaps``)
   and credits each idle nanosecond to the stage of the innermost program
   span that covers it.  Idle time under no staged span is
   :data:`UNSPANNED`: the harness between calls, and the own time of
   unstaged program spans (the ``encode`` request's, each
   ``sweep.phase``'s), which is host work no stage names; the latter is
   printed on stderr by span name.

Every idle nanosecond lands in exactly one bucket, so the buckets
partition the device's idle share by construction; :func:`split` checks
that sum against ``tracereduce``'s own idle share (to
:data:`PARTITION_TOL` points) as a check of this arithmetic, not of how
much host work the spans cover -- :data:`UNSPANNED` says that.
Readers (``bench/metrics/idle.*.py`` and the counter ratios) call
:func:`analyse`, which returns None where the program records no spans
(a program without ``repro.trace``), so those metrics stay silent there.
"""
from __future__ import annotations

import importlib
import sys
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench import tracereduce

#: the span attribute that names a span's host stage
STAGE = "stage"
UNSPANNED = "unspanned"
#: the program's request span, and the harness's span around each call
ROOT, HARNESS = "encode", "compress"
#: points by which the stages' shares may miss the device's idle share
PARTITION_TOL = 0.5

Interval = Tuple[str, float, float]       # (span name, start_ns, end_ns)
#: a program span on the trace's clock:
#: (id, parent, name, stage or None, start_ns, end_ns)
Span = Tuple[int, Optional[int], str, Optional[str], float, float]
#: a span's own time: (name, stage or None, start_ns, end_ns)
Piece = Tuple[str, Optional[str], float, float]


@dataclass
class Stages:
    """A traced window's idle time by stage and the requests' counters."""
    idle_pct: Dict[str, float]      # stage -> % of the window
    unstaged_pct: Dict[str, float]  # unstaged span -> % (in UNSPANNED)
    counts: Dict[str, int]          # counter -> sum over the requests
    requests: int
    max_end_skew_ns: float          # largest |aligned end - harness end|


def align(records: Sequence, harness: Iterable[Interval]
          ) -> Tuple[List[Span], List[float]]:
    """The window's requests on the trace's clock.

    ``records`` are the program's finished spans (``repro.trace.Record``
    or anything with its fields); ``harness`` the trace's host spans.
    Returns ``(spans, skews)``: each span of an ``encode`` request with
    its stage (its own or its nearest staged ancestor's), shifted by its
    request's anchor, and each request's |aligned end - harness end|.
    Raises ValueError unless every request has its harness span and every
    harness span its request.
    """
    roots = sorted((r for r in records
                    if r.parent is None and r.name == ROOT),
                   key=lambda r: r.start_ns)
    calls = sorted((h for h in harness if h[0] == HARNESS),
                   key=lambda h: h[1])
    if len(roots) != len(calls):
        raise ValueError(f"{len(roots)} '{ROOT}' requests recorded against "
                         f"{len(calls)} harness '{HARNESS}' spans")
    shift = {r.id: h[1] - r.start_ns for r, h in zip(roots, calls)}
    skews = [abs(r.end_ns + shift[r.id] - h[2])
             for r, h in zip(roots, calls)]
    mine = [r for r in records if r.request in shift]
    by_id = {r.id: r for r in mine}

    def stage(r) -> Optional[str]:
        while r is not None:
            if STAGE in r.attrs:
                return str(r.attrs[STAGE])
            r = by_id.get(r.parent)
        return None

    spans = [(r.id, r.parent, r.name, stage(r),
              r.start_ns + shift[r.request], r.end_ns + shift[r.request])
             for r in mine]
    return spans, skews


def self_intervals(spans: Sequence[Span]) -> List[Piece]:
    """Each span's own time, its interval less its children's, as
    disjoint ``(name, stage, start, end)`` pieces in time order."""
    kids: Dict[int, List[Span]] = defaultdict(list)
    for sp in spans:
        if sp[1] is not None:
            kids[sp[1]].append(sp)
    out: List[Piece] = []
    for sid, _, name, st, s, e in spans:
        t = s
        for *_, cs, ce in sorted(kids.get(sid, ()), key=lambda c: c[4]):
            if cs > t:
                out.append((name, st, t, min(cs, e)))
            t = max(t, ce)
        if e > t:
            out.append((name, st, t, e))
    out.sort(key=lambda v: v[2])
    # requests may touch after alignment: never credit a moment twice
    clean: List[Piece] = []
    for name, st, s, e in out:
        if clean and s < clean[-1][3]:
            s = clean[-1][3]
        if e > s:
            clean.append((name, st, s, e))
    return clean


def idle_by_stage(gaps: Sequence[Tuple[float, float]],
                  pieces: Sequence[Piece]
                  ) -> Tuple[Dict[str, float], Dict[str, float]]:
    """Idle nanoseconds per stage: each gap's overlap with the disjoint,
    time-ordered pieces; the rest, and the pieces with no stage, under
    :data:`UNSPANNED`.  Also returns the latter by span name."""
    out: Dict[str, float] = defaultdict(float)
    unstaged: Dict[str, float] = defaultdict(float)
    j = 0
    for gs, ge in gaps:
        while j < len(pieces) and pieces[j][3] <= gs:
            j += 1
        staged, k = 0.0, j
        while k < len(pieces) and pieces[k][2] < ge:
            name, st, s, e = pieces[k]
            part = min(e, ge) - max(s, gs)
            if part > 0:
                if st is None:
                    unstaged[name] += part
                else:
                    out[st] += part
                    staged += part
            k += 1
        out[UNSPANNED] += (ge - gs) - staged
    return dict(out), dict(unstaged)


def split(records: Sequence, harness: Iterable[Interval],
          ops: Sequence[Interval], lo: float, hi: float,
          idle_pct: float) -> Stages:
    """The window's stages from the program's records, the trace's host
    spans, chip 0's operations and the window [lo, hi]; ``idle_pct`` is
    the device's idle share the stages must sum to."""
    spans, skews = align(records, harness)
    ns, unstaged = idle_by_stage(tracereduce.gaps(ops, lo, hi),
                                 self_intervals(spans))
    found = {sp[3] for sp in spans if sp[3] is not None} | {UNSPANNED}
    pct = {st: 100.0 * ns.get(st, 0.0) / (hi - lo) for st in sorted(found)}
    if abs(sum(pct.values()) - idle_pct) > PARTITION_TOL:
        raise ValueError(f"idle by stage sums to {sum(pct.values()):.3f}% "
                         f"of the window, the device idles {idle_pct:.3f}%")
    counts: Dict[str, int] = defaultdict(int)
    keep = {sp[0] for sp in spans}
    for r in records:
        if r.id in keep:
            for k, v in r.counts.items():
                counts[k] += v
    return Stages(idle_pct=pct,
                  unstaged_pct={n: 100.0 * v / (hi - lo)
                                for n, v in sorted(unstaged.items())},
                  counts=dict(counts), requests=len(skews),
                  max_end_skew_ns=max(skews, default=0.0))


def analyse(ctx) -> Optional[Stages]:
    """The traced window's :class:`Stages`, computed once per run; None
    untraced or where the program records no ``encode`` request."""
    if ctx.trace is None:
        return None
    if hasattr(ctx, "stages"):
        return ctx.stages
    ctx.stages = None
    try:
        program = importlib.import_module("repro.trace")
    except ImportError:
        return None
    records = program.records()
    if not any(r.parent is None and r.name == ROOT for r in records):
        return None
    if program.dropped():
        raise ValueError(f"the program's span buffer dropped "
                         f"{program.dropped()} records in the window")
    t = ctx.trace
    ctx.stages = split(records, t.spans, t.ops[min(t.ops)], t.lo, t.hi,
                        100.0 * t.idle_share())
    print(f"stages: {ctx.stages.requests} requests, largest |end skew| "
          f"{ctx.stages.max_end_skew_ns / 1e6:.3f} ms, idle % by stage "
          f"{ctx.stages.idle_pct}, unstaged spans' own idle % (in "
          f"{UNSPANNED}) {ctx.stages.unstaged_pct}", file=sys.stderr)
    return ctx.stages


def idle_share(ctx, stage: str) -> Optional[float]:
    """Percent of the window the device idled under ``stage``; None where
    no span of the window carries that stage."""
    st = analyse(ctx)
    return None if st is None else st.idle_pct.get(stage)
