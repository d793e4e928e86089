"""Reduction of a profiler trace to the benchmark's device numbers.

The profiler writes an XPlane file (``<dir>/plugins/profile/<time>/
*.xplane.pb``).  Each chip is a plane ``/device:TPU:<n>``; its line
``XLA Ops`` holds one event per operation that ran on the chip, named by
its HLO instruction (``%interp_quant.1 = (...) custom-call(...)``): a
Pallas kernel's instruction carries the kernel's ``name``.  The
harness's own host spans (``jax.profiler.TraceAnnotation``) are events
of the host plane.

The functions below take plain lists of ``(name, start_ns, end_ns)``, so
that they can be checked on a synthetic event list
(``bench/tests/test_bench_trace.py``):

* busy time: the union of a chip's operation intervals inside the window;
* idle share: 1 - busy / window;
* kernel time: the summed durations of the operations of given names;
* idle gaps, each labelled by the innermost host span around its middle.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]          # (name, start_ns, end_ns)

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
#: an op event is named by its HLO instruction, ``%interp_quant.1 = ...``
OP_NAME = re.compile(r"%?([^\s=]+)")
SUFFIX = re.compile(r"\.\d+$")
TOP = 10


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def busy_intervals(events: Iterable[Event], lo: float, hi: float
                   ) -> List[Tuple[float, float]]:
    """The union of the events' intervals inside [lo, hi], merged."""
    out: List[List[float]] = []
    for _, s, e in sorted(clip(events, lo, hi), key=lambda v: v[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: Iterable[Event], lo: float, hi: float) -> float:
    return sum(e - s for s, e in busy_intervals(events, lo, hi))


def gaps(events: Iterable[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The idle intervals of [lo, hi]: where no operation ran."""
    out, t = [], lo
    for s, e in busy_intervals(events, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(t: float, spans: Sequence[Event]) -> str:
    """The innermost (shortest) host span that covers time ``t``."""
    best = None
    for n, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (n, s, e)
    return best[0] if best else "outside"


def kernel_ns(events: Iterable[Event], names: Iterable[str]) -> float:
    """Summed durations of the operations whose name is one of ``names``
    or one of them with a numeric suffix (``interp_quant.3``)."""
    pat = re.compile("|".join(rf"{re.escape(n)}(\.\d+)?" for n in names))
    return sum(e - s for n, s, e in events if pat.fullmatch(n))


def op_name(event_name: str) -> str:
    """``interp_quant.1`` of ``%interp_quant.1 = (s32[...]) custom-call(..."""
    return OP_NAME.match(event_name).group(1)


def top_ops(events: Iterable[Event], k: int = TOP) -> List[list]:
    """Device seconds by operation, numeric suffixes merged."""
    tot: Dict[str, float] = defaultdict(float)
    for n, s, e in events:
        tot[SUFFIX.sub("", n)] += e - s
    return [[n, v / 1e9] for n, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def top_gaps(events: Iterable[Event], spans: Sequence[Event], lo: float,
             hi: float, k: int = TOP) -> List[list]:
    g = sorted(gaps(events, lo, hi), key=lambda v: v[0] - v[1])[:k]
    return [[label((s + e) / 2, spans), (e - s) / 1e9] for s, e in g]


@dataclass
class Summary:
    """A traced window, reduced: per-chip operations in the window, the
    window's bounds, and the numbers the result line carries."""
    ops: Dict[int, List[Event]]
    spans: List[Event]
    lo: float
    hi: float
    busy_s: float
    window_s: float
    top_ops: List[list]
    top_gaps: List[list]

    def kernel_s(self, names: Iterable[str]) -> float:
        """Device seconds of the named kernels, averaged over the chips."""
        names = list(names)
        return sum(kernel_ns(ev, names) for ev in self.ops.values()) \
            / 1e9 / max(len(self.ops), 1)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def read_xplane(trace_dir: Path):
    """(device ops by chip, host spans) of the newest trace under
    ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    ops: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops.setdefault(int(m.group(1)), []).extend(
                    (op_name(e.name), e.start_ns, e.end_ns)
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events)
    return ops, spans


def summarize(trace_dir: Path, chips: int, span_names: Sequence[str]
              ) -> Summary:
    """Reduce the trace of one window: the window is the harness's
    ``window`` span; busy time is averaged over the chips used."""
    ops, spans = read_xplane(trace_dir)
    ours = [s for s in spans if s[0] in span_names]
    win = [s for s in spans if s[0] == "window"]
    if not win:
        raise ValueError("the trace holds no 'window' span")
    lo, hi = win[-1][1], win[-1][2]
    used = {d: clip(ev, lo, hi) for d, ev in sorted(ops.items())[:chips]}
    if not used:
        raise ValueError("the trace holds no device operations")
    busy = sum(busy_ns(ev, lo, hi) for ev in used.values()) / len(used)
    first = next(iter(used.values()))
    return Summary(ops=used, spans=ours, lo=lo, hi=hi, busy_s=busy / 1e9,
                   window_s=(hi - lo) / 1e9, top_ops=top_ops(first),
                   top_gaps=top_gaps(first, ours, lo, hi))
