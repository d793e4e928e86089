"""The split of the device's idle time by the program's host stages gives
known answers on synthetic records and events (``bench/stages.py``)."""
from types import SimpleNamespace
from typing import Dict, NamedTuple, Optional

import pytest

from bench import harness, stages, tracereduce


class Rec(NamedTuple):
    """The fields of ``repro.trace.Record`` that the split reads."""
    name: str
    id: int
    parent: Optional[int]
    request: int
    start_ns: int
    end_ns: int
    counts: Dict[str, int] = {}
    attrs: Dict[str, object] = {}


def staged(stage):
    return {"stage": stage}


# two compresses, each on a clock of its own (one anchor per request)
RECORDS = [
    # request 1: program clock 10000 = trace clock 100
    Rec("sweep.layout", 3, 2, 1, 10060, 10080, {}, staged("sweep_layout")),
    Rec("sweep.kernel", 4, 2, 1, 10080, 10150,
        {"h2d_bytes": 30, "d2h_bytes": 20, "traces": 2}, staged("kernel_io")),
    Rec("sweep.screen", 5, 2, 1, 10150, 10200, {"escapes": 1},
        staged("screen")),
    Rec("sweep.phase", 2, 1, 1, 10050, 10200, {}, {"level": 1}),
    Rec("pack.zlib", 6, 1, 1, 10250, 10350, {}, staged("zlib")),
    Rec("encode.container", 7, 1, 1, 10350, 10400, {}, staged("container")),
    Rec("encode", 1, None, 1, 10000, 10400, {"field_bytes": 100}),
    # request 2: program clock 50000 = trace clock 600; ends 10 ns early
    Rec("pack.negabinary", 9, 8, 8, 50100, 50200, {"traces": 1},
        staged("negabinary")),
    Rec("encode", 8, None, 8, 50000, 50290,
        {"field_bytes": 100, "d2h_bytes": 50}),
]
HARNESS = [("window", 0, 1000), ("compress", 100, 500),
           ("compress", 600, 900)]
# chip 0: busy 0-100, 200-230, 600-620; idle 850 of the window's 1000
OPS = [("fusion", 0, 100), ("interp_quant", 200, 230), ("copy", 600, 620)]
# the unstaged spans' own idle time (encode, sweep.phase) is unspanned
UNSTAGED_NS = {"encode": 50 + 50 + 80 + 90, "sweep.phase": 10}
IDLE_NS = {"container": 50, "screen": 50, "sweep_layout": 20,
           "kernel_io": 20 + 20, "zlib": 100, "negabinary": 100,
           "unspanned": 100 + 110 + sum(UNSTAGED_NS.values())}


def split(records=RECORDS, harness_spans=HARNESS, idle_pct=85.0):
    return stages.split(records, harness_spans, OPS, 0, 1000, idle_pct)


def test_idle_is_credited_to_the_innermost_span_by_stage():
    st = split()
    assert st.idle_pct == pytest.approx(
        {k: v / 10 for k, v in IDLE_NS.items()})
    assert st.unstaged_pct == pytest.approx(
        {k: v / 10 for k, v in UNSTAGED_NS.items()})
    assert st.requests == 2


def test_each_request_is_aligned_on_its_harness_span():
    spans, skews = stages.align(RECORDS, HARNESS)
    by = {sp[0]: sp[4:] for sp in spans}
    assert by[1] == (100, 500) and by[2] == (150, 300)
    assert by[8] == (600, 890) and by[9] == (700, 800)
    assert skews == [0, 10]
    assert {sp[2]: sp[3] for sp in spans}["sweep.phase"] is None
    assert split().max_end_skew_ns == 10


def test_self_time_leaves_out_the_children():
    spans, _ = stages.align(RECORDS[:7], HARNESS[:2])
    assert stages.self_intervals(spans) == [
        ("encode", None, 100, 150), ("sweep.phase", None, 150, 160),
        ("sweep.layout", "sweep_layout", 160, 180),
        ("sweep.kernel", "kernel_io", 180, 250),
        ("sweep.screen", "screen", 250, 300), ("encode", None, 300, 350),
        ("pack.zlib", "zlib", 350, 450),
        ("encode.container", "container", 450, 500)]


def test_the_stages_partition_the_idle_share():
    st = split()
    assert sum(st.idle_pct.values()) == pytest.approx(85.0)
    with pytest.raises(ValueError, match="sums to"):
        split(idle_pct=80.0)


def test_counters_sum_over_the_window_requests_only():
    other = [Rec("other", 20, None, 20, 0, 5, {"traces": 7})]
    st = split(RECORDS + other)
    assert st.counts == {"h2d_bytes": 30, "d2h_bytes": 70, "traces": 3,
                         "escapes": 1, "field_bytes": 200}


@pytest.mark.parametrize("cut", ["request", "harness"])
def test_a_request_without_its_harness_span_is_refused(cut):
    recs, spans = RECORDS, HARNESS
    if cut == "harness":
        spans = HARNESS[:2]
    else:
        recs = RECORDS[:7]
    with pytest.raises(ValueError, match="requests recorded against"):
        split(recs, spans)


def test_a_span_takes_the_stage_of_its_nearest_staged_ancestor():
    """A span a later program adds needs no edit here: without a stage it
    counts to its staged ancestor's stage (else unspanned), and a stage
    no metric reads yet is reported beside the others."""
    recs = RECORDS + [
        Rec("pack.negabinary.table", 10, 9, 8, 50150, 50180),
        Rec("pack.new", 11, 8, 8, 50210, 50220, {}, staged("new_stage")),
        Rec("pack.new.inner", 12, 11, 8, 50212, 50215)]
    st = split(recs)
    want = {k: v / 10 for k, v in IDLE_NS.items()}
    want["new_stage"] = 1.0
    want["unspanned"] -= 1.0
    assert st.idle_pct == pytest.approx(want)
    assert st.unstaged_pct["encode"] == pytest.approx(
        UNSTAGED_NS["encode"] / 10 - 1.0)


NEW_METRICS = {
    "idle.sweep_layout.compress": 2.0, "idle.kernel_io.compress": 4.0,
    "idle.screen.compress": 5.0, "idle.negabinary.compress": 10.0,
    "idle.zlib.compress": 10.0, "idle.container.compress": 5.0,
    "idle.unspanned.compress": 49.0,
    "hostdev_bytes_per_byte.compress": 100 / 200,
    "traces_per_compress.compress": 3 / 2,
}


def ctx_of(trace_summary):
    return SimpleNamespace(trace=trace_summary)


def summary():
    lo, hi = 0, 1000
    busy = tracereduce.busy_ns(OPS, lo, hi) / 1e9
    return tracereduce.Summary(
        ops={0: OPS}, spans=HARNESS[1:], lo=lo, hi=hi, busy_s=busy,
        window_s=1e-6, top_ops=[], top_gaps=[])


def read(name, ctx):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py"
                               ).read(ctx)


def test_readers_give_the_split(monkeypatch):
    from repro import trace

    monkeypatch.setattr(trace, "records", lambda: list(RECORDS))
    monkeypatch.setattr(trace, "dropped", lambda: 0)
    ctx = ctx_of(summary())
    for name, want in NEW_METRICS.items():
        assert read(name, ctx) == pytest.approx(want), name
    assert sum(read(n, ctx) for n in NEW_METRICS if n.startswith("idle.")) \
        == pytest.approx(100 * ctx.trace.idle_share())


def test_readers_are_silent_without_program_spans(monkeypatch):
    from repro import trace

    for name in NEW_METRICS:
        assert read(name, ctx_of(None)) is None
    monkeypatch.setattr(trace, "records", lambda: [])
    for name in NEW_METRICS:
        assert read(name, ctx_of(summary())) is None


def test_a_full_buffer_is_refused(monkeypatch):
    from repro import trace

    monkeypatch.setattr(trace, "records", lambda: list(RECORDS))
    monkeypatch.setattr(trace, "dropped", lambda: 3)
    with pytest.raises(ValueError, match="dropped"):
        stages.analyse(ctx_of(summary()))


def test_every_new_metric_is_declared_for_the_cell():
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    names = {m["name"] for m in harness.cell_metrics(
        spec, "isabel.compress", True)}
    assert set(NEW_METRICS) <= names
