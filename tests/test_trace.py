"""The span recorder (``repro.trace``) and the encode path's spans.

Off (no profiler trace, no ``recording()`` block) a span records nothing
and emits no profiler annotation; on, every span of one compress nests
under one ``encode`` request, counters land on the innermost span, and
the archive bytes are those of an unrecorded compress.
"""
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import trace
from repro.api import Codec, ExecPolicy
from repro.core.pipeline import encode
from repro.kernels import dispatch

#: the encode path's spans, each under its parent
PARENT = {
    "encode.prepare": "encode",
    "encode.container": "encode",
    "sweep.phase": "encode",
    "sweep.layout": "sweep.phase",
    "sweep.kernel": "sweep.phase",
    "sweep.screen": "sweep.phase",
    "pack.negabinary": "encode",
    "pack.kernel": "encode",
    "pack.zlib": "encode",
}
#: the sharded path's placement and unpadding, inside the kernel I/O
SHARD_PARENT = {("shard.layout", "sweep.kernel"),
                ("shard.layout", "pack.kernel")}
#: the host stage each span's time counts to (its ``stage`` attribute,
#: which the benchmark's stage metrics read, ``bench/stages.py``); the
#: request's and each phase's own time is in no stage
STAGE = {
    "encode": None,
    "encode.prepare": "container",
    "encode.container": "container",
    "sweep.phase": None,
    "sweep.layout": "sweep_layout",
    "sweep.kernel": "kernel_io",
    "sweep.screen": "screen",
    "pack.negabinary": "negabinary",
    "pack.kernel": "kernel_io",
    "pack.zlib": "zlib",
    "shard.layout": "shard",
}


@pytest.fixture(autouse=True)
def empty_buffer():
    trace.clear()
    yield
    trace.clear()


def field(shape=(10, 16, 20)):
    i, j, k = np.meshgrid(*(np.arange(n) for n in shape), indexing="ij")
    return (np.sin(i * 0.3) + np.cos(j * 0.2) * k * 0.01).astype(np.float32)


#: 4-row chunks of a 10-row field: a batched group of two and a 2-row tail
CODEC = Codec(eb=1e-4, relative=True, chunk_elems=4 * 16 * 20, version=3)


def listeners():
    from jax._src import monitoring
    return monitoring._event_duration_secs_listeners


def test_off_records_nothing_and_emits_no_annotation(monkeypatch):
    made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **k: made.append(a))
    assert not trace.active()
    with trace.span("stage", level=3):
        trace.count("items", 5)
    with trace.request("call"):
        trace.count("items", 1)
    assert trace.records() == [] and made == []


def test_off_whole_compress_records_nothing():
    CODEC.compress(field(), ExecPolicy(backend="numpy"))
    assert trace.records() == [] and trace.dropped() == 0


def test_on_inside_recording_block():
    with trace.recording():
        assert trace.active()
        with trace.span("outer", k=1):
            with trace.span("inner"):
                pass
    assert not trace.active()
    inner, outer = trace.records()
    assert (inner.name, outer.name) == ("inner", "outer")
    assert outer.attrs == {"k": 1}
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns


def test_on_under_the_profiler_and_annotated_in_its_trace(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert trace.active()
        with trace.span("stage.under_profiler"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert not trace.active()
    assert [r.name for r in trace.records()] == ["stage.under_profiler"]
    from jax.profiler import ProfileData

    pb = next(Path(tmp_path).rglob("*.xplane.pb"))
    names = {e.name for p in ProfileData.from_file(str(pb)).planes
             for line in p.lines for e in line.events}
    assert "stage.under_profiler" in names


def test_counters_land_on_the_innermost_span():
    with trace.recording():
        with trace.span("outer"):
            trace.count("a", 2)
            with trace.span("inner"):
                trace.count("a", 3)
                trace.count("b")
            trace.count("a", 4)
        trace.count("a", 100)      # no span open: dropped
    inner, outer = trace.records()
    assert inner.counts == {"a": 3, "b": 1}
    assert outer.counts == {"a": 6}


def test_request_ids_and_parents_nest():
    with trace.recording():
        with trace.request("r1"):
            with trace.span("s"):
                with trace.request("nested"):   # inside a request: nothing
                    with trace.span("t"):
                        pass
        with trace.request("r2"):
            pass
    by = {r.name: r for r in trace.records()}
    assert set(by) == {"r1", "s", "t", "r2"}
    assert by["r1"].parent is None and by["r1"].request == by["r1"].id
    assert by["s"].parent == by["r1"].id and by["t"].parent == by["s"].id
    assert {by["s"].request, by["t"].request} == {by["r1"].id}
    assert by["r2"].request == by["r2"].id != by["r1"].id


def test_dispatch_record_counts_a_launch_on_the_span():
    before = dispatch.counts().get("probe_kernel", 0)
    with trace.recording():
        with trace.span("stage"):
            dispatch.record("probe_kernel")
            dispatch.record("probe_kernel", devices=4)
    (rec,) = trace.records()
    assert rec.counts == {"launches": 2}
    assert dispatch.counts()["probe_kernel"] == before + 2


def test_the_profiler_flag_is_jaxs_own():
    """The recorder reads a private JAX name; a JAX that moves it fails
    this test, not every compress."""
    from jax._src.lib import _profiler

    flag = trace._profiler_flag()
    assert flag is _profiler.TraceMe.is_enabled and flag() is False
    assert trace._profiling() is False


def test_a_moved_profiler_flag_fails_by_name(monkeypatch):
    from jax._src.lib import _profiler

    monkeypatch.delattr(_profiler.TraceMe, "is_enabled")
    with pytest.raises(ImportError, match="TraceMe.is_enabled"):
        trace._profiler_flag()


def test_buffer_drops_the_oldest_and_counts_the_drops(monkeypatch):
    from collections import deque

    monkeypatch.setattr(trace, "_buf", deque(maxlen=3))
    with trace.recording():
        for i in range(5):
            with trace.span(f"s{i}"):
                pass
    assert [r.name for r in trace.records()] == ["s2", "s3", "s4"]
    assert trace.dropped() == 2
    trace.clear()
    assert trace.records() == [] and trace.dropped() == 0


def test_traces_are_counted_and_the_listener_leaves_with_the_request():
    assert trace._on_duration not in listeners()
    with trace.recording():
        with trace.request("req"):
            assert trace._on_duration in listeners()
            with trace.span("tracing"):
                jax.jit(lambda v: v * 3 + 1)(jnp.arange(7.0))
        assert trace._on_duration not in listeners()
    assert trace._on_duration not in listeners()
    tracing = next(r for r in trace.records() if r.name == "tracing")
    assert tracing.counts.get("traces", 0) >= 1


def tree(records):
    by = {r.id: r for r in records}
    return Counter((r.name, by[r.parent].name if r.parent else None)
                   for r in records)


@pytest.mark.parametrize("backend,shard", [("jax", None), ("numpy", None),
                                           ("jax", 1)],
                         ids=["jax", "numpy", "jax-shard1"])
def test_chunked_compress_spans_and_bytes(backend, shard):
    x, policy = field(), ExecPolicy(backend=backend, shard=shard)
    plain = CODEC.compress(x, policy).tobytes()
    with trace.recording():
        arc = CODEC.compress(x, policy)
    assert arc.tobytes() == plain
    recs = trace.records()
    roots = [r for r in recs if r.parent is None]
    assert [r.name for r in roots] == ["encode"]
    root = roots[0]
    assert all(r.request == root.id for r in recs)
    want = set(PARENT.items()) | {("encode", None), ("sweep.screen", "encode")}
    if backend == "numpy":
        # the host sweep: no kernel I/O in its phases
        want -= {("sweep.kernel", "sweep.phase")}
    if shard is not None:
        want |= SHARD_PARENT
    assert set(tree(recs)) == want
    assert all(r.attrs.get("stage") == STAGE[r.name] for r in recs)
    assert root.counts == {"field_bytes": x.nbytes}
    total = Counter()
    for r in recs:
        total.update(r.counts)
    assert 0 < total["zlib_out_bytes"] < total["zlib_in_bytes"]
    assert 0 < total["escapes"] < x.size
    if backend == "jax":
        kio = [r for r in recs if r.name in ("sweep.kernel", "pack.kernel")]
        assert all(r.counts["h2d_bytes"] > 0 and r.counts["d2h_bytes"] > 0
                   and r.counts["launches"] == 1 for r in kio)
        assert total["launches"] == sum(
            r.counts.get("launches", 0) for r in kio)
    if shard is None:
        assert "shard_rows" not in total and "shard_pad_rows" not in total
    else:
        # a one-device mesh takes the batched group (2 chunks) unpadded
        assert total["shard_rows"] > 0 and total["shard_pad_rows"] == 0


def test_single_chunk_and_batched_paths_nest_alike():
    """v1 (one array through the scalar primitives) and the batched group
    give the same span tree shape under one request each."""
    x, policy = field((6, 9, 11)), ExecPolicy(backend="jax")
    with trace.recording():
        encode.encode_array(x, 1e-4, policy=policy)           # v1
        Codec(eb=1e-4, chunk_elems=3 * 9 * 11, version=2).compress(
            x, policy)                                        # 2 chunks, 1 group
    roots = [r for r in trace.records() if r.parent is None]
    assert [r.name for r in roots] == ["encode", "encode"]
    per = [[r for r in trace.records() if r.request == root.id]
           for root in roots]
    t1, t2 = tree(per[0]), tree(per[1])
    assert set(t1) == set(t2)
    # one phase span per (level, dim) phase of the array, in each request
    for recs in per:
        phases = [(r.attrs["level"], r.attrs["dim"]) for r in recs
                  if r.name == "sweep.phase"]
        assert len(phases) == len(set(phases)) and (1, 2) in phases


def test_threads_keep_their_own_requests_and_lose_no_record(monkeypatch):
    """Spans of concurrent threads nest per thread; kept + dropped counts
    every span ended (a small buffer forces drops)."""
    import sys
    import threading
    from collections import deque

    threads, per = 32, 40
    monkeypatch.setattr(trace, "_buf", deque(maxlen=500))
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with trace.recording():
            def work(k):
                for _ in range(per):
                    with trace.request(f"req{k}"):
                        with trace.span("stage"):
                            trace.count("n")
            ts = [threading.Thread(target=work, args=(k,))
                  for k in range(threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    recs = trace.records()
    assert len(recs) + trace.dropped() == threads * per * 2
    by = {r.id: r for r in recs}
    for r in recs:
        if r.name == "stage":
            assert r.counts == {"n": 1}
            if r.parent in by:
                assert by[r.parent].name.startswith("req")
                assert r.request == r.parent
    assert trace._on_duration not in listeners()
