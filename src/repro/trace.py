"""Spans and counters of the program's host stages.

A span names one step of a call and times it::

    with trace.span("pack.zlib", stage="zlib"):
        ...
        trace.count("zlib_out_bytes", len(blob))

The ``stage`` attribute puts the span's time in one host stage of the
program; a span without one belongs to its nearest ancestor's stage, and
time under no staged span is host work no stage names
(``bench/stages.py`` reads it so, and only from this attribute).

Each span records its name, start and end (``time.perf_counter_ns``),
its parent span and its request: the id of the outermost span of the
thread, so every span of one ``Codec.compress`` shares the id of its
``encode`` span.  :func:`count` adds to the innermost open span of the
calling thread.  ``kernels.dispatch`` counts each kernel launch here too
(``launches``), and while a request is open every jaxpr JAX traces is
counted on the span that caused it (``traces``).

The recorder is on only while a JAX profiler trace runs
(``jax.profiler.start_trace`` ... ``stop_trace``) or inside a
:func:`recording` block.  Off, a span costs one predicate call and
records nothing.  On, each span is also a
``jax.profiler.TraceAnnotation``, so the stages appear in the profiler's
trace on the device's clock (Perfetto, TensorBoard), and a finished span
is kept in memory: :func:`records` returns them, oldest first, from a
buffer of :data:`CAPACITY` records that drops its oldest when full
(:func:`dropped` counts those).

An operator's view of one compress::

    with trace.recording():
        Codec(eb=1e-6, relative=True, chunk_elems=2**22).compress(x)
    for r in trace.records():
        print(r.name, (r.end_ns - r.start_ns) / 1e6, "ms", r.counts)
    trace.clear()
"""
from __future__ import annotations

import itertools
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager, nullcontext
from typing import Dict, Iterator, List, NamedTuple, Optional

#: records kept in memory; the oldest are dropped beyond this
CAPACITY = 1 << 16
#: JAX's monitoring event for one jaxpr traced
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


class Record(NamedTuple):
    """One finished span."""
    name: str
    id: int
    parent: Optional[int]      # None for the outermost span of a request
    request: int               # id of the request's outermost span
    start_ns: int              # time.perf_counter_ns()
    end_ns: int
    counts: Dict[str, int]
    attrs: Dict[str, object]


_buf: deque = deque(maxlen=CAPACITY)
_dropped = 0
_recording = 0             # depth of open recording() blocks
_ids = itertools.count(1)
_lock = threading.Lock()   # guards the module's counters and buffer
_open_requests = 0         # requests open in any thread (listener refcount)
_NULL = nullcontext()


class _Local(threading.local):
    def __init__(self):
        self.stack: List["_Span"] = []     # open spans, innermost last


_local = _Local()


def _profiler_flag():
    """JAX's own flag for a running profiler trace:
    ``jax._src.lib._profiler.TraceMe.is_enabled`` (JAX 0.9.0), a private
    name with no public equivalent; a JAX that moves it fails here, by
    name, and ``tests/test_trace.py`` pins it."""
    try:
        from jax._src.lib import _profiler
        return _profiler.TraceMe.is_enabled
    except (ImportError, AttributeError) as e:
        raise ImportError(
            "repro.trace needs jax._src.lib._profiler.TraceMe.is_enabled "
            "to tell whether a profiler trace runs; this JAX has no such "
            "name") from e


def _profiling() -> bool:
    """Whether a JAX profiler trace runs.  None can before JAX is
    imported, so importing this module does not import JAX; once it is,
    the name is rebound, once, to :func:`_profiler_flag`."""
    global _profiling
    if "jax" not in sys.modules:
        return False
    _profiling = _profiler_flag()
    return _profiling()


def active() -> bool:
    """Whether spans record now: a profiler trace runs, or a
    :func:`recording` block is open."""
    return _recording > 0 or _profiling()


def _on_duration(event: str, duration: float, **_) -> None:
    if event == TRACE_EVENT:
        count("traces")


def _listen(delta: int) -> None:
    """Keep the trace listener registered while any request is open."""
    global _open_requests
    import jax

    with _lock:
        before = _open_requests
        _open_requests += delta
        if before == 0 and _open_requests == 1:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
        elif before == 1 and _open_requests == 0:
            jax.monitoring.unregister_event_duration_listener(_on_duration)


class _Span:
    __slots__ = ("name", "attrs", "id", "parent", "request", "start",
                 "counts", "_ann")

    def __init__(self, name: str, attrs: dict):
        self.name, self.attrs = name, attrs

    def __enter__(self) -> "_Span":
        stack = _local.stack
        up = stack[-1] if stack else None
        self.id = next(_ids)
        self.parent = None if up is None else up.id
        self.request = self.id if up is None else up.request
        self.counts: Dict[str, int] = {}
        if up is None:
            _listen(1)
        import jax

        self._ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        stack.append(self)
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        _local.stack.pop()
        self._ann.__exit__(*exc)
        if self.parent is None:
            _listen(-1)
        _keep(Record(self.name, self.id, self.parent, self.request,
                     self.start, end, self.counts, self.attrs))


def _keep(rec: Record) -> None:
    global _dropped
    with _lock:
        if len(_buf) == _buf.maxlen:
            _dropped += 1
        _buf.append(rec)


def span(name: str, **attrs):
    """Context manager timing one step named ``name``; ``attrs`` (among
    them ``stage``) go into the record and onto the profiler's event."""
    if not active():
        return _NULL
    return _Span(name, attrs)


def request(name: str, **attrs):
    """A span that opens a request: like :func:`span` when no span is
    open in this thread, nothing inside one (so an entry point that calls
    another stays one request)."""
    if not active() or _local.stack:
        return _NULL
    return _Span(name, attrs)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` of the innermost open span (no-op
    when none is open)."""
    stack = _local.stack
    if stack:
        c = stack[-1].counts
        c[name] = c.get(name, 0) + n


@contextmanager
def recording() -> Iterator[None]:
    """Record spans inside the block, with or without the profiler."""
    global _recording
    with _lock:
        _recording += 1
    try:
        yield
    finally:
        with _lock:
            _recording -= 1


def records() -> List[Record]:
    """The finished spans kept, oldest first (a span is kept when it
    ends, so children come before their parent)."""
    with _lock:
        return list(_buf)


def dropped() -> int:
    """Records dropped since the last :func:`clear` because the buffer
    was full."""
    return _dropped


def clear() -> None:
    """Empty the buffer and the drop count."""
    global _dropped
    with _lock:
        _buf.clear()
        _dropped = 0
