"""Serving-tier load generator: the paper's many-readers workload.

Drives the continuous-batching :class:`repro.serving.RetrievalServer`
with a >=16-request mixed-fidelity workload (coarse previews, tight
bounds, byte budgets, bitrates, full reads, refine chains) over several
archives, in three execution modes:

* ``percall``   — no coalescing, no cache: every request is planned and
  decoded as its own group (the per-request baseline);
* ``coalesced`` — cross-request coalescing: same-shape chunk jobs from
  different requests share one batched kernel launch per scheduler tick;
* ``cached``    — coalescing plus the shared :class:`PlaneCache`:
  requests reuse each other's decoded plane prefixes.

Recorded per mode: wall time, requests/sec, p50/p99 request latency,
backend-primitive dispatch counts (``decode_level`` / ``reconstruct`` /
``dedup_reuse`` from the server's counters — backend-independent), the
Pallas launch counts from ``repro.kernels.dispatch``, and cache
hit/miss/byte accounting.  Claim checks pin the serving wins: nonzero
cache-hit rate with byte accounting, strictly fewer dispatches coalesced
than per-call, and every served reconstruction bit-identical to a
private uncached session at the same fidelity (refine chains compared
against a private session walking the same ladder).  Results go to
``BENCH_serve.json`` (a CI artifact).

A fourth section benchmarks the storage layout itself: the same refine
ladder over IPC2 (chunk-major) and IPC3 (plane-major) archives of one
array, with every byte-range request logged through a
:class:`~repro.core.bytesource.CountingSource`.  Claim checks pin the
v3 layout win — monotone, single-run contiguous reads, strictly fewer
coalesced ranges and less seek distance than v2.

A fifth section runs that refine ladder over real loopback HTTP through
:class:`~repro.core.remote.HTTPSource` against the test suite's
in-process range server — once clean, once with a dropped GET — pinning
bit parity with a local session, one coalesced data run on the wire,
and retry-path recovery.

CPU caveat (same as ``backend_speed``): off-TPU the jax backend runs
Pallas in interpret mode, so wall-clock favors numpy and the dispatch /
cache counters are the trendable metrics.

  PYTHONPATH=src python -m benchmarks.serve_bench [--requests 18]
      [--backend jax] [--json-out BENCH_serve.json]
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from .common import csv_row
from repro import Archive, Codec, ExecPolicy, Fidelity
from repro.core.bytesource import CountingSource
from repro.kernels import dispatch
from repro.serving import PlaneCache, RetrievalServer

JSON_OUT = "BENCH_serve.json"
CACHE_BYTES = 32 << 20


def _archives():
    """Three small archives spanning the container shapes the scheduler
    handles: a v2 uneven chunk grid, a v3 plane-major even grid, and a
    v1 single slab."""
    rng = np.random.default_rng(11)
    fields = {
        "turb": np.cumsum(rng.standard_normal((96, 96)), axis=0) / 10.0,
        "wave": (np.sin(np.linspace(0, 9, 64 * 64)).reshape(64, 64)
                 * 3.0),
        "blob": np.exp(-((np.mgrid[0:64, 0:64] - 32) ** 2
                         ).sum(0) / 300.0),
    }
    codecs = {
        "turb": Codec(eb=1e-5, chunk_elems=2048),
        "wave": Codec(eb=1e-5, chunk_elems=1024, version=3),
        "blob": Codec(eb=1e-5),              # v1: single slab
    }
    return {name: codecs[name].compress(x) for name, x in fields.items()}


def _workload(n_requests: int):
    """The mixed-fidelity request mix, as (archive_id, Fidelity, chain)
    tuples; ``chain`` marks a refine riding on the previous request for
    the same archive.  Cycled to ``n_requests`` entries."""
    base = [
        ("turb", Fidelity.error_bound(1e-2), False),
        ("turb", Fidelity.error_bound(1e-2), False),   # duplicate consumer
        ("turb", Fidelity.error_bound(1e-4), False),
        ("turb", Fidelity.full(), True),               # refine the preview
        ("wave", Fidelity.error_bound(1e-2), False),
        ("wave", Fidelity.bitrate(4.0), False),
        ("wave", Fidelity.full(), False),
        ("blob", Fidelity.error_bound(1e-3), False),
        ("blob", Fidelity.max_bytes(3000), False),
        ("blob", Fidelity.full(), True),               # refine the budget read
        ("turb", Fidelity.bitrate(6.0), False),
        ("wave", Fidelity.error_bound(1e-2), False),   # duplicate consumer
    ]
    return [base[i % len(base)] for i in range(n_requests)]


def _submit_all(server, workload):
    """Queue the workload; refine chains attach to the latest earlier
    request for the same archive."""
    reqs, last = [], {}
    for archive_id, fid, chain in workload:
        parent = last.get(archive_id) if chain else None
        req = server.submit(archive_id, fid, refine_of=parent)
        last[archive_id] = req
        reqs.append(req)
    return reqs


def _reference_bits(archives, workload):
    """Private uncached numpy sessions, one per request; refine chains
    walk the same ladder inside one session."""
    outs, last_session = [], {}
    for archive_id, fid, chain in workload:
        if chain and archive_id in last_session:
            session = last_session[archive_id]
        else:
            session = archives[archive_id].open()
        outs.append(session.read(fid))
        last_session[archive_id] = session
    return outs


def _percentile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q))


def _run_mode(mode, archives, workload, policy):
    cache = PlaneCache(max_bytes=CACHE_BYTES) if mode == "cached" else None
    server = RetrievalServer(policy=policy, cache=cache,
                             coalesce=mode != "percall")
    for name, arc in archives.items():
        server.add_archive(name, arc)
    reqs = _submit_all(server, workload)
    with dispatch.measure() as launches:
        t0 = time.perf_counter()
        server.drain()
        dt = time.perf_counter() - t0
    assert all(r.status == "done" for r in reqs), \
        [(r.req_id, r.error) for r in reqs if r.status != "done"]
    lat = [r.latency_s for r in reqs]
    record = dict(
        mode=mode, requests=len(reqs), seconds=dt,
        req_per_s=len(reqs) / dt, ticks=server.ticks,
        p50_latency_s=_percentile(lat, 50),
        p99_latency_s=_percentile(lat, 99),
        counters=dict(server.counters),
        primitive_dispatches=sum(v for k, v in server.counters.items()
                                 if k != "dedup_reuse"),
        pallas_launches=sum(launches.values()),
        bytes_read=[int(r.bytes_read) for r in reqs],
    )
    if cache is not None:
        record["cache"] = cache.stats()
    return record, [r.result for r in reqs]


LAYOUT_LADDER = [1e-2, 1e-3, 1e-4, 1e-5]


def _layout_bench():
    """IPC3 plane-major layout vs IPC2 chunk-major, as the storage tier
    sees it: the same refine ladder over the same array, with every
    byte-range request logged by a :class:`CountingSource`.  Recorded per
    version: request count, coalesced run count, and total backward /
    gap seek distance over the data section.  The claim is the format's
    reason to exist — the v3 ladder reads strictly fewer contiguous
    ranges (one run, monotone) than v2's per-chunk scatter."""
    rng = np.random.default_rng(23)
    x = np.cumsum(rng.standard_normal((96, 96)), axis=0) / 10.0
    fids = [Fidelity.error_bound(E) for E in LAYOUT_LADDER]
    record, outs = {}, {}
    for name, codec in (
            ("v2", Codec(eb=1e-5, chunk_elems=2048)),
            ("v3", Codec(eb=1e-5, chunk_elems=2048, version=3))):
        arc = codec.compress(x)
        cs = CountingSource(arc.tobytes())
        session = Archive.from_source(cs).open()
        for f in fids:
            out = session.read(f)
        outs[name] = out
        header_end = arc._meta.header_end
        data = [r for r in cs.requests if r[0] >= header_end]
        runs = CountingSource(b"")
        runs.requests = data
        record[name] = dict(
            archive_bytes=arc.nbytes, session_bytes_read=session.bytes_read,
            data_requests=len(data), coalesced_runs=len(runs.coalesced()),
            monotone=runs.monotone(), seek_distance=runs.seek_distance)
    checks = [
        ("serve_v3_monotone_contiguous", "ladder", "layout",
         record["v3"]["monotone"] and record["v3"]["coalesced_runs"] == 1),
        ("serve_v3_fewer_ranges", "ladder", "layout",
         record["v3"]["coalesced_runs"] < record["v2"]["coalesced_runs"]
         and record["v3"]["seek_distance"] < record["v2"]["seek_distance"]),
        ("serve_v3_ladder_bits_bounded", "ladder", "layout",
         float(np.abs(outs["v3"] - x).max()) <= LAYOUT_LADDER[-1]
         and float(np.abs(outs["v2"] - x).max()) <= LAYOUT_LADDER[-1]),
    ]
    row = csv_row(
        "serve/layout/v3_vs_v2", 0.0,
        f"v2_runs={record['v2']['coalesced_runs']};"
        f"v3_runs={record['v3']['coalesced_runs']};"
        f"v2_seek={record['v2']['seek_distance']};"
        f"v3_seek={record['v3']['seek_distance']}")
    return record, checks, row


REMOTE_LADDER = [1e-2, 1e-3, 1e-4, 1e-5]


def _remote_bench():
    """The same refine ladder pulled over real (loopback) HTTP through
    :class:`~repro.core.remote.HTTPSource`, against the in-process range
    server the network test suites use.  Two passes over one v3 archive:
    a clean server, and one that drops a connection mid-ladder so the
    retry/backoff path is on the measured path.  Recorded: wall time,
    GET counts, wire bytes vs archive bytes, and retry counts.  Claim
    checks pin the remote story — bit parity with a local BufferSource
    session, one coalesced data run over the wire, and fault recovery
    with a nonzero retry count."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tests"))
    from range_server import RangeHTTPServer, ServerFault

    from repro.core.remote import HTTPSource

    rng = np.random.default_rng(23)
    x = np.cumsum(rng.standard_normal((96, 96)), axis=0) / 10.0
    arc = Codec(eb=1e-5, chunk_elems=2048, version=3).compress(x)
    buf = arc.tobytes()
    header_end = int(arc._meta.header_end)
    fids = [Fidelity.error_bound(E) for E in REMOTE_LADDER]
    local = Archive.frombytes(buf).open()
    reference = [local.read(f) for f in fids]

    record = {}
    outs = {}
    for name, faults in (
            ("clean", None),
            ("faulted", [ServerFault("drop", at=3)])):
        srv = RangeHTTPServer(buf, faults=faults)
        try:
            src = HTTPSource(srv.url, timeout=10.0, backoff=0.01)
            session = Archive.from_source(src).open()
            t0 = time.perf_counter()
            for f in fids:
                out = session.read(f)
            dt = time.perf_counter() - t0
            outs[name] = out
            data = [r for r in src.requests if r[0] >= header_end]
            runs = CountingSource(b"")
            runs.requests = data
            record[name] = dict(
                seconds=dt, archive_bytes=len(buf),
                session_bytes_read=session.bytes_read,
                gets=srv.n_gets, retries=src.retry_count,
                wire_bytes=src.wire_bytes,
                data_coalesced_runs=len(runs.coalesced()),
                monotone=runs.monotone())
            src.close()
        finally:
            srv.stop()
    checks = [
        ("serve_remote_bits_match_local", "ladder", "remote",
         all(np.array_equal(outs[n], reference[-1]) for n in outs)),
        ("serve_remote_one_data_run", "ladder", "remote",
         record["clean"]["data_coalesced_runs"] == 1
         and record["clean"]["monotone"]),
        ("serve_remote_fault_recovered", "ladder", "remote",
         record["faulted"]["retries"] > 0),
        # no data byte crosses the wire twice: wire volume is bounded by
        # the framing/header region plus the bytes the session planned
        ("serve_remote_no_refetch", "ladder", "remote",
         record["clean"]["wire_bytes"]
         <= header_end + record["clean"]["session_bytes_read"] + 16),
    ]
    row = csv_row(
        "serve/remote/http_ladder", record["clean"]["seconds"] * 1e6,
        f"gets={record['clean']['gets']};"
        f"wire={record['clean']['wire_bytes']};"
        f"faulted_retries={record['faulted']['retries']}")
    return record, checks, row


def run(scale=None, n_requests: int = 18, backend: str = "jax",
        json_out: str = JSON_OUT):
    if n_requests < 16:
        raise SystemExit(f"--requests must be >= 16, got {n_requests}")
    archives = _archives()
    workload = _workload(n_requests)
    policy = ExecPolicy(backend=backend)
    rows, checks, records = [], [], []
    reference = _reference_bits(archives, workload)

    results = {}
    for mode in ("percall", "coalesced", "cached"):
        record, outs = _run_mode(mode, archives, workload, policy)
        records.append(record)
        results[mode] = outs
        derived = (f"req_per_s={record['req_per_s']:.1f};"
                   f"p50={record['p50_latency_s'] * 1e3:.1f}ms;"
                   f"p99={record['p99_latency_s'] * 1e3:.1f}ms;"
                   f"dispatches={record['primitive_dispatches']}")
        if "cache" in record:
            derived += (f";hit_rate={record['cache']['hit_rate']:.2f};"
                        f"fetch_saved={record['cache']['fetch_bytes_saved']}")
        rows.append(csv_row(f"serve/{n_requests}req/{mode}",
                            record["seconds"] * 1e6, derived))
        print(rows[-1])

    # (c) served bits == private uncached per-session bits, every mode
    for mode, outs in results.items():
        ok = all(np.array_equal(a, b) for a, b in zip(outs, reference))
        checks.append((f"serve_bits_match_sessions_{mode}",
                       f"{n_requests}req", "serve", ok))
    # (b) coalescing strictly reduces dispatch counts vs per-request
    percall, coalesced, cached = records
    checks.append(("serve_coalesce_fewer_dispatches", f"{n_requests}req",
                   "serve", coalesced["primitive_dispatches"]
                   < percall["primitive_dispatches"]))
    # (a) the shared cache sees real reuse, with byte accounting
    cstats = cached["cache"]
    checks.append(("serve_cache_hits", f"{n_requests}req", "serve",
                   cstats["hits"] > 0 and cstats["hit_rate"] > 0))
    checks.append(("serve_cache_byte_accounting", f"{n_requests}req",
                   "serve", cstats["bytes_cached"] > 0
                   and cstats["hit_bytes"] > 0))
    # (d) IPC3 plane-major layout: strictly fewer, monotone, contiguous
    # byte ranges than v2 for the same refine ladder
    layout_record, layout_checks, layout_row = _layout_bench()
    checks.extend(layout_checks)
    rows.append(layout_row)
    print(layout_row)
    # (e) the same ladder over real loopback HTTP: bit parity, one range
    # per rung on the wire, and the retry path survives a dropped GET
    remote_record, remote_checks, remote_row = _remote_bench()
    checks.extend(remote_checks)
    rows.append(remote_row)
    print(remote_row)

    if json_out:
        with open(json_out, "w") as f:
            json.dump(dict(
                requests=n_requests, backend=backend,
                cache_max_bytes=CACHE_BYTES,
                workload=[(a, repr(f), c) for a, f, c in workload],
                records=records, layout=layout_record,
                remote=remote_record,
                checks=[dict(name=c[0], case=c[1], op=c[2], ok=bool(c[3]))
                        for c in checks]), f, indent=2)
        print(f"wrote {json_out} ({len(records)} mode records)")
    return rows, checks


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=18,
                    help="workload size (>= 16)")
    ap.add_argument("--backend", default="jax",
                    choices=["numpy", "jax"],
                    help="server ExecPolicy backend")
    ap.add_argument("--json-out", default=JSON_OUT,
                    help="JSON artifact path ('' disables)")
    args = ap.parse_args()
    _, checks = run(n_requests=args.requests, backend=args.backend,
                    json_out=args.json_out)
    for name, ds, op, ok in checks:
        print(f"check {name}[{ds}/{op}]: {'ok' if ok else 'FAILED'}")
    if not all(c[-1] for c in checks):
        raise SystemExit(1)


if __name__ == "__main__":
    from pathlib import Path

    from repro import compile_cache
    compile_cache.enable(Path(__file__).resolve().parents[1])
    main()
