"""Codec backend throughput: numpy reference vs jax/Pallas kernels.

Reports compress AND decode throughput for both backends on a >=2^20-element
field (the acceptance smoke case), plus the chunked variant in BOTH
execution modes — the per-chunk loop and the batched shape-group engine
(``ExecPolicy(batch_chunks=...)``), whose ``jax.vmap``-ed dispatches are
the roadmap's equal-shape chunk batching, plus — whenever more than one
device is visible — a sharded entry (``ExecPolicy(shard="auto")``) that
runs the chunk grid data-parallel over the local device mesh and records
sharded vs single-device MB/s and per-device launch fan-out, plus a
fused-decode entry that races the ``jax`` backend's decode megakernel
(one ``decode_fused`` + one whole-level recon launch per level) against
the pre-fusion ``jax_unfused`` baseline, recording MB/s, dispatches and
launches per level.  Everything drives the object API (``Codec`` / ``Archive`` / ``Fidelity`` /
``ExecPolicy``), so the benchmark doubles as its smoke test.  Kernel
dispatch counts for all modes come from ``repro.kernels.dispatch``, so the
batched-vs-looped launch-count reduction (and the sharded fan-out) is a
recorded, trendable number, not a claim.  Decode is measured
as the two retrieval operations the paper optimizes (§5): a full-precision
read and one incremental ``refine`` step (Algorithm 2's delta cascade) on
top of a coarse first retrieval.

CPU caveat: off-TPU the Pallas kernels run in *interpret mode*, a
correctness harness, so the jax numbers on CPU measure dispatch overhead,
not kernel speed; parity of the emitted bytes (encode) and reconstructed
bits (decode) is asserted regardless.  On TPU the same path compiles to
Mosaic.  That cuts both ways for the chunk-batch entry: the vmapped
interpreter can make *batched wall-clock slower on CPU* even as launches
collapse — off-TPU the dispatch counts are the trendable metric, the MB/s
columns become meaningful on real hardware.

Usage:
  PYTHONPATH=src python -m benchmarks.backend_speed [--n 1048576] [--full]
      [--json-out BENCH_decode.json] [--json-out-compress BENCH_compress.json]

CI-smoke mode (default) runs one warm repetition per backend; --full adds
a second field and best-of-3 timing.  The decode measurements are written
to ``BENCH_decode.json`` and the compress measurements (including the
chunk-batch speed entry) to ``BENCH_compress.json`` (both uploaded as CI
artifacts).
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from .common import csv_row, timed
from repro import Archive, Codec, ExecPolicy, Fidelity
from repro.core import chunk_bounds
from repro.kernels import dispatch

JSON_OUT = "BENCH_decode.json"
JSON_OUT_COMPRESS = "BENCH_compress.json"

#: coarse-then-refine targets for the Algorithm 2 timing, relative to eb
REFINE_COARSE = 1e3
REFINE_FINE = 1e1

#: chunk size for the chunk-batch entries (16 chunks on the 2^20 field)
CHUNK_ELEMS = 1 << 16


def _field(n: int) -> np.ndarray:
    side = int(np.sqrt(n))
    i, j = np.meshgrid(np.arange(side), np.arange(n // side), indexing="ij")
    return np.sin(i * 0.01) * np.cos(j * 0.013) + 1e-3 * np.sin(i * j * 1e-4)


def _decode_rows(x: np.ndarray, eb: float, buf: bytes, case: str,
                 repeat: int, rows, records, outs):
    """Measure full read + one refine step for both decode backends."""
    archive = Archive(buf)
    for bk in ("numpy", "jax"):
        policy = ExecPolicy(backend=bk)
        if bk == "jax":
            # warm every jit cache entry the timed calls will hit — incl.
            # the refine ladder, whose plane prefixes are distinct static
            # args of the unpack kernel (a cold refine would time tracing)
            archive.open(policy).read()
            warm = archive.open(policy)
            warm.read(Fidelity.error_bound(REFINE_COARSE * eb))
            warm.refine(Fidelity.error_bound(REFINE_FINE * eb))
        out, dt = timed(lambda: archive.open(policy).read(), repeat=repeat)
        outs.setdefault(case, {})[bk] = out
        mbps = x.nbytes / dt / 1e6
        rows.append(csv_row(f"backend_speed/{case}/{bk}/decompress",
                            dt * 1e6, f"MBps={mbps:.1f}"))
        print(rows[-1])
        records.append(dict(case=case, backend=bk, op="decompress",
                            seconds=dt, mbps=mbps, bytes=len(buf)))

        # one refine step: coarse retrieval outside the clock, then time
        # the incremental delta cascade to the tighter bound
        session = archive.open(policy)
        session.read(Fidelity.error_bound(REFINE_COARSE * eb))
        _, dt = timed(session.refine, Fidelity.error_bound(REFINE_FINE * eb),
                      repeat=1)
        mbps = x.nbytes / dt / 1e6
        rows.append(csv_row(f"backend_speed/{case}/{bk}/refine",
                            dt * 1e6,
                            f"MBps={mbps:.1f};"
                            f"bytes_read={session.bytes_read}"))
        print(rows[-1])
        records.append(dict(case=case, backend=bk, op="refine",
                            seconds=dt, mbps=mbps,
                            bytes_read=int(session.bytes_read)))


def _fused_rows(x: np.ndarray, eb: float, buf: bytes, rows, checks,
                dec_records):
    """The fused-decode megakernel entry: ``jax`` (fused decode path) vs
    ``jax_unfused`` (the pre-fusion per-phase pipeline, kept registered as
    the baseline) on the v1 2^20 archive.  Records MB/s, total dispatches,
    per-kernel launch counts and launches per level.  The fused path must
    issue strictly FEWER dispatches (a structural property, asserted even
    in interpret mode) and reach >= 2x the unfused MB/s on this case.
    """
    from repro.core import open_archive

    archive = Archive(buf)
    L = open_archive(buf).meta.L
    stats, outs = {}, {}
    for bk in ("jax_unfused", "jax"):
        policy = ExecPolicy(backend=bk)
        archive.open(policy).read()  # warm jit caches out of the timing
        warm = archive.open(policy)
        warm.read(Fidelity.error_bound(REFINE_COARSE * eb))
        warm.refine(Fidelity.error_bound(REFINE_FINE * eb))
        with dispatch.measure() as d:
            outs[bk], dt = timed(lambda: archive.open(policy).read(),
                                 repeat=1)
        nd = sum(d.values())
        mbps = x.nbytes / dt / 1e6
        rows.append(csv_row(f"backend_speed/fused_decode/{bk}/decompress",
                            dt * 1e6, f"MBps={mbps:.1f};dispatches={nd};"
                            f"per_level={nd / L:.1f}"))
        print(rows[-1])
        dec_records.append(dict(case="fused_decode", backend=bk,
                                op="decompress", seconds=dt, mbps=mbps,
                                dispatches=nd, levels=L,
                                dispatches_per_level=nd / L,
                                dispatches_by_kernel=dict(d)))
        stats[bk] = (mbps, nd)

        session = archive.open(policy)
        session.read(Fidelity.error_bound(REFINE_COARSE * eb))
        with dispatch.measure() as d:
            _, dt = timed(session.refine,
                          Fidelity.error_bound(REFINE_FINE * eb), repeat=1)
        nd = sum(d.values())
        mbps = x.nbytes / dt / 1e6
        rows.append(csv_row(f"backend_speed/fused_decode/{bk}/refine",
                            dt * 1e6, f"MBps={mbps:.1f};dispatches={nd}"))
        print(rows[-1])
        dec_records.append(dict(case="fused_decode", backend=bk, op="refine",
                                seconds=dt, mbps=mbps, dispatches=nd,
                                levels=L, dispatches_per_level=nd / L,
                                dispatches_by_kernel=dict(d)))
    checks.append(("fused_parity_bits", "fused_decode", "decompress",
                   bool(np.array_equal(outs["jax"], outs["jax_unfused"]))))
    checks.append(("fused_fewer_dispatches", "fused_decode", "decompress",
                   stats["jax"][1] < stats["jax_unfused"][1]))
    checks.append(("fused_2x_mbps", "fused_decode", "decompress",
                   stats["jax"][0] >= 2.0 * stats["jax_unfused"][0]))


def _chunk_batch_rows(x: np.ndarray, eb: float, rows, checks,
                      comp_records, dec_records):
    """The chunk-batch speed entry: batched vs looped dispatch counts and
    MB/s for both codec directions on a CHUNK_ELEMS-slabbed archive."""
    codec = Codec(eb=eb, chunk_elems=CHUNK_ELEMS)
    n_chunks = len(chunk_bounds(x.shape, CHUNK_ELEMS))
    bufs = {}
    for mode, flag in (("looped", False), ("batched", True)):
        policy = ExecPolicy(backend="jax", batch_chunks=flag)
        codec.compress(x, policy)  # warm jit caches out of the timing
        with dispatch.measure() as d:
            arc, dt = timed(codec.compress, x, policy, repeat=1)
        bufs[mode] = arc.tobytes()
        mbps = x.nbytes / dt / 1e6
        nd = sum(d.values())
        rows.append(csv_row(f"backend_speed/chunk_batch/{mode}/compress",
                            dt * 1e6,
                            f"MBps={mbps:.1f};dispatches={nd}"))
        print(rows[-1])
        comp_records.append(dict(case="chunk_batch", mode=mode,
                                 op="compress", seconds=dt, mbps=mbps,
                                 chunks=n_chunks, dispatches=nd,
                                 dispatches_by_kernel=d))

        coarse = Fidelity.error_bound(REFINE_COARSE * eb)
        arc.open(policy).read(coarse)  # warm
        with dispatch.measure() as d:
            _, dt = timed(lambda: arc.open(policy).read(coarse), repeat=1)
        mbps = x.nbytes / dt / 1e6
        nd = sum(d.values())
        rows.append(csv_row(f"backend_speed/chunk_batch/{mode}/retrieve",
                            dt * 1e6,
                            f"MBps={mbps:.1f};dispatches={nd}"))
        print(rows[-1])
        dec_records.append(dict(case="chunk_batch", mode=mode, op="retrieve",
                                seconds=dt, mbps=mbps, chunks=n_chunks,
                                dispatches=nd, dispatches_by_kernel=d))
    checks.append(("chunk_batch_parity_bytes", "chunked", "compress",
                   bufs["looped"] == bufs["batched"]))
    loop_d = sum(comp_records[-2]["dispatches_by_kernel"].values())
    bat_d = sum(comp_records[-1]["dispatches_by_kernel"].values())
    checks.append(("chunk_batch_fewer_dispatches", "chunked", "compress",
                   bat_d < loop_d))


def _sharded_rows(x: np.ndarray, eb: float, rows, checks,
                  comp_records, dec_records):
    """Sharded-vs-single-device entry: both codec directions over the
    chunk grid on a mesh of every local device (run the benchmark under
    XLA_FLAGS=--xla_force_host_platform_device_count=8 for a forced CPU
    mesh).  Byte/bit parity is asserted; on CPU the MB/s delta measures
    shard_map + interpret-mode overhead, on real hardware it measures the
    scale-out.  Skipped (one informational record) on single-device hosts.
    """
    import jax
    n_dev = jax.device_count()
    if n_dev < 2:
        comp_records.append(dict(case="sharded", mode="skipped",
                                 op="compress", devices=n_dev))
        print("backend_speed/sharded: single device visible, skipped "
              "(set XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        return
    codec = Codec(eb=eb, chunk_elems=CHUNK_ELEMS)
    n_chunks = len(chunk_bounds(x.shape, CHUNK_ELEMS))
    bufs, outs = {}, {}
    for mode, shard in (("single", None), ("sharded", "auto")):
        policy = ExecPolicy(backend="jax", shard=shard)
        codec.compress(x, policy)  # warm jit caches out of the timing
        with dispatch.measure() as d, dispatch.measure_devices() as dd:
            arc, dt = timed(codec.compress, x, policy, repeat=1)
        bufs[mode] = arc.tobytes()
        mbps = x.nbytes / dt / 1e6
        rows.append(csv_row(f"backend_speed/sharded/{mode}/compress",
                            dt * 1e6, f"MBps={mbps:.1f};devices="
                            f"{n_dev if shard else 1};"
                            f"dispatches={sum(d.values())};"
                            f"device_launches={sum(dd.values())}"))
        print(rows[-1])
        comp_records.append(dict(case="sharded", mode=mode, op="compress",
                                 seconds=dt, mbps=mbps, chunks=n_chunks,
                                 devices=n_dev if shard else 1,
                                 dispatches=sum(d.values()),
                                 device_launches=sum(dd.values()),
                                 dispatches_by_kernel=d))

        coarse = Fidelity.error_bound(REFINE_COARSE * eb)
        arc.open(policy).read(coarse)  # warm
        with dispatch.measure() as d, dispatch.measure_devices() as dd:
            outs[mode], dt = timed(lambda: arc.open(policy).read(coarse),
                                   repeat=1)
        mbps = x.nbytes / dt / 1e6
        rows.append(csv_row(f"backend_speed/sharded/{mode}/retrieve",
                            dt * 1e6, f"MBps={mbps:.1f};devices="
                            f"{n_dev if shard else 1};"
                            f"dispatches={sum(d.values())};"
                            f"device_launches={sum(dd.values())}"))
        print(rows[-1])
        dec_records.append(dict(case="sharded", mode=mode, op="retrieve",
                                seconds=dt, mbps=mbps, chunks=n_chunks,
                                devices=n_dev if shard else 1,
                                dispatches=sum(d.values()),
                                device_launches=sum(dd.values()),
                                dispatches_by_kernel=d))
    checks.append(("sharded_parity_bytes", "sharded", "compress",
                   bufs["single"] == bufs["sharded"]))
    checks.append(("sharded_parity_bits", "sharded", "retrieve",
                   bool(np.array_equal(outs["single"], outs["sharded"]))))


def run(scale=None, n: int = 1 << 20, smoke: bool = True,
        json_out: str = JSON_OUT, json_out_compress: str = JSON_OUT_COMPRESS):
    rows, checks, records, comp_records = [], [], [], []
    if n < 1 << 20:
        raise SystemExit(f"--n must be >= {1 << 20} (2^20) elements, got {n}")
    x = _field(n)
    eb = 1e-5
    repeat = 1 if smoke else 3
    variants = [
        ("numpy", Codec(eb=eb), ExecPolicy(backend="numpy")),
        ("jax", Codec(eb=eb), ExecPolicy(backend="jax")),
        ("jax_chunked", Codec(eb=eb, chunk_elems=1 << 18),
         ExecPolicy(backend="jax")),
    ]
    bufs = {}
    for name, codec, policy in variants:
        if name.startswith("jax"):
            codec.compress(x, policy)  # warm the jit caches out of timing
        arc, dt = timed(codec.compress, x, policy, repeat=repeat)
        bufs[name] = arc.tobytes()
        mbps = x.nbytes / dt / 1e6
        rows.append(csv_row(f"backend_speed/{x.size}el/{name}/compress",
                            dt * 1e6,
                            f"MBps={mbps:.1f};bytes={arc.nbytes}"))
        print(rows[-1])
        comp_records.append(dict(case=f"{x.size}el", variant=name,
                                 op="compress", seconds=dt, mbps=mbps,
                                 bytes=arc.nbytes))
    checks.append(("backend_parity_bytes", f"{x.size}el", "compress",
                   bufs["numpy"] == bufs["jax"]))

    # decode direction: v1 archive and the chunked v2 archive
    outs = {}
    _decode_rows(x, eb, bufs["numpy"], f"{x.size}el_v1", repeat, rows,
                 records, outs)
    _decode_rows(x, eb, bufs["jax_chunked"], f"{x.size}el_v2", repeat, rows,
                 records, outs)
    for case, by_bk in outs.items():
        checks.append(("decode_parity_bits", case, "decompress",
                       bool(np.array_equal(by_bk["numpy"], by_bk["jax"]))))

    # fused decode megakernel vs the pre-fusion jax baseline
    _fused_rows(x, eb, bufs["numpy"], rows, checks, records)

    # chunk-batch speed entry: batched vs looped dispatch counts + MB/s
    _chunk_batch_rows(x, eb, rows, checks, comp_records, records)

    # sharded entry: chunk grid over a device mesh vs single device
    _sharded_rows(x, eb, rows, checks, comp_records, records)

    if not smoke:
        y = _field(1 << 22)
        for name, codec, policy in variants:
            arc, dt = timed(codec.compress, y, policy, repeat=1)
            rows.append(csv_row(f"backend_speed/{y.size}el/{name}/compress",
                                dt * 1e6,
                                f"MBps={y.nbytes / dt / 1e6:.1f}"))
            print(rows[-1])
    # each artifact carries only the checks about the ops it records, so a
    # per-file "all ok" read is unambiguous about which direction failed
    def _check_dicts(ops):
        return [dict(name=c[0], case=c[1], op=c[2], ok=bool(c[3]))
                for c in checks if c[2] in ops]

    import jax

    dev = jax.devices()[0]
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=jax.device_count())
    if json_out:
        with open(json_out, "w") as f:
            json.dump(dict(n=int(x.size), eb=eb, device=device,
                           refine_bounds=[REFINE_COARSE * eb,
                                          REFINE_FINE * eb],
                           records=records,
                           checks=_check_dicts(("decompress", "retrieve"))),
                      f, indent=2)
        print(f"wrote {json_out} ({len(records)} decode records)")
    if json_out_compress:
        with open(json_out_compress, "w") as f:
            json.dump(dict(n=int(x.size), eb=eb, device=device,
                           chunk_elems=CHUNK_ELEMS,
                           records=comp_records,
                           checks=_check_dicts(("compress",))),
                      f, indent=2)
        print(f"wrote {json_out_compress} ({len(comp_records)} compress "
              "records)")
    return rows, checks


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=1 << 20,
                    help="elements in the benchmark field (>= 2^20)")
    ap.add_argument("--full", action="store_true",
                    help="best-of-3 timing plus a 4M-element field")
    ap.add_argument("--json-out", default=JSON_OUT,
                    help="decode-benchmark JSON artifact path ('' disables)")
    ap.add_argument("--json-out-compress", default=JSON_OUT_COMPRESS,
                    help="compress-benchmark JSON artifact path "
                         "('' disables)")
    args = ap.parse_args()
    _, checks = run(n=args.n, smoke=not args.full, json_out=args.json_out,
                    json_out_compress=args.json_out_compress)
    for name, ds, op, ok in checks:
        print(f"check {name}[{ds}/{op}]: {'ok' if ok else 'FAILED'}")
    if not all(c[-1] for c in checks):
        raise SystemExit(1)


if __name__ == "__main__":
    from pathlib import Path

    from repro import compile_cache
    compile_cache.enable(Path(__file__).resolve().parents[1])
    main()
