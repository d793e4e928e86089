"""Checkpoint subsystem benchmark: coarse-first restart economics.

Builds a model-shaped IPCB bundle (transformer-ish smooth leaves + raw
norms) and measures the save/restore paths end to end, writing the
trendable artifact ``BENCH_ckpt.json``.  The claim checks gate the
subsystem's load-bearing promises:

* ``ckpt_coarse_byte_fraction``   — a coarse restore at the benchmark
  ``weight_error`` reads <= 35% of the bytes a full restore reads;
* ``ckpt_refine_never_rereads``   — refining coarse -> full fetches
  exactly the missing plane segments (session ``bytes_read`` delta ==
  ladder-prefix byte delta), and repeating a round reads zero;
* ``ckpt_remote_bit_identical``   — the same session over HTTP range
  requests, WITH one injected transient fault (a dropped GET mid-
  ladder), restores bit-identically to the local FileSource session;
* ``ckpt_parallel_encode_deterministic`` — 1-worker and 4-worker saves
  publish byte-identical bundles.

  PYTHONPATH=src python -m benchmarks.ckpt_bench [--json-out ...]
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile

import numpy as np

from .common import csv_row, timed

JSON_OUT = "BENCH_ckpt.json"
#: checkpoint fidelity: 1e-9 of each leaf's range is below f32 ulp for
#: most weights — the refined restore is effectively lossless, and the
#: deep bitplane ladder is exactly what makes the coarse prefix cheap
REL_EB = 1e-9
WEIGHT_ERR = 1e-2


def _model_leaves(scale=None):
    """Transformer-shaped float32 leaves with init-scaled Gaussian
    statistics (what real weight matrices look like: dense, noise-like,
    ~N(0, 1/d)) plus near-one norm scales stored raw."""
    s = 1.0 if scale is None else max(scale / 0.15, 0.25)
    d = int(256 * min(s, 2.0))
    rng = np.random.default_rng(0)

    def winit(shape, seed):
        r = np.random.default_rng(seed)
        return (r.standard_normal(shape) / np.sqrt(shape[-1])) \
            .astype(np.float32)

    leaves = {"embed.table": winit((4 * d, d), 1)}
    for i in range(4):
        leaves[f"blocks.{i}.attn.wqkv"] = winit((d, 3 * d), 10 + i)
        leaves[f"blocks.{i}.mlp.win"] = winit((d, 4 * d), 20 + i)
        leaves[f"blocks.{i}.norm.scale"] = \
            (1.0 + 0.01 * rng.standard_normal(d)).astype(np.float32)
    return leaves


def _write(path, leaves, workers):
    from repro.checkpoint import LeafSpec, write_bundle
    specs = [LeafSpec(lid=k, arr=v, dtype="float32", raw_nbytes=v.nbytes)
             for k, v in leaves.items()]
    return write_bundle(path, specs, step=1, rel_eb=REL_EB, interp="cubic",
                        workers=workers)


def _local_sessions(path, leaves):
    from repro.checkpoint import Bundle, RestoreSession
    record = {}
    with RestoreSession(Bundle.open(path)) as s:
        coarse, t_coarse = timed(s.restore, WEIGHT_ERR)
        record["coarse_bytes"] = b0 = s.bytes_read
        pos0 = s.ladder_positions()
        full, t_full = timed(s.restore, None)
        record["full_bytes"] = s.bytes_read
        planes = s.plane_bytes_between(pos0, s.ladder_positions())
        record["refine_delta_bytes"] = record["full_bytes"] - b0
        record["refine_plane_bytes"] = planes
        s.restore(None)
        record["reread_bytes"] = s.bytes_read - record["full_bytes"]
        record["coarse_seconds"] = t_coarse
        record["refine_seconds"] = t_full
        record["achieved_bound"] = s.achieved_bound
    record["byte_fraction"] = record["coarse_bytes"] / record["full_bytes"]
    for lid, ref in leaves.items():
        err = float(np.max(np.abs(coarse[lid] - ref)))
        rng_v = max(float(ref.max() - ref.min()), 1e-12)
        assert err <= WEIGHT_ERR * rng_v * 1.01 or ref.size <= 4096, \
            (lid, err)
    return coarse, full, record


def _remote_session(path, local_coarse, local_full):
    """The SAME restore over loopback HTTP with one dropped GET mid-
    ladder — the remote layer retries and the bits must not change."""
    from repro.checkpoint import Bundle, RestoreSession
    from tests.range_server import ServerFault, serve
    payload = open(path, "rb").read()
    record = {}
    with serve(payload, faults=[ServerFault("drop", at=2)]) as srv:
        with RestoreSession(Bundle.open(srv.url, timeout=5.0,
                                        backoff=0.01)) as s:
            coarse, t_coarse = timed(s.restore, WEIGHT_ERR)
            full, t_full = timed(s.restore, None)
            record["coarse_seconds"] = t_coarse
            record["refine_seconds"] = t_full
            src = s.bundle.source
            record["stats"] = getattr(src, "stats", lambda: {})()
        record["gets"] = sum(1 for m, _ in srv.log if m == "GET")
    ok = all(np.array_equal(coarse[k], local_coarse[k])
             for k in local_coarse) and \
        all(np.array_equal(full[k], local_full[k]) for k in local_full)
    return ok, record


def run(scale=None, json_out: str = JSON_OUT):
    rows, checks = [], []
    leaves = _model_leaves(scale)
    raw_bytes = sum(v.nbytes for v in leaves.values())
    with tempfile.TemporaryDirectory() as td:
        p1 = os.path.join(td, "w1.ckpt")
        p4 = os.path.join(td, "w4.ckpt")
        man, t_w1 = timed(_write, p1, leaves, 1)
        _, t_w4 = timed(_write, p4, leaves, 4)
        same = open(p1, "rb").read() == open(p4, "rb").read()
        bundle_bytes = os.path.getsize(p1)
        rows.append(csv_row("ckpt/save/workers1", t_w1 * 1e6,
                            f"bundle_bytes={bundle_bytes};"
                            f"ratio={raw_bytes / bundle_bytes:.2f}x"))
        rows.append(csv_row("ckpt/save/workers4", t_w4 * 1e6,
                            f"speedup={t_w1 / max(t_w4, 1e-9):.2f}x"))
        checks.append(("ckpt_parallel_encode_deterministic", "model", "save",
                       same))

        coarse, full, local = _local_sessions(p1, leaves)
        rows.append(csv_row(
            "ckpt/restore/coarse", local["coarse_seconds"] * 1e6,
            f"bytes={local['coarse_bytes']};"
            f"fraction={local['byte_fraction']:.3f};"
            f"weight_error={WEIGHT_ERR}"))
        rows.append(csv_row(
            "ckpt/restore/refine_to_full", local["refine_seconds"] * 1e6,
            f"delta_bytes={local['refine_delta_bytes']};"
            f"plane_bytes={local['refine_plane_bytes']}"))
        checks.append(("ckpt_coarse_byte_fraction", "model", "restore",
                       local["byte_fraction"] <= 0.35))
        checks.append(("ckpt_refine_never_rereads", "model", "restore",
                       local["refine_delta_bytes"]
                       == local["refine_plane_bytes"]
                       and local["reread_bytes"] == 0))

        remote_ok, remote = _remote_session(p1, coarse, full)
        rows.append(csv_row(
            "ckpt/restore/remote_coarse", remote["coarse_seconds"] * 1e6,
            f"gets={remote['gets']};faulted=1"))
        checks.append(("ckpt_remote_bit_identical", "model", "restore",
                       remote_ok))

    if json_out:
        with open(json_out, "w") as f:
            json.dump(dict(
                rel_eb=REL_EB, weight_error=WEIGHT_ERR,
                raw_bytes=raw_bytes, bundle_bytes=bundle_bytes,
                n_leaves=len(leaves),
                kinds={k: e["kind"] for k, e in man["leaves"].items()},
                local=local, remote=remote,
                save_seconds={"workers1": t_w1, "workers4": t_w4},
                checks=[dict(name=c[0], case=c[1], op=c[2], ok=bool(c[3]))
                        for c in checks]), f, indent=2)
        print(f"wrote {json_out}")
    return rows, checks


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--json-out", default=JSON_OUT,
                    help="JSON artifact path ('' disables)")
    args = ap.parse_args()
    rows, checks = run(scale=args.scale, json_out=args.json_out)
    for r in rows:
        print(r)
    for name, ds, op, ok in checks:
        print(f"check {name}[{ds}/{op}]: {'ok' if ok else 'FAILED'}")
    if not all(c[-1] for c in checks):
        raise SystemExit(1)


if __name__ == "__main__":
    from pathlib import Path

    from repro import compile_cache
    compile_cache.enable(Path(__file__).resolve().parents[1])
    main()
