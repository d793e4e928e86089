"""Codec-kernel roofline report from ``BENCH_decode.json``.

The codec kernels are memory-bound: a few integer/fma ops per element
against streaming plane words, negabinary states, and f64 residuals.  The
meaningful roofline axis is therefore BYTES PER SECOND, not flops —
``kernels.dispatch`` meters the HBM bytes every wrapper moves per launch
(``measure_bytes``), ``benchmarks/backend_speed.py`` records them next to
the wall-clock of each decode op, and this report divides the two:

    achieved bytes/s per kernel  vs  the substrate's peak bandwidth

The peak is looked up by the ``device_kind`` the benchmark recorded
(``PEAK_HBM_GBS``); a device without a published peak in the table is an
error, so a CPU run never gets a roofline share.

Usage:
  PYTHONPATH=src python -m benchmarks.roofline_report BENCH_decode.json
"""
from __future__ import annotations

import argparse
import json

#: published HBM bandwidth per ``jax.Device.device_kind``, GB/s.
#: Source: Google Cloud documentation, "TPU v5e" (16 GB HBM2 at 819 GB/s
#: per chip); JAX reports a v5e chip as "TPU v5 lite".
PEAK_HBM_GBS = {
    "TPU v5 lite": 819.0,
    "TPU v5e": 819.0,
}


def peak_gbs(device_kind: str) -> float:
    """Published HBM peak of ``device_kind``; unknown kinds raise."""
    try:
        return PEAK_HBM_GBS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published HBM peak for device kind {device_kind!r}; "
            f"known: {sorted(PEAK_HBM_GBS)}") from None


def kernel_rows(records):
    """Aggregate per-kernel (dispatches, bytes, seconds) over every record
    that carries the ``kernel_bytes`` meter.

    A record's wall-clock covers all its kernels, so per-kernel seconds
    attribute the op's time proportionally to bytes moved — exact enough
    for a bandwidth trend, and it keeps the report free of per-launch
    timers the wrappers do not have.
    """
    agg: dict = {}
    for r in records:
        kb = r.get("kernel_bytes")
        if not kb:
            continue
        total_b = sum(kb.values()) or 1
        for k, nb in kb.items():
            disp = r.get("dispatches_by_kernel", {}).get(k, 0)
            a = agg.setdefault(k, dict(dispatches=0, nbytes=0, seconds=0.0))
            a["dispatches"] += disp
            a["nbytes"] += nb
            a["seconds"] += r["seconds"] * (nb / total_b)
    return agg


def render(results: dict, peak_gbs: float) -> str:
    agg = kernel_rows(results.get("records", []))
    out = [f"### Codec kernel roofline (peak {peak_gbs:.0f} GB/s)", ""]
    out.append("| kernel | dispatches | bytes moved | bytes/launch | "
               "achieved GB/s | roofline frac |")
    out.append("|---|---|---|---|---|---|")
    for k in sorted(agg, key=lambda k: -agg[k]["nbytes"]):
        a = agg[k]
        per_launch = a["nbytes"] / max(a["dispatches"], 1)
        gbs = a["nbytes"] / max(a["seconds"], 1e-12) / 1e9
        out.append(f"| {k} | {a['dispatches']} | {a['nbytes'] / 1e6:.1f} MB "
                   f"| {per_launch / 1e3:.1f} kB | {gbs:.3f} | "
                   f"{gbs / peak_gbs:.5f} |")
    if len(out) == 4:
        out.append("| (no kernel_bytes records — rerun "
                   "benchmarks.backend_speed) | — | — | — | — | — |")
    out.append("")
    fused = [r for r in results.get("records", [])
             if r.get("case") == "fused_decode"]
    if fused:
        out.append("### Fused vs unfused decode (2^20 case)")
        out.append("")
        out.append("| backend | op | MB/s | dispatches | launches/level |")
        out.append("|---|---|---|---|---|")
        for r in fused:
            out.append(f"| {r['backend']} | {r['op']} | {r['mbps']:.1f} | "
                       f"{r['dispatches']} | "
                       f"{r.get('dispatches_per_level', 0):.1f} |")
    return "\n".join(out)


def main():
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("bench_json", help="BENCH_decode.json from "
                   "benchmarks.backend_speed")
    args = p.parse_args()
    with open(args.bench_json) as f:
        results = json.load(f)
    kind = results.get("device", {}).get("kind")
    if kind is None:
        raise SystemExit(f"{args.bench_json} records no device kind; "
                         "rerun benchmarks.backend_speed")
    print(render(results, peak_gbs(kind)))


if __name__ == "__main__":
    main()
