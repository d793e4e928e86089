"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV; claim-checks are summarized at the
end (a failed claim check is a regression against the paper's comparisons,
not a crash).

The ``backend_speed`` module (in the default set) also writes the
trendable JSON artifacts ``BENCH_compress.json`` and ``BENCH_decode.json``
to the working directory — run from the repo root so CI picks them up.
``BENCH_compress.json`` carries the chunk-batch speed entry: batched vs
looped kernel dispatch counts and MB/s for the vmapped shape-group engine.
The ``serve`` module drives the serving tier's mixed-fidelity workload
(per-call vs coalesced vs cached) and writes ``BENCH_serve.json``.

  PYTHONPATH=src python -m benchmarks.run [--scale 0.15] [--only fig5,...]
"""
from __future__ import annotations

import argparse
import sys

from . import (backend_speed, ckpt_bench, fig5_ratio, fig6_retrieval,
               fig7_bitrate, fig8_speed, fig10_psnr, serve_bench,
               table2_entropy, grad_compress_bench)

MODULES = {
    "fig5": fig5_ratio, "fig6": fig6_retrieval, "fig7": fig7_bitrate,
    "fig8": fig8_speed, "fig10": fig10_psnr, "table2": table2_entropy,
    "grad_compress": grad_compress_bench, "backend_speed": backend_speed,
    "serve": serve_bench, "ckpt": ckpt_bench,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=None)
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(MODULES)
    all_checks = []
    print("name,us_per_call,derived")
    for n in names:
        rows, checks = MODULES[n].run(args.scale)
        for r in rows:
            print(r)
        all_checks.extend(checks)
    ok = sum(1 for c in all_checks if c[-1])
    print(f"\n# claim-checks: {ok}/{len(all_checks)} hold", file=sys.stderr)
    for c in all_checks:
        if not c[-1]:
            print(f"#   FAILED: {c}", file=sys.stderr)


if __name__ == "__main__":
    from pathlib import Path

    from repro import compile_cache
    compile_cache.enable(Path(__file__).resolve().parents[1])
    main()
