"""Cross-pod gradient-reduction wire traffic: plain f32 psum vs IPComp
bitplane-compressed psum (the paper's §4.4 pipeline on the inter-pod links).

Collective bytes are read from the compiled HLO of the isolated reduction
(the integrated train step compresses the same tensors; on XLA:CPU the
mixed manual/auto module trips a compiler bug in AllReducePromotion —
EXPERIMENTS.md §Perf cell 3 — so the wire measurement is taken here).

The host-side section always runs (no dry-run env needed): it pushes
seeded gradient-shaped leaves through the actual quantize/truncate path
(``grad._quantize_leaf``), negabinary-codes them, and measures the
entropy-coded occupied bitplanes — the compressed bits per value that
would cross the wire.  Claim: <= ``keep_bits`` per value (truncation
really dropped the planes it claims to drop), with the measurement
written to ``BENCH_grad.json``.
"""
from __future__ import annotations

import json
import zlib

import numpy as np

JSON_OUT = "BENCH_grad.json"
KEEP_BITS = 14
REL_EB = 1e-4


def _leaf_wire_bits(g, keep_bits: int, rel_eb: float) -> float:
    """Compressed wire bits/value for one gradient leaf: quantize +
    occupied-width truncate (the grad path), negabinary, then zlib over
    each occupied MSB-first bitplane (the codec's plane channel)."""
    import jax.numpy as jnp
    from repro.compression.grad import _quantize_leaf
    from repro.core.negabinary import to_negabinary
    q, _, _ = _quantize_leaf(jnp.asarray(g, jnp.float32),
                             jnp.zeros(g.shape, jnp.float32),
                             rel_eb, keep_bits)
    nb = to_negabinary(np.asarray(q, np.int64))
    occupied = int(nb.max()).bit_length()
    total_bytes = 0
    for b in range(occupied - 1, -1, -1):   # MSB-first, like the codec
        plane = np.packbits((nb >> np.uint32(b)) & np.uint32(1))
        total_bytes += len(zlib.compress(plane.tobytes(), 6))
    return total_bytes * 8.0 / g.size


def _wire_bits_bench(scale=None):
    rows, checks = [], []
    s = 1.0 if scale is None else max(scale / 0.15, 0.25)
    n = int((1 << 18) * min(s, 4.0))
    shapes = {"mlp.win": (n // 256, 256), "attn.wqkv": (n // 512, 512)}
    bits = {}
    for name, shape in shapes.items():
        rng = np.random.default_rng(zlib.crc32(name.encode()))
        g = (rng.standard_normal(shape) / np.sqrt(shape[-1])) \
            .astype(np.float32)
        bits[name] = _leaf_wire_bits(g, KEEP_BITS, REL_EB)
        rows.append(f"grad_compress/wire_bits/{name},0.0,"
                    f"bits_per_value={bits[name]:.2f};keep_bits={KEEP_BITS};"
                    f"vs_f32=32")
    worst = max(bits.values())
    checks.append(("grad_bits_per_value_within_keep",
                   f"{len(shapes)}leaves", "wire", worst <= KEEP_BITS))
    return rows, checks, bits


def run(scale=None, json_out: str = JSON_OUT):
    import os
    rows, checks, bits = _wire_bits_bench(scale)
    if json_out:
        with open(json_out, "w") as f:
            json.dump(dict(keep_bits=KEEP_BITS, rel_eb=REL_EB,
                           bits_per_value=bits,
                           checks=[dict(name=c[0], case=c[1], op=c[2],
                                        ok=bool(c[3])) for c in checks]),
                      f, indent=2)
    if "XLA_FLAGS" not in os.environ:  # needs the 512-device dry-run env
        rows.append("grad_compress/skipped(no XLA_FLAGS),0.0,run via dryrun")
        return rows, checks
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.compression.grad import compressed_psum
    from repro.launch.mesh import make_production_mesh
    from repro.launch.roofline import collective_bytes
    from repro.parallel.compat import shard_map

    mesh = make_production_mesh(multi_pod=True)
    npods = mesh.shape["pod"]
    # yi-6b-sized flat gradient shard per device pair
    n = 6_061_000_000 // 512  # one device's FSDP+TP shard of the grads
    n = (n // 128) * 128
    g = jax.ShapeDtypeStruct((npods, n), jnp.float32)

    def plain(x):
        return jax.lax.psum(x, "pod") / npods

    def comp(x):
        return compressed_psum(x, "pod", keep_bits=14, rel_eb=1e-4) / npods

    out = []
    for name, fn in (("plain_f32", plain), ("ipcomp_bitplane", comp)):
        f = jax.jit(shard_map(fn, mesh=mesh, in_specs=P("pod"),
                              out_specs=P("pod"), axis_names={"pod"},
                              check_vma=False))
        hlo = f.lower(g).compile().as_text()
        coll = collective_bytes(hlo)
        tot = sum(coll.values())
        out.append(tot)
        rows.append(f"grad_compress/{name},0.0,"
                    f"coll_bytes={tot};breakdown={coll}")
    ratio = out[0] / max(out[1], 1)
    rows.append(f"grad_compress/reduction,0.0,ratio={ratio:.2f}x")
    checks.append(("compressed_wire_smaller", "yi-6b", "", out[1] < out[0]))
    return rows, checks


def main():
    import argparse
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
