#!/usr/bin/env python3
"""Smoke test of the codec's main path on a TPU.

Drives the path a user calls, at the published width of a Table 3 field
(SpeedX, 100 x 500 x 500 float32, ``configs.paper.generate(scale=1.0)``
from a fixed seed):

  0. the arithmetic contract (``core.arith``): each kernel's float32
     results equal the numpy reference bit for bit on inputs built to
     stress it — subnormals, tiny normals whose sums underflow, bins past
     2**24 (int -> float rounding);
  1. ``Codec(eb=1e-6, relative=True, chunk_elems=2**22, version=3)
     .compress(x, ExecPolicy(backend="jax"))`` — several equal chunks, so
     the batched shape-group path runs; the archive bytes must equal the
     numpy reference's;
  2. one progressive session (``Archive.open``): reads at absolute bounds
     of 1e-3 and 1e-5 of the value range, then ``Fidelity.full()``.  Every
     rung must meet its bound against ``x``, read more bytes than the one
     before, and equal the numpy session bit for bit;
  3. a ``RetrievalServer`` holding the archive answers requests at mixed
     fidelities, one chained with ``refine_of``; every request must settle
     DONE with the session's bits.

It fails (exit 1, or 2 when there is nothing to run on) without printing
the result line when: the device is not a TPU, the kernel mode is not
Pallas, any kernel launch ran in the Pallas interpreter, or any check
fails.  The last stdout line of a passing run is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

``--chips 4`` runs only the sharded chunk grid instead: compress and a
full read over ``codec_mesh(4)``, compared in the same process with a
one-device run (equal bytes, equal bits), and launches recorded on all
four devices.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # four chips (sharded path)
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent
SEED = 20250206
EB_REL = 1e-6
CHUNK_ELEMS = 1 << 22
LADDER = (1e-3, 1e-5)  # fractions of the value range, then a full read


class CheckFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    print(f"check {'pass' if cond else 'FAIL'}: {what}", flush=True)
    if not cond:
        raise CheckFailed(what)


def log(**kv) -> None:
    print(json.dumps(kv), flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def setup(chips: int):
    """Import the codec and check the device; exits 2 when there is no
    repo beside this script or no TPU."""
    sys.path.insert(0, str(REPO / "src"))
    try:
        from repro import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        sys.exit(2)
    compile_cache.enable(REPO)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devs[0].platform!r} "
              "devices)", file=sys.stderr)
        sys.exit(2)
    if len(devs) < chips:
        print(f"chip_smoke: --chips {chips} needs {chips} devices, found "
              f"{len(devs)}", file=sys.stderr)
        sys.exit(2)
    from repro.kernels import mode

    check(mode.kernel_mode() == mode.PALLAS,
          f"kernel mode is {mode.PALLAS!r} ({mode.ENV} unset)")
    return devs


def field():
    from repro.configs.paper import TABLE3, generate

    ds = next(d for d in TABLE3 if d.name == "SpeedX")
    x, dt = timed(lambda: generate(ds, scale=1.0, seed=SEED))
    check(x.shape == ds.shape and x.dtype == np.float32,
          f"SpeedX field is {ds.shape} float32")
    log(phase="field", shape=list(x.shape), dtype=str(x.dtype),
        seconds=dt)
    return x


def equal_bits(a: np.ndarray, b: np.ndarray) -> bool:
    return a.dtype == b.dtype and a.shape == b.shape and \
        a.tobytes() == b.tobytes()


def probe_arith() -> None:
    """Kernel float32 arithmetic == the numpy reference, bit for bit."""
    import jax.numpy as jnp

    from repro.core import arith, interpolation, negabinary
    from repro.kernels.bitplane_pack import bitplane_pack
    from repro.kernels.decode_fused import decode_fused
    from repro.kernels.interp_quant import interp_quant
    from repro.kernels.interp_recon import interp_recon

    rng = np.random.default_rng(SEED)
    R, C, s, eb = 64, 301, 1, 3e-4
    c = arith.consts(eb, np.float32)

    def surface():
        v = rng.standard_normal((R, C)).astype(np.float32)
        v[::3] *= np.float32(1e-37)               # sums that underflow
        v[1::7, ::5] = np.float32(3e-39)          # subnormal operands
        v[2::7, 1::5] = np.float32(-1e-40)
        v[3::7, 2::5] = 0.0
        return v

    x, xh = surface(), surface()
    idx = np.arange(s, C, 2 * s)
    for interp in ("cubic", "linear"):
        q, pred = interp_quant(jnp.asarray(x), jnp.asarray(xh), s=s, eb=eb,
                               interp=interp)
        pred_ref = interpolation.predict_block(xh, 1, idx, s, C, interp,
                                               ftz=True)
        q_ref = arith.bins(np, x[:, idx], pred_ref, c, np.int64)
        check(equal_bits(np.asarray(pred), pred_ref),
              f"arith: {interp} prediction equals numpy")
        check(np.array_equal(np.asarray(q, np.int64), q_ref),
              f"arith: {interp} bins equal numpy")
        res = arith.dequantize(q_ref, c)
        out = interp_recon(jnp.asarray(xh), jnp.asarray(res), s=s,
                           interp=interp)
        check(equal_bits(np.asarray(out),
                         arith.recon(np, pred_ref, res, True)),
              f"arith: {interp} reconstruction equals numpy")
    bins = rng.integers(-arith.QMAX, arith.QMAX, 50000)
    bins[::2] //= 1 << 9                          # both sides of 2**24
    packed, n = bitplane_pack(jnp.asarray(bins, jnp.int32))
    words = np.asarray(packed).reshape(32, -1)
    for low_zero in (0, 5):
        nb, res = decode_fused(words, None, n, eb=eb, low_zero=low_zero,
                               dtype=np.float32)
        want = negabinary.truncate(negabinary.to_negabinary(bins), low_zero)
        check(np.array_equal(np.asarray(nb), want),
              f"arith: unpacked words equal numpy (low_zero={low_zero})")
        check(equal_bits(np.asarray(res), arith.dequantize(
            negabinary.from_negabinary(want), c)),
            f"arith: dequantized residuals equal numpy (low_zero={low_zero})")


def run_one_chip(x: np.ndarray) -> None:
    from repro.api import Codec, ExecPolicy, Fidelity
    from repro.kernels import dispatch
    from repro.serving import server as srv

    jx, ref = ExecPolicy(backend="jax"), ExecPolicy(backend="numpy")
    codec = Codec(eb=EB_REL, relative=True, chunk_elems=CHUNK_ELEMS,
                  version=3)
    vrange = float(x.max()) - float(x.min())
    eb = EB_REL * vrange

    # -- compress: first call compiles, second is warm
    with dispatch.measure() as launches:
        arc, t_cold = timed(lambda: codec.compress(x, jx))
    _, t_warm = timed(lambda: codec.compress(x, jx))
    arc_ref, t_ref = timed(lambda: codec.compress(x, ref))
    log(phase="compress", chunks=arc.n_chunks, archive_bytes=arc.nbytes,
        ratio=x.nbytes / arc.nbytes, cold_s=t_cold, warm_s=t_warm,
        numpy_s=t_ref, launches=launches)
    check(arc.n_chunks > 2, "several chunks (batched shape-group path)")
    check(launches.get("interp_quant", 0) > 0 and
          launches.get("bitplane_pack", 0) > 0, "compress ran the kernels")
    check(arc.tobytes() == arc_ref.tobytes(),
          "archive bytes equal the numpy backend's")

    # -- progressive session: coarse -> finer -> full, vs numpy
    rungs = [(Fidelity.error_bound(f * vrange), f * vrange) for f in LADDER]
    rungs.append((Fidelity.full(), eb))
    sess, sess_ref = arc.open(jx), arc.open(ref)
    outs, prev_bytes = [], 0
    for fid, bound in rungs:
        with dispatch.measure() as launches:
            out, t = timed(lambda: sess.read(fid))
        out_ref, t_ref = timed(lambda: sess_ref.read(fid))
        err = float(np.max(np.abs(out.astype(np.float64) - x)))
        log(phase="read", fidelity=repr(fid), bound=bound, max_err=err,
            achieved_bound=sess.achieved_bound, bytes_read=sess.bytes_read,
            seconds=t, numpy_s=t_ref, launches=launches)
        check(err <= bound and sess.achieved_bound <= bound,
              f"{fid!r}: max error {err:.6g} within {bound:.6g}")
        check(sess.bytes_read > prev_bytes, f"{fid!r}: bytes_read grew")
        check(equal_bits(out, out_ref), f"{fid!r}: bits equal numpy's")
        check(launches.get("interp_recon", 0) > 0,
              f"{fid!r}: reconstruction ran the kernels")
        prev_bytes = sess.bytes_read
        outs.append(out)

    # -- serving: mixed fidelities, one refine chain, all DONE
    server = srv.RetrievalServer(policy=jx)
    server.add_archive("speedx", arc)
    coarse = server.submit("speedx", rungs[0][0])
    reqs = [(coarse, outs[0]),
            (server.submit("speedx", rungs[1][0]), outs[1]),
            (server.submit("speedx", Fidelity.full()), outs[2]),
            (server.submit("speedx", rungs[1][0], refine_of=coarse),
             outs[1])]
    _, t = timed(server.drain)
    log(phase="serve", requests=len(reqs), seconds=t,
        statuses=[r.status for r, _ in reqs], stats=server.stats())
    for r, want in reqs:
        check(r.status == srv.DONE,
              f"request {r.req_id} ({r.fidelity!r}) settled DONE")
        check(equal_bits(r.result, want),
              f"request {r.req_id}: bits equal the session's")


def _fanned_out(logical: dict, dev: dict, kernel: str) -> bool:
    """Some of ``kernel``'s dispatches ran on all four devices: each one
    counts once logically and four times per device (the ragged tail chunk
    is a singleton group and runs on one device, counting 1 and 1)."""
    extra = dev.get(kernel, 0) - logical.get(kernel, 0)
    return extra > 0 and extra % 3 == 0


def run_four_chips(x: np.ndarray) -> None:
    from repro.api import Codec, ExecPolicy, Fidelity
    from repro.kernels import dispatch
    from repro.parallel import codec_mesh

    mesh = codec_mesh.codec_mesh(4)
    one, four = ExecPolicy(backend="jax"), ExecPolicy(backend="jax",
                                                      shard=mesh)
    codec = Codec(eb=EB_REL, relative=True, chunk_elems=CHUNK_ELEMS,
                  version=3)
    arc1, t1 = timed(lambda: codec.compress(x, one))
    with dispatch.measure() as logical, dispatch.measure_devices() as dev:
        arc4, t4 = timed(lambda: codec.compress(x, four))
    log(phase="compress", one_device_s=t1, four_devices_s=t4,
        launches=logical, device_launches=dev)
    check(arc4.tobytes() == arc1.tobytes(),
          "sharded archive bytes equal the one-device archive")
    check(_fanned_out(logical, dev, "interp_quant"),
          "sharded compress launched on all four devices")

    out1, t1 = timed(lambda: arc1.open(one).read(Fidelity.full()))
    with dispatch.measure() as logical, dispatch.measure_devices() as dev:
        out4, t4 = timed(lambda: arc4.open(four).read(Fidelity.full()))
    err = float(np.max(np.abs(out4.astype(np.float64) - x)))
    log(phase="read", one_device_s=t1, four_devices_s=t4, max_err=err,
        launches=logical, device_launches=dev)
    check(equal_bits(out4, out1), "sharded full read equals one-device bits")
    check(err <= EB_REL * (float(x.max()) - float(x.min())),
          "sharded full read within eb")
    check(_fanned_out(logical, dev, "interp_recon"),
          "sharded read launched on all four devices")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the main path on one chip; 4: only the "
                         "sharded chunk grid over four chips")
    args = ap.parse_args()
    try:
        devs = setup(args.chips)
        from repro.kernels import dispatch

        dev = devs[0]
        log(phase="device", platform=dev.platform, kind=dev.device_kind,
            count=len(devs))
        if args.chips == 1:
            probe_arith()
        x = field()
        if args.chips == 4:
            run_four_chips(x)
        else:
            run_one_chip(x)
        interpreted = dispatch.interpreted_counts()
        check(dispatch.total() > 0 and not interpreted,
              f"no kernel ran interpreted ({interpreted})")
    except CheckFailed:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
