"""Fused decode megakernel + dynamic plane prefixes: acceptance suite.

The bar for the fused progressive-decode path: routing the jax backend's
retrieval through ``decode_level_fused`` (plane-unpack + negabinary
dequantize + Algorithm 2 delta in ONE launch per level) and grouping chunk
decode jobs by ``(nbits,)`` alone — the loaded-prefix length is a runtime
kernel operand now — must be bit-identical to both the pre-fusion jax path
(registered as the ``jax_unfused`` backend) and the numpy reference, on v1
and chunked v2 archives, across escapes, mixed per-chunk prefixes,
refine-after-retrieve interleaves, and mesh sharding.  And it must be
strictly CHEAPER: fewer kernel dispatches than the ``(nbits, prefix)``
grouping produced.
"""
import jax
import numpy as np
import pytest

from _fields import smooth_field
from repro.core import (CUBIC, compress, decompress, metrics, open_archive,
                        refine, retrieve)
from repro.core import jax_backend
from repro.core.pipeline import backends
from repro.kernels import dispatch
from repro.parallel import codec_mesh

N_DEV = jax.device_count()


def _chunky_field(shape=(50, 41), seed=0, rough=0.01):
    rng = np.random.default_rng(seed)
    return smooth_field(shape, seed) + rough * rng.standard_normal(shape)


# ----------------------------------------------------- backend registration

def test_fused_backend_slots():
    """jax ships the fused family + dynamic grouping; jax_unfused is the
    same encode side with the pre-fusion decode, numpy has neither."""
    jx = backends.get("jax")
    assert jx.decode_level_fused is not None
    assert jx.decode_level_fused_batch is not None
    assert jx.inflate_level is not None and jx.inflate_level_batch is not None
    assert jx.dynamic_low_zero
    unf = backends.get("jax_unfused")
    assert unf.decode_level_fused is None
    assert not unf.dynamic_low_zero
    assert unf.decorrelate is jx.decorrelate  # shared encode side
    np_ = backends.get("numpy")
    assert np_.decode_level_fused is None and not np_.dynamic_low_zero
    # registered names are ExecPolicy-selectable
    assert "jax_unfused" in backends.names()


# ------------------------------------------------- kernel-level bit parity

@pytest.mark.parametrize("nprev,want", [(0, 3), (2, 5), (5, 5), (3, 11)])
def test_decode_level_fused_matches_unfused(nprev, want):
    """One fused launch == unfused decode + three host passes, bit for
    bit, at every (previous prefix, new prefix) rung."""
    from repro.core import negabinary

    rng = np.random.default_rng(nprev * 16 + want)
    q = rng.integers(-900, 900, size=1023).astype(np.int64)
    blobs, nbits = jax_backend.encode_level(q)
    eb = 3.7e-4
    prev = [blobs[i] if i < min(nprev, nbits) else None for i in range(nbits)]
    cur = [blobs[i] if i < min(want, nbits) else None for i in range(nbits)]
    nb_old = jax_backend.decode_level(prev, nbits, q.size)
    nb_ref = jax_backend.decode_level(cur, nbits, q.size)
    dq = negabinary.from_negabinary(nb_ref) - negabinary.from_negabinary(nb_old)
    dy_ref = dq.astype(np.float64) * 2.0 * eb
    with dispatch.measure() as d:
        nb_new, dy = jax_backend.decode_level_fused(cur, nbits, q.size,
                                                    nb_old, eb)
    assert np.array_equal(nb_new, nb_ref)
    assert np.array_equal(dy, dy_ref)
    assert d.get("decode_fused", 0) == 1


@pytest.mark.parametrize("want", [1, 4, 11])
def test_decode_level_fused_f32_matches_host(want):
    """float32 mode: one fused launch returns the level's float32 residual
    of the loaded prefix, bit-identical to the host dequantization."""
    from repro.core import arith, negabinary

    rng = np.random.default_rng(want)
    q = rng.integers(-(1 << 29), 1 << 29, size=1500).astype(np.int64)
    q[::3] //= 1 << 8
    blobs, nbits = jax_backend.encode_level(q)
    cur = [blobs[i] if i < min(want, nbits) else None for i in range(nbits)]
    nb_ref = jax_backend.decode_level(cur, nbits, q.size)
    eb = 2.5e-6
    want_res = arith.dequantize(negabinary.from_negabinary(nb_ref),
                                arith.consts(eb, np.float32))
    with dispatch.measure() as d:
        nb_new, res = jax_backend.decode_level_fused(
            cur, nbits, q.size, None, eb, dtype=np.float32)
    assert d.get("decode_fused", 0) == 1
    assert np.array_equal(nb_new, nb_ref)
    assert res.dtype == np.float32 and res.tobytes() == want_res.tobytes()


def test_decode_level_fused_batch_mixed_prefixes_and_ebs():
    """Per-chunk prefixes AND per-chunk error bounds ride one launch."""
    from repro.core import negabinary

    rng = np.random.default_rng(9)
    q = rng.integers(-500, 500, size=640).astype(np.int64)
    blobs, nbits = jax_backend.encode_level(q)
    wants = [nbits, max(1, nbits - 2), 1, 0]
    ebs = [1e-3, 2e-4, 5e-5, 1e-3]
    blob_lists = [[blobs[i] if i < w else None for i in range(nbits)]
                  for w in wants]
    olds = [jax_backend.decode_level(
        [blobs[i] if i < max(0, w - 1) else None for i in range(nbits)],
        nbits, q.size) for w in wants]
    with dispatch.measure() as d:
        outs = jax_backend.decode_level_fused_batch(blob_lists, nbits,
                                                    q.size, olds, ebs)
    assert d["decode_fused"] == 1
    for (nb_new, dy), bl, old, eb, w in zip(outs, blob_lists, olds, ebs,
                                            wants):
        nb_ref = jax_backend.decode_level(bl, nbits, q.size)
        if w == 0:  # nothing loaded: state untouched, delta zero
            assert np.array_equal(nb_new, old)
            assert not dy.any()
            continue
        dq = negabinary.from_negabinary(nb_ref) - \
            negabinary.from_negabinary(old)
        assert np.array_equal(nb_new, nb_ref)
        assert np.array_equal(dy, dq.astype(np.float64) * 2.0 * eb)


def test_inflate_level_prefetch_seam():
    """``decode_level_fused(words=...)`` consumes a pre-inflated
    ``inflate_level`` result unchanged — the two-slot prefetch seam."""
    q = np.arange(-200, 200, dtype=np.int64)
    blobs, nbits = jax_backend.encode_level(q)
    nb_old = np.zeros(q.size, np.uint32)
    direct = jax_backend.decode_level_fused(blobs, nbits, q.size, nb_old,
                                            1e-4)
    words = jax_backend.inflate_level(blobs, nbits, q.size)
    via = jax_backend.decode_level_fused(blobs, nbits, q.size, nb_old,
                                         1e-4, words=words)
    assert np.array_equal(direct[0], via[0])
    assert np.array_equal(direct[1], via[1])


# ------------------------------------------------- session-level bit parity

def test_v1_ladder_fused_vs_unfused_vs_numpy():
    """Progressive v1 ladder with escapes: every rung bit-identical across
    the three backends, byte accounting included."""
    x = smooth_field((60, 47), 2)
    x[11, 7] = 1e14  # escape
    with np.errstate(invalid="ignore"):
        buf = compress(x, 1e-6, CUBIC)
    ladders = {}
    for bk in ("numpy", "jax", "jax_unfused"):
        st, rungs = None, []
        for E in (1e-1, 1e-3, None):
            kw = {} if E is None else dict(error_bound=E)
            out, st = retrieve(open_archive(buf), state=st, backend=bk, **kw)
            rungs.append((out.copy(), st.bytes_read))
        ladders[bk] = rungs
    for bk in ("jax", "jax_unfused"):
        for (o1, b1), (o2, b2) in zip(ladders["numpy"], ladders[bk]):
            assert np.array_equal(o1, o2), bk
            assert b1 == b2, bk
    assert metrics.linf(x, ladders["jax"][-1][0]) <= 1e-6


def test_f32_ladder_fused_vs_unfused_vs_numpy():
    """float32 chunked ladder with an escape: fused (float32 residual out
    of the kernel) == unfused == numpy at every rung."""
    x = _chunky_field((50, 41)).astype(np.float32)
    x[20, 3] = np.float32(-7e30)
    buf = compress(x, 1e-5, chunk_elems=600)
    outs = {}
    for bk in ("numpy", "jax", "jax_unfused"):
        out1, st = retrieve(open_archive(buf), error_bound=1e-2, backend=bk)
        out2, st = refine(st, error_bound=1e-4, backend=bk)
        out3, st = refine(st, backend=bk)
        outs[bk] = ([o.tobytes() for o in (out1, out2, out3)], st.bytes_read)
    for bk in ("jax", "jax_unfused"):
        assert outs[bk] == outs["numpy"], bk
    assert metrics.linf(x, out3) <= 1e-5


def test_chunked_budget_ladder_fused_vs_unfused():
    """Chunked v2 + byte budgets (mixed per-chunk prefixes) + an escape
    chunk + refine-after-retrieve interleave: fused == unfused == numpy at
    every step."""
    rng = np.random.default_rng(3)
    x = smooth_field((60, 33), 1)
    x[:20] += 0.5 * rng.standard_normal((20, 33))  # chunk 0 much rougher
    x[40, 5] = -1e15                               # escape in chunk 2
    with np.errstate(invalid="ignore"):
        buf = compress(x, 1e-6, chunk_elems=700)
    outs = {}
    for bk in ("numpy", "jax", "jax_unfused"):
        out1, st = retrieve(open_archive(buf), max_bytes=4000, backend=bk)
        out2, st = refine(st, max_bytes=9000, backend=bk)
        out3, st = refine(st, backend=bk)
        outs[bk] = (out1, out2, out3, st.bytes_read)
    for bk in ("jax", "jax_unfused"):
        for a, b in zip(outs["numpy"][:3], outs[bk][:3]):
            assert np.array_equal(a, b), bk
        assert outs[bk][3] == outs["numpy"][3], bk
    assert metrics.linf(x, outs["jax"][2]) <= 1e-6


def test_fused_sharded_parity():
    """Mesh-sharded fused retrieval equals the unsharded one bit for bit
    (degenerates to 1 device gracefully; CI's 8-device lane exercises the
    real fan-out)."""
    x = _chunky_field((48, 41))
    buf = compress(x, 1e-5, chunk_elems=500)
    mesh = codec_mesh.codec_mesh()
    a, sa = retrieve(open_archive(buf), error_bound=1e-3, backend="jax")
    b, sb = retrieve(open_archive(buf), error_bound=1e-3, backend="jax",
                     shard=mesh)
    assert np.array_equal(a, b)
    assert sa.bytes_read == sb.bytes_read


# ------------------------------------------------- dispatch-count collapse

def test_dynamic_grouping_fewer_dispatches_than_per_prefix():
    """The tentpole's scheduling win, in the serving shape that exposes
    it: sessions over the SAME archive bytes (equal nbits) targeting
    DIFFERENT fidelities want different plane prefixes.  The old
    (nbits, prefix) grouping fragments each level into one launch per
    distinct prefix; the (nbits,) grouping runs ONE fused launch per
    level — strictly fewer dispatches, same bits per session."""
    from repro.core import loader
    from repro.core.pipeline.decode import decode_group
    from repro.core.pipeline.spec import ExecPolicy

    x = smooth_field((48, 41), 4)
    buf = compress(x, 1e-6)
    bounds = (1e-1, 1e-3, 1e-5)
    results = {}
    for bk in ("jax_unfused", "jax"):
        readers = [open_archive(buf) for _ in bounds]
        keeps = [loader.plan_error_mode(r.meta, E, loader.SAFE).keep_planes
                 for r, E in zip(readers, bounds)]
        assert len({tuple(k) for k in keeps}) == 3  # genuinely mixed
        ctx = ExecPolicy(backend=bk).bind(chunked=False, encode=False)
        with dispatch.measure() as d:
            sts = decode_group(readers, [None] * len(readers), keeps, ctx)
        results[bk] = ([st.xhat.copy() for st in sts], dict(d))
    for a, b in zip(results["jax"][0], results["jax_unfused"][0]):
        assert np.array_equal(a, b)
    d_new, d_old = results["jax"][1], results["jax_unfused"][1]
    # per-prefix grouping launched one unpack per distinct prefix per
    # level; dynamic grouping runs one fused launch per populated level
    assert d_new["decode_fused"] < d_old["bitplane_unpack"]
    assert sum(d_new.values()) < sum(d_old.values())


def test_refine_interleave_dispatch_and_bits():
    """Refine-after-retrieve on the fused path: deltas decode through the
    same fused launches, nothing is re-read, bits match the unfused path."""
    x = _chunky_field((50, 41))
    buf = compress(x, 1e-6, chunk_elems=500)
    outs = {}
    for bk in ("jax", "jax_unfused"):
        out1, st = retrieve(open_archive(buf), error_bound=1e-2, backend=bk,
                            batch_chunks=True)
        with dispatch.measure() as d:
            out2, st = refine(st, error_bound=1e-4, backend=bk,
                              batch_chunks=True)
        prev = st.bytes_read
        out3, st = refine(st, error_bound=1e-4, backend=bk,
                          batch_chunks=True)
        assert st.bytes_read == prev  # nothing re-read
        outs[bk] = (out1, out2, out3, d)
    for a, b in zip(outs["jax"][:3], outs["jax_unfused"][:3]):
        assert np.array_equal(a, b)
    assert outs["jax"][3]["decode_fused"] <= \
        outs["jax_unfused"][3]["bitplane_unpack"]
