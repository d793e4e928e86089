"""Batched chunk execution: vmapped shape-group scheduling parity.

The acceptance bar of the batched chunk engine: grouping a v2 archive's
equal-shaped chunks and running the codec primitives once per group via
``jax.vmap`` must (a) emit byte-identical archives and bit-identical
reconstructions — refine deltas included — to the per-chunk loop on both
backends, and (b) issue strictly fewer kernel dispatches than chunks x
levels.  The batch axis is an execution detail, never a format change.
"""
import numpy as np
import pytest

from _fields import smooth_field
from repro.core import (CUBIC, ChunkedRetrievalState, compress, decompress,
                        metrics, open_archive, refine, retrieve)
from repro.core import bitplane
from repro.core.pipeline import backends, shape_groups
from repro.kernels import dispatch


def _chunky_field(shape=(50, 41), seed=0, rough=0.01):
    rng = np.random.default_rng(seed)
    return smooth_field(shape, seed) + rough * rng.standard_normal(shape)


# ------------------------------------------------------- group scheduling

def test_shape_groups_structure():
    # typical chunk grid: equal interior slabs + ragged tail
    assert shape_groups([12, 12, 12, 2]) == [[0, 1, 2], [3]]
    assert shape_groups([7]) == [[0]]                  # single chunk
    assert shape_groups([3, 3, 3, 3]) == [[0, 1, 2, 3]]
    # arbitrary mixtures keep first-occurrence order, ascending indices
    assert shape_groups([5, 2, 5, 2, 9]) == [[0, 2], [1, 3], [4]]


def test_shape_groups_caps_batch_size():
    """A batched stack materializes its whole group in memory, so big
    groups split into max_group-sized runs (chunking must keep bounding
    working memory)."""
    assert shape_groups([3] * 7, max_group=3) == [[0, 1, 2], [3, 4, 5], [6]]
    assert shape_groups([3] * 40) == [list(range(16)), list(range(16, 32)),
                                      list(range(32, 40))]
    assert shape_groups([3] * 40, max_group=None) == [list(range(40))]


def test_decode_level_batch_mixed_prefixes_one_dispatch():
    """low_zero is a RUNTIME kernel operand now: streams with different
    loaded prefixes share ONE batched dispatch and each decodes exactly
    like its scalar call."""
    from repro.core import jax_backend
    q = np.arange(-50, 50, dtype=np.int64)
    blobs, nbits, _ = jax_backend.encode_level(q, 1.0)
    blobs = bitplane.wait(blobs)
    full = list(blobs)
    shorter = [blobs[i] if i < nbits - 1 else None for i in range(nbits)]
    shortest = [blobs[i] if i < 2 else None for i in range(nbits)]
    prefixes = [full, shorter, shortest]
    with dispatch.measure() as d:
        out = jax_backend.decode_level_batch(prefixes, nbits, q.size)
    assert d["bitplane_unpack"] == 1
    for got, bl in zip(out, prefixes):
        assert np.array_equal(got, jax_backend.decode_level(bl, nbits,
                                                            q.size))


def test_backend_batch_slots():
    """jax ships the batched primitives; numpy deliberately loops."""
    jx, np_ = backends.get("jax"), backends.get("numpy")
    assert jx.batches_encode and jx.batches_decode
    assert not np_.batches_encode and not np_.batches_decode


# --------------------------------------------------------- encode parity

@pytest.mark.parametrize("shape,chunk", [((50, 41), 500), ((3000,), 700),
                                         ((24, 20, 18), 2000)])
def test_batched_compress_byte_identical(shape, chunk):
    """Batched, looped, and numpy archives are the same bytes — including
    the ragged tail chunk every shape here produces."""
    x = _chunky_field(shape)
    b_loop = compress(x, 1e-5, CUBIC, backend="jax", chunk_elems=chunk,
                      batch_chunks=False)
    b_bat = compress(x, 1e-5, CUBIC, backend="jax", chunk_elems=chunk,
                     batch_chunks=True)
    b_np = compress(x, 1e-5, CUBIC, backend="numpy", chunk_elems=chunk)
    assert b_bat == b_loop == b_np


def test_batched_compress_fewer_dispatches_than_chunks_x_levels():
    """The point of batching: per-level pack launches collapse from one
    per (chunk, level) to one per (shape-group, level)."""
    x = _chunky_field((48, 41))
    with dispatch.measure() as loop:
        compress(x, 1e-5, backend="jax", chunk_elems=500, batch_chunks=False)
    with dispatch.measure() as bat:
        buf = compress(x, 1e-5, backend="jax", chunk_elems=500,
                       batch_chunks=True)
    r = open_archive(buf)
    n_chunks = len(r.meta.chunks)
    n_levels = r.chunk_reader(0).meta.L
    assert n_chunks >= 3
    # looped: one pack dispatch per non-empty (chunk, level)
    assert loop["bitplane_pack"] > bat["bitplane_pack"]
    assert bat["bitplane_pack"] < n_chunks * n_levels
    # the sweep dispatches shrink too, and so does the overall count
    assert bat["interp_quant"] < loop["interp_quant"]
    assert sum(bat.values()) < sum(loop.values())


def test_isabel_settings_archives_and_loss_table_counts():
    """The benchmark's codec settings (relative bound, cubic, v3, equal
    chunks plus a ragged tail) on a small float32 field: jax and numpy
    write the same bytes, the jax backend makes every level's
    truncation-loss table from its device extrema and the numpy backend
    none (it tables on the host)."""
    from collections import Counter

    from repro import trace
    from repro.api import Codec, ExecPolicy

    x = _chunky_field((18, 16, 12)).astype(np.float32)
    codec = Codec(eb=1e-6, relative=True, interp=CUBIC,
                  chunk_elems=4 * 16 * 12, version=3)
    arcs, counts = {}, {}
    for backend in ("jax", "numpy"):
        trace.clear()
        with trace.recording():
            arcs[backend] = codec.compress(
                x, ExecPolicy(backend=backend)).tobytes()
        counts[backend] = Counter()
        for r in trace.records():
            counts[backend].update(r.counts)
    trace.clear()
    assert arcs["jax"] == arcs["numpy"]
    r = open_archive(arcs["jax"])
    shapes = [r.chunk_reader(i).meta.L for i in range(len(r.meta.chunks))]
    assert len(shapes) == 5             # 4 equal slabs + a 2-row tail
    tables = sum(shapes)
    assert counts["jax"]["loss_tables_device"] == tables
    assert counts["numpy"]["loss_tables_device"] == 0


def test_numpy_backend_batch_flag_is_noop():
    """numpy has no batched slots: batch_chunks=True falls back to the
    loop instead of erroring, and bytes are unchanged."""
    x = _chunky_field((30, 20))
    a = compress(x, 1e-4, backend="numpy", chunk_elems=200,
                 batch_chunks=True)
    b = compress(x, 1e-4, backend="numpy", chunk_elems=200,
                 batch_chunks=False)
    assert a == b


def test_single_chunk_archive_batched_path():
    """A one-chunk v2 archive is a singleton group: the scheduler must
    fall through to the scalar path and still round-trip."""
    x = _chunky_field((16, 10))
    buf = compress(x, 1e-5, backend="jax", chunk_elems=10 ** 6,
                   batch_chunks=True)
    r = open_archive(buf)
    assert len(r.meta.chunks) == 1
    out, st = retrieve(r, error_bound=1e-3, backend="jax",
                       batch_chunks=True)
    assert metrics.linf(x, out) <= 1e-3
    assert np.array_equal(out, retrieve(buf, error_bound=1e-3,
                                        backend="numpy")[0])


# --------------------------------------------------------- decode parity

@pytest.mark.parametrize("mode", [dict(error_bound=1e-3),
                                  dict(max_bytes=3000), dict()])
def test_batched_retrieve_bit_identical(mode):
    """Every plan mode: batched jax == looped jax == numpy, bit for bit,
    with identical per-chunk byte accounting."""
    x = _chunky_field((50, 41))
    buf = compress(x, 1e-5, chunk_elems=500)
    a, sa = retrieve(open_archive(buf), backend="jax", batch_chunks=False,
                     **mode)
    b, sb = retrieve(open_archive(buf), backend="jax", batch_chunks=True,
                     **mode)
    c, sc = retrieve(open_archive(buf), backend="numpy", **mode)
    assert np.array_equal(a, b) and np.array_equal(a, c)
    assert sa.bytes_read == sb.bytes_read == sc.bytes_read
    assert sa.err_bound == sb.err_bound == sc.err_bound
    for ca, cb in zip(sa.chunk_states, sb.chunk_states):
        assert ca.planes_loaded == cb.planes_loaded
        assert ca.bytes_read == cb.bytes_read


def test_batched_retrieve_fewer_dispatches():
    x = _chunky_field((50, 41))
    buf = compress(x, 1e-5, chunk_elems=500)
    with dispatch.measure() as loop:
        retrieve(open_archive(buf), error_bound=1e-3, backend="jax",
                 batch_chunks=False)
    with dispatch.measure() as bat:
        retrieve(open_archive(buf), error_bound=1e-3, backend="jax",
                 batch_chunks=True)
    r = open_archive(buf)
    n_chunks = len(r.meta.chunks)
    n_levels = r.chunk_reader(0).meta.L
    assert bat["interp_recon"] < loop["interp_recon"]
    # the jax decode path runs the fused megakernel: plane unpack +
    # dequantize + delta are one launch per (group, level)
    assert bat.get("decode_fused", 0) <= loop["decode_fused"]
    assert bat.get("decode_fused", 0) < n_chunks * n_levels
    assert sum(bat.values()) < sum(loop.values())


def test_batched_refine_deltas_bit_identical_and_no_rereads():
    """Algorithm 2 on the batched engine: every rung of a progressive
    ladder matches the looped ladder bit-for-bit, refine still loads only
    missing planes (cumulative bytes equal the loop's at every step), and
    the final state reaches full precision."""
    x = _chunky_field((80, 44), 2)
    buf = compress(x, 1e-6, CUBIC, chunk_elems=900)
    ladders = {}
    for bc in (False, True):
        r = open_archive(buf)
        st, rungs = None, []
        for E in (1e-1, 1e-3, None):
            kw = {} if E is None else dict(error_bound=E)
            out, st = retrieve(r, state=st, backend="jax", batch_chunks=bc,
                               **kw)
            rungs.append((out.copy(), st.bytes_read))
        ladders[bc] = (rungs, st)
    for (o1, b1), (o2, b2) in zip(ladders[False][0], ladders[True][0]):
        assert np.array_equal(o1, o2)
        assert b1 == b2
    # repeating the final bound adds no bytes (nothing re-read)
    st = ladders[True][1]
    prev = st.bytes_read
    out, st = refine(st, backend="jax", batch_chunks=True)
    assert st.bytes_read == prev
    assert metrics.linf(x, out) <= 1e-6


def test_batched_refine_mixed_prefix_groups():
    """Byte-budget plans give each chunk a different plane prefix, so the
    (nbits, prefix) decode grouping sees mixed keys — results must still
    match the loop exactly."""
    rng = np.random.default_rng(3)
    x = smooth_field((60, 33), 1)
    x[:20] += 0.5 * rng.standard_normal((20, 33))  # chunk 0 much rougher
    buf = compress(x, 1e-6, chunk_elems=700)
    for budget in (4000, 9000):
        a, sa = retrieve(open_archive(buf), max_bytes=budget, backend="jax",
                         batch_chunks=False)
        b, sb = retrieve(open_archive(buf), max_bytes=budget, backend="jax",
                         batch_chunks=True)
        assert np.array_equal(a, b)
        assert sa.bytes_read == sb.bytes_read


def test_batched_backend_switch_mid_refinement():
    """State stays backend- and batching-agnostic: numpy-started ladders
    refined on the batched jax engine equal the pure loop."""
    x = _chunky_field((40, 30), 7)
    buf = compress(x, 1e-6, chunk_elems=400)
    r1 = open_archive(buf)
    out1, st1 = retrieve(r1, error_bound=1e-2, backend="numpy")
    out1, st1 = retrieve(r1, error_bound=1e-5, state=st1, backend="jax",
                         batch_chunks=True)
    r2 = open_archive(buf)
    out2, st2 = retrieve(r2, error_bound=1e-2, backend="jax",
                         batch_chunks=True)
    out2, st2 = retrieve(r2, error_bound=1e-5, state=st2, backend="numpy")
    assert np.array_equal(out1, out2)
    assert st1.bytes_read == st2.bytes_read


def test_batched_with_escapes_bit_identical():
    """Escaped outliers land in specific chunks: the per-chunk override
    writeback inside the batched reconstruct must hit the same points."""
    x = smooth_field((40, 40), 1)
    x[13, 17] = 1e15
    x[35, 2] = -1e15
    with np.errstate(invalid="ignore"):
        buf = compress(x, 1e-7, CUBIC, chunk_elems=400)
    a, _ = retrieve(open_archive(buf), error_bound=1e-2, backend="jax",
                    batch_chunks=False)
    b, _ = retrieve(open_archive(buf), error_bound=1e-2, backend="jax",
                    batch_chunks=True)
    assert np.array_equal(a, b)
    assert metrics.linf(x, decompress(buf, backend="jax")) <= 1e-7


def test_batched_chunked_state_type_and_assembly():
    """The chunked state keeps its per-chunk structure under batching and
    the assembled array equals the per-chunk reconstructions."""
    x = _chunky_field((50, 41)).astype(np.float32)
    buf = compress(x, 1e-3, chunk_elems=500)
    out, st = retrieve(open_archive(buf), error_bound=1e-2, backend="jax",
                       batch_chunks=True)
    assert isinstance(st, ChunkedRetrievalState)
    assert out.dtype == np.float32
    for cm, cs in zip(st.reader.meta.chunks, st.chunk_states):
        assert np.array_equal(out[cm.start:cm.stop],
                              cs.xhat.astype(np.float32))
        assert metrics.linf(x[cm.start:cm.stop], cs.xhat) <= 1e-2
