"""numpy vs jax (Pallas) DECODE backend parity.

The acceptance bar of the backend-symmetric decode path: ``retrieve`` /
``refine`` / ``decompress`` with ``backend="jax"`` (interpret mode on CPU)
must produce BIT-IDENTICAL arrays to ``backend="numpy"`` on every field —
including the escape-override path, Algorithm 2's incremental zero-anchor
delta cascade, and chunked (v2) archives — plus primitive-level parity of
``decode_level`` (kernel bit-unpack + closed-form XOR-undo + negabinary
decode) against the sequential host reference.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # tier-1 container has no hypothesis; vendored fallback
    from _hypothesis_shim import given, settings, strategies as st

from _fields import smooth_field
from repro.core import (CUBIC, LINEAR, compress, decompress, jax_backend,
                        metrics, open_archive, refine, retrieve)
from repro.core import bitplane as bp
from repro.core import interpolation, negabinary as nbmod
from repro.core.pipeline import backends


# ------------------------------------------------------ full-array parity

@pytest.mark.parametrize("shape", [(257,), (33, 41), (17, 13, 11)])
@pytest.mark.parametrize("interp", [LINEAR, CUBIC])
def test_decompress_bit_identical_smooth(shape, interp):
    x = smooth_field(shape)
    eb = 1e-4 * (x.max() - x.min())
    buf = compress(x, eb, interp)
    a = decompress(buf, backend="numpy")
    b = decompress(buf, backend="jax")
    assert np.array_equal(a, b)
    assert metrics.linf(x, b) <= eb


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 3), st.integers(0, 10 ** 6),
       st.sampled_from([LINEAR, CUBIC]), st.floats(1e-5, 1e-1))
def test_retrieve_bit_identical_property(ndim, seed, interp, rel_eb):
    """Rough random data: the fma-sensitive regime of the recon kernel."""
    rng = np.random.default_rng(seed)
    shape = tuple(int(rng.integers(2, [120, 24, 12][ndim - 1]))
                  for _ in range(ndim))
    x = rng.standard_normal(shape) * rng.uniform(0.1, 100)
    eb = rel_eb * (x.max() - x.min())
    buf = compress(x, eb, interp)
    E = 10.0 * eb
    a, sa = retrieve(buf, error_bound=E, backend="numpy")
    b, sb = retrieve(buf, error_bound=E, backend="jax")
    assert np.array_equal(a, b)
    assert sa.err_bound == sb.err_bound
    assert sa.bytes_read == sb.bytes_read


@pytest.mark.parametrize("chunk_elems", [None, 300])
@pytest.mark.parametrize("interp", [LINEAR, CUBIC])
def test_retrieve_bit_identical_f32(interp, chunk_elems):
    """float32 archives (float32 arithmetic): a refine ladder on numpy and
    jax stays bit-identical at every rung, accounting included."""
    x = smooth_field((41, 30), 8).astype(np.float32)
    x[9, 4] = np.float32(3e30)   # an escape
    eb = 1e-6 * float(np.ptp(smooth_field((41, 30), 8)))
    buf = compress(x, eb, interp, chunk_elems=chunk_elems)
    sa = sb = None
    for E in (1e-2, 1e-4, None):
        kw = {} if E is None else dict(error_bound=E)
        a, sa = retrieve(open_archive(buf) if sa is None else sa.reader,
                         state=sa, backend="numpy", **kw)
        b, sb = retrieve(open_archive(buf) if sb is None else sb.reader,
                         state=sb, backend="jax", **kw)
        assert a.dtype == b.dtype == np.float32
        assert a.tobytes() == b.tobytes()
        assert sa.err_bound == sb.err_bound
        assert sa.bytes_read == sb.bytes_read
        assert metrics.linf(x, b) <= sb.err_bound
    assert metrics.linf(x, b) <= eb


def test_decode_bit_identical_with_escapes():
    """Escaped outliers: the exact-override writeback must land identically
    (initial state AND pinned-zero deltas on later refinements)."""
    x = smooth_field((40, 40), 1)
    x[13, 17] = 1e15
    x[0, 0] = -1e15
    with np.errstate(invalid="ignore"):
        buf = compress(x, 1e-7, CUBIC)
    for E in (1e-2, None):
        kw = {} if E is None else dict(error_bound=E)
        a, _ = retrieve(buf, backend="numpy", **kw)
        b, _ = retrieve(buf, backend="jax", **kw)
        assert np.array_equal(a, b)
    assert metrics.linf(x, decompress(buf, backend="jax")) <= 1e-7


def test_refine_delta_cascade_bit_identical():
    """Algorithm 2 on the kernels: every rung of a progressive ladder is
    bit-identical, including the final full-precision refine()."""
    x = smooth_field((48, 36), 2)
    buf = compress(x, 1e-7, CUBIC)
    states = {}
    for bk in ("numpy", "jax"):
        r = open_archive(buf)
        st_, outs = None, []
        for E in (1e-1, 1e-3, 1e-6):
            out, st_ = retrieve(r, error_bound=E, state=st_, backend=bk)
            outs.append(out.copy())
        out, st_ = refine(st_, backend=bk)       # to full precision
        outs.append(out)
        states[bk] = (outs, st_)
    for a, b in zip(states["numpy"][0], states["jax"][0]):
        assert np.array_equal(a, b)
    assert states["numpy"][1].bytes_read == states["jax"][1].bytes_read


def test_backend_switch_mid_refinement():
    """State is backend-agnostic: numpy-started, jax-refined (and vice
    versa) equals a single-backend ladder bit-for-bit."""
    x = smooth_field((40, 30), 7)
    buf = compress(x, 1e-6)
    r1 = open_archive(buf)
    out1, st1 = retrieve(r1, error_bound=1e-2, backend="numpy")
    out1, st1 = retrieve(r1, error_bound=1e-5, state=st1, backend="jax")
    r2 = open_archive(buf)
    out2, st2 = retrieve(r2, error_bound=1e-2, backend="jax")
    out2, st2 = retrieve(r2, error_bound=1e-5, state=st2, backend="numpy")
    r3 = open_archive(buf)
    out3, st3 = retrieve(r3, error_bound=1e-2, backend="numpy")
    out3, st3 = retrieve(r3, error_bound=1e-5, state=st3, backend="numpy")
    assert np.array_equal(out1, out2)
    assert np.array_equal(out1, out3)


def test_chunked_v2_decode_bit_identical():
    """The acceptance path for v2: per-chunk kernel decode == numpy."""
    x = smooth_field((96, 50), 3)
    buf = compress(x, 1e-6, CUBIC, chunk_elems=1000)
    a, sa = retrieve(buf, error_bound=1e-3, backend="numpy")
    b, sb = retrieve(buf, error_bound=1e-3, backend="jax")
    assert np.array_equal(a, b)
    assert sa.bytes_read == sb.bytes_read
    a2, _ = retrieve(sa.reader, state=sa, backend="numpy")
    b2, _ = retrieve(sb.reader, state=sb, backend="jax")
    assert np.array_equal(a2, b2)
    assert metrics.linf(x, b2) <= 1e-6


def test_chunked_v2_batched_decode_bit_identical():
    """Shape-group batched v2 decode (vmapped kernels) == numpy, including
    a mid-ladder refine; dispatch scheduling must not perturb parity."""
    x = smooth_field((96, 50), 3)
    buf = compress(x, 1e-6, CUBIC, chunk_elems=1000)
    a, sa = retrieve(buf, error_bound=1e-3, backend="numpy")
    b, sb = retrieve(buf, error_bound=1e-3, backend="jax", batch_chunks=True)
    assert np.array_equal(a, b)
    assert sa.bytes_read == sb.bytes_read
    a2, _ = retrieve(sa.reader, state=sa, backend="numpy")
    b2, _ = retrieve(sb.reader, state=sb, backend="jax", batch_chunks=True)
    assert np.array_equal(a2, b2)
    assert metrics.linf(x, b2) <= 1e-6


def test_f32_dtype_preserved():
    x = smooth_field((50, 60), 2).astype(np.float32)
    buf = compress(x, 1e-3)
    b = decompress(buf, backend="jax")
    assert b.dtype == np.float32
    assert np.array_equal(decompress(buf, backend="numpy"), b)


def test_bitrate_mode_parity():
    x = smooth_field((64, 64), 4)
    buf = compress(x, 1e-7, CUBIC)
    for bpp in (0.5, 2.0):
        a, sa = retrieve(buf, bitrate=bpp, backend="numpy")
        b, sb = retrieve(buf, bitrate=bpp, backend="jax")
        assert np.array_equal(a, b)
        assert sa.bytes_read == sb.bytes_read


# ----------------------------------------------------- primitive parity

def _dec_parity(q, wants=None):
    q = np.asarray(q, np.int64)
    nb = nbmod.to_negabinary(q)
    blobs, nbits = bp.encode_level(nb)
    blobs = bp.wait(blobs)
    if wants is None:
        wants = sorted({0, 1, nbits // 2, max(nbits - 1, 0), nbits})
    for want in wants:
        loaded = [blobs[i] if i < want else None for i in range(nbits)]
        a = bp.decode_level(loaded, nbits, q.size)
        b = jax_backend.decode_level(loaded, nbits, q.size)
        assert np.array_equal(a, b), f"want={want}"


@pytest.mark.parametrize("n", [1, 7, 255, 4096, 4097, 8192 + 3])
def test_decode_level_parity_padding_edges(n):
    rng = np.random.default_rng(n)
    _dec_parity(rng.integers(-(1 << 20), 1 << 20, n))


def test_decode_level_parity_all_zero_middle_plane():
    """b'' (loaded, all-zero encoded plane) must still XOR-propagate."""
    _dec_parity(np.full(500, 5, np.int64))


def test_decode_level_parity_extreme_bins():
    rng = np.random.default_rng(0)
    q = rng.integers(-(1 << 30), 1 << 30, 3000)
    q[0], q[1] = (1 << 30), -(1 << 30)
    _dec_parity(q)


def test_decode_level_empty_and_nbits_zero():
    assert np.array_equal(jax_backend.decode_level([], 0, 0),
                          np.zeros(0, np.uint32))
    assert np.array_equal(jax_backend.decode_level([None] * 5, 5, 100),
                          np.zeros(100, np.uint32))


@given(st.lists(st.integers(-(1 << 30), 1 << 30), min_size=1, max_size=300))
def test_decode_level_parity_property(vals):
    _dec_parity(np.array(vals, np.int64))


def test_reconstruct_parity_direct():
    """jax_backend.reconstruct == interpolation.reconstruct bit-for-bit on
    a full-precision residual set with overrides."""
    rng = np.random.default_rng(5)
    shape = (19, 23)
    L = interpolation.num_levels(shape)
    sizes = interpolation.level_sizes(shape, L)
    anchors_shape = np.zeros(shape)[interpolation.anchor_slices(shape, L)].shape
    anchors = rng.standard_normal(anchors_shape)
    yhat = [rng.standard_normal(n) for n in sizes]
    overrides = []
    for n in sizes:
        k = min(3, n)
        idx = np.sort(rng.choice(n, size=k, replace=False)).astype(np.int64)
        overrides.append((idx, rng.standard_normal(k) * 1e6))
    a = interpolation.reconstruct(shape, CUBIC, anchors, yhat,
                                  overrides=overrides)
    b = jax_backend.reconstruct(shape, CUBIC, anchors, yhat,
                                overrides=overrides)
    assert np.array_equal(a, b)


# ------------------------------------------------------------- registry

def test_registry_resolution():
    assert backends.get("numpy").name == "numpy"
    assert backends.get("jax").name == "jax"
    assert backends.get(None).name in ("numpy", "jax")
    assert backends.get("auto").name == backends.get(None).name
    assert backends.names() == ["numpy", "jax"] or \
        backends.names() == sorted(backends.names())
    with pytest.raises(ValueError):
        backends.get("cuda")
    # the historical alias keeps working and agrees with the registry
    assert jax_backend.resolve("jax") == "jax"
    assert jax_backend.resolve(None) == backends.resolve_name(None)
