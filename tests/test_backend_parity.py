"""numpy vs jax (Pallas) compression backend parity.

The jax backend must be a drop-in: same archive bytes, same decode, same
escape channel, across dims/interps/dtypes — including the adversarial
regimes that historically broke bit-exactness (fma contraction on rough
data, int32 wrap/saturation at escape outliers, kernel pad-region
truncation in the bitplane packer).
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # tier-1 container has no hypothesis; vendored fallback
    from _hypothesis_shim import given, settings, strategies as st

from _fields import smooth_field
from repro.core import (CUBIC, LINEAR, compress, decompress, jax_backend,
                        metrics, retrieve)
from repro.core import bitplane as bp
from repro.core import negabinary as nbmod


# ------------------------------------------------------- archive parity

# float32 fields run the float32 arithmetic contract (core.arith) on both
# backends: same archive bytes, same reconstruction bits

def _f32_hostile(shape, seed):
    """float32 field with the values the contract has to pin down:
    subnormals of both signs, tiny normals whose sums underflow, zeros."""
    rng = np.random.default_rng(seed)
    x = smooth_field(shape, seed).astype(np.float32)
    flat = x.reshape(-1)
    flat[::7] = np.float32(3e-39)
    flat[3::11] = np.float32(-1e-41)
    flat[5::13] *= np.float32(1e-37)
    flat[1::17] = 0.0
    return x + np.float32(0) * rng.standard_normal(shape).astype(np.float32)



@pytest.mark.parametrize("shape", [(257,), (33, 41), (17, 13, 11)])
@pytest.mark.parametrize("interp", [LINEAR, CUBIC])
def test_archives_byte_identical_smooth(shape, interp):
    x = smooth_field(shape)
    eb = 1e-4 * (x.max() - x.min())
    a = compress(x, eb, interp, backend="numpy")
    b = compress(x, eb, interp, backend="jax")
    assert a == b
    xa, xb = decompress(a), decompress(b)
    assert np.array_equal(xa, xb)
    assert metrics.linf(x, xb) <= eb


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 3), st.integers(0, 10 ** 6),
       st.sampled_from([LINEAR, CUBIC]),
       st.floats(1e-5, 1e-1))
def test_archives_byte_identical_property(ndim, seed, interp, rel_eb):
    """Rough random data + large relative eb: the fma-sensitive regime."""
    rng = np.random.default_rng(seed)
    shape = tuple(int(rng.integers(2, [160, 30, 14][ndim - 1]))
                  for _ in range(ndim))
    x = rng.standard_normal(shape) * rng.uniform(0.1, 100)
    eb = rel_eb * (x.max() - x.min())
    a = compress(x, eb, interp, backend="numpy")
    b = compress(x, eb, interp, backend="jax")
    assert a == b
    assert np.array_equal(decompress(a), decompress(b))


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_archives_byte_identical_recorded_counterexample(dtype):
    """The case hypothesis once recorded against the byte-parity property
    (ndim=1, seed=0, linear, rel_eb=0.0625), pinned for both contracts."""
    rng = np.random.default_rng(0)
    shape = (int(rng.integers(2, 160)),)
    x = (rng.standard_normal(shape) * rng.uniform(0.1, 100)).astype(dtype)
    eb = 0.0625 * float(x.max() - x.min())
    a = compress(x, eb, LINEAR, backend="numpy")
    b = compress(x, eb, LINEAR, backend="jax")
    assert a == b
    assert decompress(a).tobytes() == decompress(b, backend="jax").tobytes()


def test_archives_byte_identical_with_escapes():
    """Outliers exercise the int32 wrap/saturate path of the kernel bins."""
    x = smooth_field((40, 40), 1)
    x[13, 17] = 1e15
    x[0, 0] = -1e15
    eb = 1e-7
    with np.errstate(invalid="ignore"):
        a = compress(x, eb, CUBIC, backend="numpy")
    b = compress(x, eb, CUBIC, backend="jax")
    assert a == b
    assert metrics.linf(x, decompress(b)) <= eb


@pytest.mark.parametrize("shape", [(257,), (33, 41), (17, 13, 11)])
@pytest.mark.parametrize("interp", [LINEAR, CUBIC])
def test_archives_byte_identical_smooth_f32(shape, interp):
    x = smooth_field(shape).astype(np.float32)
    eb = 1e-4 * float(x.max() - x.min())
    a = compress(x, eb, interp, backend="numpy")
    b = compress(x, eb, interp, backend="jax")
    assert a == b
    xa, xb = decompress(a), decompress(b, backend="jax")
    assert xb.dtype == np.float32 and xa.tobytes() == xb.tobytes()
    assert metrics.linf(x, xb) <= eb


@pytest.mark.parametrize("interp", [LINEAR, CUBIC])
def test_archives_byte_identical_f32_subnormals(interp):
    """Subnormal operands and underflowing sums: the kernels and the numpy
    reference flush them identically (and the bound still holds)."""
    x = _f32_hostile((37, 29), 4)
    eb = 1e-5 * float(x.max() - x.min())
    a = compress(x, eb, interp, backend="numpy")
    b = compress(x, eb, interp, backend="jax")
    assert a == b
    xa, xb = decompress(a), decompress(b, backend="jax")
    assert xa.tobytes() == xb.tobytes()
    assert metrics.linf(x, xb) <= eb


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_f32_bound_by_construction_near_ulp(backend):
    """eb of about one float32 ulp of the data: rounding alone would break
    the bound, so the verify-and-escape screen must do real work — and
    every read, full or partial, must still meet its reported bound."""
    from repro.core.container import parse_meta

    x = (1000.0 + 50.0 * smooth_field((40, 36), 6)).astype(np.float32)
    eb = float(np.spacing(np.float32(np.abs(x).max())))
    buf = compress(x, eb, CUBIC, backend=backend)
    meta = parse_meta(buf)
    assert sum(lv.esc_size for lv in meta.levels) > 0  # the screen escaped
    full = decompress(buf, backend=backend)
    assert np.abs(full.astype(np.float64) - x).max() < eb
    for E in (1e3 * eb, 30 * eb):
        out, st = retrieve(buf, error_bound=E, backend=backend)
        assert st.err_bound <= E
        assert np.abs(out.astype(np.float64) - x).max() <= st.err_bound


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 3), st.integers(0, 10 ** 6),
       st.sampled_from([LINEAR, CUBIC]), st.floats(1e-7, 1e-1))
def test_archives_byte_identical_f32_property(ndim, seed, interp, rel_eb):
    """Rough float32 data over eb from near-ulp to coarse: bytes and bits
    equal, and the full read within eb."""
    rng = np.random.default_rng(seed)
    shape = tuple(int(rng.integers(2, [160, 30, 14][ndim - 1]))
                  for _ in range(ndim))
    x = (rng.standard_normal(shape) * rng.uniform(0.1, 100)
         ).astype(np.float32)
    eb = rel_eb * float(x.max() - x.min())
    a = compress(x, eb, interp, backend="numpy")
    b = compress(x, eb, interp, backend="jax")
    assert a == b
    xa, xb = decompress(a), decompress(b, backend="jax")
    assert xa.tobytes() == xb.tobytes()
    assert np.abs(xb.astype(np.float64) - x).max() < eb


def test_archives_byte_identical_f32_and_chunked():
    x = smooth_field((50, 60), 2).astype(np.float32)
    a = compress(x, 1e-3, backend="numpy")
    b = compress(x, 1e-3, backend="jax")
    assert a == b
    assert decompress(b).dtype == np.float32
    y = smooth_field((96, 50), 3)
    a = compress(y, 1e-5, CUBIC, backend="numpy", chunk_elems=1000)
    b = compress(y, 1e-5, CUBIC, backend="jax", chunk_elems=1000)
    assert a == b


def test_jax_archive_readable_by_numpy_retrieve():
    """Cross-backend progressive read: jax-written, numpy-planned/decoded."""
    x = smooth_field((48, 48))
    buf = compress(x, 1e-6, CUBIC, backend="jax")
    for E in (1e-2, 1e-4):
        out, state = retrieve(buf, error_bound=E)
        assert metrics.linf(x, out) <= E
        assert 0 < state.bytes_read < len(buf)


def test_backend_resolve():
    assert jax_backend.resolve("numpy") == "numpy"
    assert jax_backend.resolve("jax") == "jax"
    assert jax_backend.resolve(None) in ("numpy", "jax")
    assert jax_backend.resolve("auto") == jax_backend.resolve(None)
    with pytest.raises(ValueError):
        jax_backend.resolve("cuda")


# ------------------------------------------- bitplane_pack blob parity

def _enc_parity(q):
    q = np.asarray(q, np.int64)
    nb = nbmod.to_negabinary(q)
    want = bp.encode_level(nb)
    got = jax_backend.encode_level(q)
    assert got[1] == want[1], "nbits mismatch"
    assert got[0] == want[0], "blob mismatch"


@pytest.mark.parametrize("n", [1, 7, 255, 256, 4095, 4096, 4097, 8192 + 3])
def test_encode_level_padding_edges(n):
    """n not a multiple of ROWS_B*GROUP: pad region must not leak into blobs."""
    rng = np.random.default_rng(n)
    _enc_parity(rng.integers(-(1 << 20), 1 << 20, n))


def test_encode_level_nbits_zero():
    _enc_parity(np.zeros(100, np.int64))        # all-zero: ([], 0)
    assert jax_backend.encode_level(np.zeros(0, np.int64)) == ([], 0)


def test_encode_level_all_zero_middle_plane():
    """A zero XOR-plane below the MSB must produce the b'' blob convention."""
    # nb(5) = 0b101 -> enc = 0b110: plane 0 all-zero, planes 1-2 set
    _enc_parity(np.full(500, 5, np.int64))


def test_encode_level_extreme_bins():
    """Bins at the QMAX boundary occupy all 32 negabinary digits."""
    rng = np.random.default_rng(0)
    q = rng.integers(-(1 << 30), 1 << 30, 3000)
    q[0], q[1] = (1 << 30), -(1 << 30)
    _enc_parity(q)


@given(st.lists(st.integers(-(1 << 30), 1 << 30), min_size=1, max_size=400))
def test_encode_level_property(vals):
    _enc_parity(np.array(vals, np.int64))
