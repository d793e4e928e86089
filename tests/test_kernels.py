"""Pallas kernels vs pure-jnp oracles (interpret mode, shape/dtype sweeps)."""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _seed(*key) -> int:
    """Deterministic per-case RNG seed (``hash()`` of strings is randomized
    per process, which made the sweep data — and one-in-many-runs edge-case
    draws — unreproducible)."""
    return zlib.crc32(repr(key).encode())

from repro.kernels.interp_quant import interp_quant, interp_quant_ref
from repro.kernels.interp_recon import interp_recon, interp_recon_ref
from repro.kernels.bitplane_pack import (bitplane_pack, bitplane_pack_ref,
                                         bitplane_unpack,
                                         bitplane_unpack_ref,
                                         unpack_planes_ref)
from repro.core import negabinary as nbmod
from repro.core import bitplane as bpmod
from repro.core import interpolation


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("shape,s", [
    ((8, 128), 1), ((8, 128), 4), ((16, 256), 1), ((16, 256), 8),
    ((3, 96), 1),          # unaligned rows -> wrapper pads
    ((8, 130), 1),         # odd width, boundary fallback at right edge
    ((8, 129), 2),         # odd width, stride 2
    ((40, 512), 16),
])
@pytest.mark.parametrize("interp", ["linear", "cubic"])
def test_interp_quant_matches_ref(shape, s, interp, dtype):
    if dtype == jnp.float64 and not jax.config.read("jax_enable_x64"):
        pytest.skip("x64 disabled")
    rng = np.random.default_rng(_seed(shape, s, interp))
    R, C = shape
    if len(range(s, C, 2 * s)) == 0:
        pytest.skip("no targets")
    x = jnp.asarray(rng.standard_normal(shape), dtype)
    # xhat: known points only (even multiples of s carry values)
    xh = jnp.asarray(rng.standard_normal(shape), dtype)
    eb = 1e-3
    q, pred = interp_quant(x, xh, s=s, eb=eb, interp=interp)
    q_ref, pred_ref = interp_quant_ref(x, xh, s, eb, interp)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
    np.testing.assert_allclose(np.asarray(pred), np.asarray(pred_ref),
                               rtol=1e-6, atol=1e-6)


def test_interp_quant_error_bound():
    """Reconstruction pred + 2eb*q at targets obeys |x - recon| <= eb."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((16, 256)), jnp.float32)
    xh = jnp.asarray(rng.standard_normal((16, 256)), jnp.float32)
    eb = 1e-2
    q, pred = interp_quant(x, xh, s=2, eb=eb)
    recon = np.asarray(pred, np.float64) + \
        np.asarray(q, np.float64) * (2.0 * eb)
    tgt = np.asarray(x)[:, 2::4]
    assert np.abs(tgt - recon).max() <= eb * (1 + 1e-5)


@pytest.mark.parametrize("shape", [(8, 32), (8, 128), (16, 256), (5, 96),
                                   (8, 131)])
def test_bitplane_pack_matches_ref(shape):
    rng = np.random.default_rng(shape[1])
    q = rng.integers(-(1 << 20), 1 << 20, size=shape).astype(np.int32)
    packed, n, _ = bitplane_pack(q)
    # oracle on the padded array the wrapper actually packed
    R, C = shape
    pr, pc = (-R) % 8, (-C) % 32
    qp = np.pad(q, ((0, pr), (0, pc)))
    ref = bitplane_pack_ref(jnp.asarray(qp))
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(ref))


@pytest.mark.parametrize("keep", [0, 1, 5, 17, 32])
def test_bitplane_pack_prefix_decodes_to_truncation(keep):
    """Kernel planes decode (via oracle) to negabinary truncation — the same
    invariant the CPU container relies on (§4.4)."""
    rng = np.random.default_rng(keep)
    q = rng.integers(-(1 << 24), 1 << 24, size=(8, 64)).astype(np.int32)
    packed, _, _ = bitplane_pack(q)
    got_nb = np.asarray(unpack_planes_ref(jnp.asarray(packed), keep))
    want = nbmod.truncate(nbmod.to_negabinary(q.astype(np.int64).ravel()),
                          32 - keep).reshape(8, 64)
    np.testing.assert_array_equal(got_nb, want.astype(np.uint32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("shape,s", [
    ((8, 128), 1), ((8, 128), 4), ((16, 256), 8),
    ((3, 96), 1),          # unaligned rows -> wrapper pads
    ((8, 130), 1),         # odd width, boundary fallback at right edge
    ((8, 129), 2),         # odd width, stride 2
])
@pytest.mark.parametrize("interp", ["linear", "cubic"])
def test_interp_recon_matches_ref(shape, s, interp, dtype):
    if dtype == jnp.float64 and not jax.config.read("jax_enable_x64"):
        pytest.skip("x64 disabled")
    rng = np.random.default_rng(_seed("recon", shape, s, interp))
    R, C = shape
    T = len(range(s, C, 2 * s))
    if T == 0:
        pytest.skip("no targets")
    xh = jnp.asarray(rng.standard_normal(shape), dtype)
    res = jnp.asarray(rng.standard_normal((R, T)), dtype)
    out = interp_recon(xh, res, s=s, interp=interp)
    ref = interp_recon_ref(xh, res, s, interp)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_interp_recon_bit_identical_to_numpy_sweep():
    """Decode kernel == interpolation.predict_block + res, bitwise (f64) —
    the invariant that makes jax retrieval parity possible at all."""
    with jax.enable_x64(True):
        rng = np.random.default_rng(11)
        for (R, C), s, interp in [((8, 128), 1, "cubic"),
                                  ((5, 257), 4, "cubic"),
                                  ((3, 96), 2, "linear")]:
            xh = rng.standard_normal((R, C)) * 50
            idx = np.arange(s, C, 2 * s)
            res = rng.standard_normal((R, idx.size))
            out = np.asarray(interp_recon(xh, res, s=s, interp=interp))
            pred = interpolation.predict_block(xh, 1, idx, s, C, interp)
            np.testing.assert_array_equal(out, pred + res)


def test_interp_recon_inverts_interp_quant():
    """recon(xhat, dequantized q) == the encode sweep's lossy writeback."""
    with jax.enable_x64(True):
        rng = np.random.default_rng(3)
        x = rng.standard_normal((8, 256))
        xh = rng.standard_normal((8, 256))
        eb = 1e-3
        q, pred = interp_quant(x, xh, s=2, eb=eb)
        res = np.asarray(q, np.float64) * (2.0 * eb)
        recon = np.asarray(interp_recon(xh, res, s=2))
        want = np.asarray(pred, np.float64) + res
        np.testing.assert_array_equal(recon, want)
        tgt = x[:, 2::4]
        assert np.abs(tgt - recon).max() <= eb * (1 + 1e-12)


@pytest.mark.parametrize("keep", [0, 1, 5, 17, 32])
def test_bitplane_unpack_kernel_matches_truncation(keep):
    """Closed-form XOR-inverse kernel == sequential oracle == negabinary
    truncation, over a pack -> unpack round trip."""
    rng = np.random.default_rng(keep + 100)
    n = 5000
    q = rng.integers(-(1 << 30), 1 << 30, n).astype(np.int32)
    q[0], q[1] = (1 << 30), -(1 << 30)
    packed, _, _ = bitplane_pack(q)
    packed = np.asarray(packed)
    words = packed.reshape(32, -1).copy()
    low = 32 - keep
    if low > 0:
        words[:low] = 0           # absent planes arrive as all-zero streams
    got = np.asarray(bitplane_unpack(words, n=n, low_zero=low))
    want_nb = nbmod.truncate(nbmod.to_negabinary(q.astype(np.int64)), low)
    want = nbmod.from_negabinary(want_nb)
    np.testing.assert_array_equal(got.astype(np.int64), want)
    ref = np.asarray(bitplane_unpack_ref(jnp.asarray(packed),
                                         keep)).reshape(-1)[:n]
    np.testing.assert_array_equal(ref.astype(np.int64), want)


@pytest.mark.parametrize("n", [1, 31, 32, 33, 4096, 4097])
def test_bitplane_unpack_padding_edges(n):
    """n not a multiple of the word/row geometry: full round trip exact."""
    rng = np.random.default_rng(n)
    q = rng.integers(-(1 << 24), 1 << 24, n).astype(np.int32)
    packed, _, _ = bitplane_pack(q)
    words = np.asarray(packed).reshape(32, -1)
    got = np.asarray(bitplane_unpack(words, n=n, low_zero=0))
    np.testing.assert_array_equal(got, q)


def test_bitplane_pack_agrees_with_cpu_container_bits():
    """Plane k bit content matches the CPU pipeline's XOR-encoded plane k."""
    rng = np.random.default_rng(3)
    q = rng.integers(-(1 << 15), 1 << 15, size=(8, 32)).astype(np.int32)
    packed, _, _ = bitplane_pack(q)
    nb = nbmod.to_negabinary(q.astype(np.int64).ravel())
    planes = bpmod.split_planes(nb, 32)
    enc = bpmod.xor_encode(planes)
    for k in (0, 3, 12, 31):
        word = np.asarray(packed[k]).reshape(8, 1)
        bits = ((word >> np.arange(31, -1, -1, dtype=np.uint32)) & 1).ravel()
        np.testing.assert_array_equal(bits.astype(np.uint8), enc[k])
