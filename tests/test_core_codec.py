"""Core IPComp codec: round-trip, error-bound, and progressive invariants."""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # tier-1 container has no hypothesis; vendored fallback
    from _hypothesis_shim import given, settings, strategies as st

from _fields import smooth_field
from repro.core import (CUBIC, LINEAR, compress, decompress, metrics,
                        open_archive, retrieve)
from repro.core import negabinary as nb
from repro.core import bitplane as bp
from repro.core import loader
from repro.core.container import parse_meta


# ------------------------------------------------------------ negabinary

@given(st.lists(st.integers(-(1 << 30), 1 << 30), min_size=1, max_size=200))
def test_negabinary_roundtrip(vals):
    q = np.array(vals, np.int64)
    assert np.array_equal(nb.from_negabinary(nb.to_negabinary(q)), q)


def test_negabinary_paper_examples():
    # paper §4.4.2: 1 -> ...0001, -1 -> ...0011
    assert int(nb.to_negabinary(np.array([1]))[0]) == 0b1
    assert int(nb.to_negabinary(np.array([-1]))[0]) == 0b11
    assert int(nb.to_negabinary(np.array([-2]))[0]) == 0b10


@given(st.lists(st.integers(-(1 << 20), 1 << 20), min_size=1, max_size=64),
       st.integers(0, 24))
def test_negabinary_truncation_uncertainty(vals, d):
    """Truncating d digits perturbs the value by < (2/3)*2^d + 1 (paper formula)."""
    q = np.array(vals, np.int64)
    x = nb.to_negabinary(q)
    t = nb.from_negabinary(nb.truncate(x, d))
    bound = (2.0 / 3.0) * (1 << d)
    assert np.all(np.abs(q - t) <= bound + 1)


# ------------------------------------------------------------ bitplanes

@given(st.lists(st.integers(0, (1 << 31) - 1), min_size=1, max_size=300))
def test_bitplane_roundtrip(vals):
    x = np.array(vals, np.uint32)
    blobs, nbits = bp.encode_level(x)
    blobs = bp.wait(blobs)
    got = bp.decode_level(list(blobs), nbits, x.size)
    assert np.array_equal(got, x)


@given(st.lists(st.integers(0, (1 << 20) - 1), min_size=4, max_size=200),
       st.integers(0, 19))
def test_bitplane_prefix_decode_is_truncation(vals, keep_from_msb):
    """Loading a plane prefix must equal negabinary truncation exactly."""
    x = np.array(vals, np.uint32)
    blobs, nbits = bp.encode_level(x)
    blobs = bp.wait(blobs)
    k = min(keep_from_msb, nbits)
    part = list(blobs[:k]) + [None] * (nbits - k)
    got = bp.decode_level(part, nbits, x.size)
    assert np.array_equal(got, nb.truncate(x, nbits - k))


# ------------------------------------------------------------ round trip

@pytest.mark.parametrize("shape", [(1000,), (64, 80), (24, 37, 41)])
@pytest.mark.parametrize("interp", [LINEAR, CUBIC])
def test_roundtrip_error_bound(shape, interp):
    x = smooth_field(shape)
    eb = 1e-4 * (x.max() - x.min())
    buf = compress(x, eb, interp)
    xh = decompress(buf)
    assert metrics.linf(x, xh) <= eb
    assert len(buf) < x.nbytes  # it actually compresses smooth data


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 3), st.integers(0, 10 ** 6),
       st.sampled_from([LINEAR, CUBIC]),
       st.floats(1e-6, 1e-1))
def test_roundtrip_property(ndim, seed, interp, rel_eb):
    rng = np.random.default_rng(seed)
    shape = tuple(int(rng.integers(2, [200, 40, 18][ndim - 1])) for _ in range(ndim))
    x = rng.standard_normal(shape) * rng.uniform(0.1, 100)
    eb = rel_eb * (x.max() - x.min())
    xh = decompress(compress(x, eb, interp))
    assert metrics.linf(x, xh) <= eb * (1 + 1e-12)


def test_outlier_escape_channel():
    """Huge outliers (escape channel) must still satisfy the bound exactly."""
    x = smooth_field((40, 40))
    x[13, 17] = 1e15
    x[0, 0] = -1e15
    eb = 1e-7
    xh = decompress(compress(x, eb, CUBIC))
    assert metrics.linf(x, xh) <= eb


def test_f32_input_roundtrip():
    x = smooth_field((50, 60)).astype(np.float32)
    eb = 1e-3
    xh = decompress(compress(x, eb))
    assert xh.dtype == np.float32
    assert metrics.linf(x, xh) <= eb + 1e-6  # f32 cast slack


# ------------------------------------------------------------ progressive

def test_progressive_error_bounds_hold():
    x = smooth_field((48, 48, 48))
    buf = compress(x, 1e-6, CUBIC)
    r = open_archive(buf)
    st_ = None
    prev_bytes = 0
    for E in (1e-1, 1e-2, 1e-3, 1e-4, 1e-5):
        out, st_ = retrieve(r, error_bound=E, state=st_)
        assert metrics.linf(x, out) <= E, f"bound {E} violated"
        assert st_.err_bound <= E
        assert st_.bytes_read >= prev_bytes  # refinement only adds data
        prev_bytes = st_.bytes_read


def test_progressive_adversarial_noise():
    rng = np.random.default_rng(7)
    x = rng.standard_normal((37, 53)) * 50
    buf = compress(x, 1e-3, CUBIC)
    for E in (10.0, 1.0, 1e-1, 1e-2):
        out, _ = retrieve(buf, error_bound=E)
        assert metrics.linf(x, out) <= E


def test_refine_equals_scratch():
    x = smooth_field((32, 40, 24))
    buf = compress(x, 1e-5, CUBIC)
    r = open_archive(buf)
    out_a, st_ = retrieve(r, error_bound=1e-1)
    out_a, st_ = retrieve(r, error_bound=1e-3, state=st_)
    out_a, st_ = retrieve(r, state=st_)           # full
    out_b = decompress(buf)
    np.testing.assert_allclose(out_a, out_b, atol=1e-12)


def test_single_pass_retrieval_volume():
    """Partial retrieval must touch strictly less data than the archive."""
    x = smooth_field((48, 48, 48))
    buf = compress(x, 1e-6, CUBIC)
    out, st_ = retrieve(buf, error_bound=1e-2)
    assert 0 < st_.bytes_read < len(buf)


def test_bitrate_mode_respects_budget():
    x = smooth_field((48, 48, 48))
    buf = compress(x, 1e-6, CUBIC)
    n = x.size
    for target_bpp in (0.5, 1.0, 2.0, 4.0):
        out, st_ = retrieve(buf, bitrate=target_bpp)
        got_bpp = 8 * st_.bytes_read / n
        assert got_bpp <= target_bpp * 1.05 + 0.2
        # fidelity should improve with bitrate
    errs = []
    for target_bpp in (0.5, 1.0, 2.0, 4.0):
        out, _ = retrieve(buf, bitrate=target_bpp)
        errs.append(metrics.linf(x, out))
    assert errs == sorted(errs, reverse=True) or errs[-1] <= errs[0]


def test_arbitrary_error_bounds_supported():
    """IPComp supports arbitrary eb (vs residual baselines' fixed ladder)."""
    x = smooth_field((40, 40))
    buf = compress(x, 1e-7, CUBIC)
    rng = np.random.default_rng(3)
    for _ in range(10):
        E = 10 ** rng.uniform(-6.5, -1)
        out, _ = retrieve(buf, error_bound=E)
        assert metrics.linf(x, out) <= E


# ------------------------------------------------------------ DP loader

def _tiny_meta():
    x = smooth_field((32, 32))
    buf = compress(x, 1e-5, CUBIC)
    return parse_meta(buf), buf, x


def test_dp_plan_feasible_and_brute_force_competitive():
    m, buf, x = _tiny_meta()
    for E in (1e-1, 1e-2, 1e-3):
        plan = loader.plan_error_mode(m, E, loader.SAFE)
        assert plan.err_bound <= E
    # brute force over small level subsets to confirm DP near-optimality
    import itertools
    E = 1e-2
    plan = loader.plan_error_mode(m, E, loader.SAFE)
    errs, sizes = loader._level_cost_tables(m, loader.SAFE)
    best = None
    nl = len(m.levels)
    choices = [range(lv.nbits + 1) for lv in m.levels]
    if np.prod([len(c) for c in choices]) <= 200000:
        for combo in itertools.product(*choices):
            e = m.eb + sum(float(errs[i][b]) for i, b in enumerate(combo))
            if e <= E:
                sz = sum(int(sizes[i][b]) for i, b in enumerate(combo))
                if best is None or sz < best:
                    best = sz
        assert best is not None
        # DP discretization costs at most a few % extra volume
        assert plan.loaded_bytes <= best * 1.10 + 4096


def test_dp_bitrate_plan_within_budget():
    m, buf, x = _tiny_meta()
    total = m.total_size
    _, sizes = loader._level_cost_tables(m, loader.SAFE)
    min_bytes = sum(int(s[-1]) for s in sizes)  # escape channels only
    prev_err = None
    for frac in (0.05, 0.2, 0.5, 0.8, 1.0):
        S = max(int(total * frac), min_bytes)
        plan = loader.plan_bitrate_mode(m, S, loader.SAFE)
        assert plan.loaded_bytes <= S
        if prev_err is not None:
            assert plan.err_bound <= prev_err + 1e-15  # more budget, less error
        prev_err = plan.err_bound
