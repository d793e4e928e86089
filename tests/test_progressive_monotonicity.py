"""Progressive monotonicity across refinement ladders (Algorithm 2).

Across a ladder of shrinking error bounds — on v1 and chunked v2 archives,
on both decode backends — the progressive contract must hold at every rung:

  * ``err_bound`` never increases (refinement never loses precision),
  * ``bytes_read`` never decreases (and never re-reads loaded planes),
  * refining to a bound equals a fresh retrieval at that same bound
    (the delta cascade reaches the identical plane set; arrays match to
    float-accumulation tolerance, bitwise across backends).
"""
import numpy as np
import pytest

from _fields import smooth_field
from repro.core import CUBIC, compress, metrics, open_archive, refine, retrieve

LADDER = (1e-1, 1e-2, 1e-3, 1e-5)


def _archive(version):
    x = smooth_field((72, 40), 9)
    kw = dict(chunk_elems=900) if version == "v2" else {}
    return x, compress(x, 1e-7, CUBIC, **kw)


def _plane_sets(st):
    """planes_loaded across v1 / v2 states, as one flat list."""
    if hasattr(st, "chunk_states"):
        return [cs.planes_loaded for cs in st.chunk_states]
    return [st.planes_loaded]


@pytest.mark.parametrize("version", ["v1", "v2"])
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_ladder_monotone_and_refine_equals_fresh(version, backend):
    x, buf = _archive(version)
    reader = open_archive(buf)
    st = None
    prev_err, prev_bytes = float("inf"), 0
    for E in LADDER:
        out, st = retrieve(reader, error_bound=E, state=st, backend=backend)
        # monotone guarantees
        assert st.err_bound <= prev_err
        assert st.bytes_read >= prev_bytes
        assert st.err_bound <= E
        assert metrics.linf(x, out) <= E
        prev_err, prev_bytes = st.err_bound, st.bytes_read
        # vs a fresh retrieval at the same bound: the refined plane union
        # contains the fresh plan (want = max(have, plan)), so the ladder
        # state can only dominate — DP plans need not nest across bounds,
        # so exact equality is only required when the plane sets coincide
        fresh, fst = retrieve(open_archive(buf), error_bound=E,
                              backend=backend)
        assert metrics.linf(x, fresh) <= E
        assert st.bytes_read >= fst.bytes_read
        assert st.err_bound <= fst.err_bound
        if _plane_sets(st) == _plane_sets(fst):
            np.testing.assert_allclose(out, fresh, atol=1e-12)
    # full precision: the plan is every plane, so refine == fresh exactly
    out, st = retrieve(reader, state=st, backend=backend)
    fresh, fst = retrieve(open_archive(buf), backend=backend)
    assert _plane_sets(st) == _plane_sets(fst)
    assert st.bytes_read == fst.bytes_read
    assert st.err_bound == fst.err_bound
    np.testing.assert_allclose(out, fresh, atol=1e-12)


@pytest.mark.parametrize("version", ["v1", "v2"])
def test_ladder_bit_identical_across_backends(version):
    """The same ladder stepped on numpy and jax: every rung bit-identical."""
    x, buf = _archive(version)
    rn, rj = open_archive(buf), open_archive(buf)
    sn = sj = None
    for E in LADDER:
        on, sn = retrieve(rn, error_bound=E, state=sn, backend="numpy")
        oj, sj = retrieve(rj, error_bound=E, state=sj, backend="jax")
        assert np.array_equal(on, oj)
        assert sn.err_bound == sj.err_bound
        assert sn.bytes_read == sj.bytes_read


@pytest.mark.parametrize("version", ["v1", "v2"])
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_ladder_monotone_f32_refine_equals_fresh_bitwise(version, backend):
    """float32 sessions re-sweep from the current bins, so every rung is
    path-independent: wherever the plane sets coincide, the refined and
    the fresh read are bit-identical — not merely close."""
    x, _ = _archive(version)
    x = x.astype(np.float32)
    kw = dict(chunk_elems=900) if version == "v2" else {}
    buf = compress(x, 1e-6, CUBIC, **kw)
    reader = open_archive(buf)
    st = None
    prev_err, prev_bytes = float("inf"), 0
    for E in LADDER[:-1] + (None,):
        args = {} if E is None else dict(error_bound=E)
        out, st = retrieve(reader, state=st, backend=backend, **args)
        assert st.err_bound <= prev_err
        assert st.bytes_read >= prev_bytes
        assert metrics.linf(x, out) <= st.err_bound
        if E is not None:
            assert st.err_bound <= E
        prev_err, prev_bytes = st.err_bound, st.bytes_read
        fresh, fst = retrieve(open_archive(buf), backend=backend, **args)
        if _plane_sets(st) == _plane_sets(fst):
            assert out.tobytes() == fresh.tobytes()
    assert st.err_bound == 1e-6


@pytest.mark.parametrize("version", ["v1", "v2"])
def test_ladder_bit_identical_across_backends_f32(version):
    x, _ = _archive(version)
    x = x.astype(np.float32)
    kw = dict(chunk_elems=900) if version == "v2" else {}
    buf = compress(x, 1e-6, CUBIC, **kw)
    rn, rj = open_archive(buf), open_archive(buf)
    sn = sj = None
    for E in LADDER:
        on, sn = retrieve(rn, error_bound=E, state=sn, backend="numpy")
        oj, sj = retrieve(rj, error_bound=E, state=sj, backend="jax")
        assert on.tobytes() == oj.tobytes()
        assert sn.err_bound == sj.err_bound
        assert sn.bytes_read == sj.bytes_read


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_refine_api_monotone_bitrate(backend):
    """refine() under growing byte budgets: error monotone non-increasing,
    volume monotone non-decreasing."""
    x = smooth_field((64, 48), 12)
    buf = compress(x, 1e-7, CUBIC)
    out, st = retrieve(buf, bitrate=0.25, backend=backend)
    prev_err, prev_bytes = st.err_bound, st.bytes_read
    for bpp in (0.5, 1.0, 2.0):
        out, st = refine(st, bitrate=bpp, backend=backend)
        assert st.err_bound <= prev_err
        assert st.bytes_read >= prev_bytes
        prev_err, prev_bytes = st.err_bound, st.bytes_read
