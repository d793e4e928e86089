"""Truncation-loss tables from the device pack's extrema.

The jax backend makes each level's ``delta_table`` from the max and min
low-digit values that its pack program reduces on the device
(``kernels.bitplane_pack.kernel.loss_extrema``); the host only finishes
the reference's float64 expression.  Every table must equal
``negabinary.truncation_loss_table`` element for element (bit for bit),
on the scalar, batched and sharded entry points, for every stream of
bins the escape screen lets a level store (within +-``arith.QMAX``),
32-digit words included.
"""
from collections import Counter

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # tier-1 container has no hypothesis; vendored fallback
    from _hypothesis_shim import given, settings, strategies as st

from repro import trace
from repro.core import arith, jax_backend
from repro.core import negabinary as nbmod
from repro.parallel import codec_mesh

EB = 2.5e-6


def _reference(q, nbits, eb=EB):
    return nbmod.truncation_loss_table(nbmod.to_negabinary(q), nbits, eb)


def _assert_tables(got, q2, eb=EB):
    """Each (blobs, nbits, delta) equals the host reference bit for bit."""
    for (_, nbits, delta), q in zip(got, q2):
        want = _reference(np.asarray(q, np.int64), nbits, eb)
        assert delta.dtype == np.float64 and delta.shape == want.shape
        assert delta.tobytes() == want.tobytes()


def _counts(fn):
    """(result, counters summed over the spans ``fn`` records)."""
    trace.clear()
    with trace.recording():
        out = fn()
    total = Counter()
    for r in trace.records():
        total.update(r.counts)
    trace.clear()
    return out, total


def _stream(n, nbits, seed):
    """n int64 bins whose negabinary words need exactly ``nbits`` digits."""
    rng = np.random.default_rng(seed)
    nb = rng.integers(0, 1 << nbits, n, dtype=np.uint64).astype(np.uint32)
    if nbits:
        nb[rng.integers(n)] |= np.uint32(1 << (nbits - 1))
    return nbmod.from_negabinary(nb)


#: 0, a single bin, under one word, over one pack row (4096 bins), and
#: a length that is no multiple of the pack's (8 x 4096)-bin padding
@pytest.mark.parametrize("n", [0, 1, 31, 4097, 40000 + 3])
def test_scalar_tables_match_reference(n):
    q = np.random.default_rng(n).integers(-(1 << 18), 1 << 18, n)
    got, counts = _counts(lambda: jax_backend.encode_level(q, EB))
    _assert_tables([got], [q])
    assert counts["loss_tables_device"] == (1 if n else 0)


def test_batch_rows_of_every_nbits_1_to_31():
    """One batched launch whose rows need 1..31 digits each."""
    q2 = np.stack([_stream(4097, nbits, nbits) for nbits in range(1, 32)])
    got, counts = _counts(lambda: jax_backend.encode_level_batch(q2, EB))
    assert [nbits for _, nbits, _ in got] == list(range(1, 32))
    _assert_tables(got, q2)
    assert counts["loss_tables_device"] == 31


def test_full_width_streams_are_tabled_on_the_device():
    """Bins at the screen's bound +-QMAX, and bins just below -715,827,882
    (the least value whose negabinary word needs all 32 digits), are
    tabled from the device extrema, equal to the reference at every b,
    32 included, beside narrow rows in the same launch."""
    lo32 = -715_827_883                     # -(2**1 + 2**3 + ... + 2**29) - 1
    rng = np.random.default_rng(7)
    edges = rng.integers(-1000, 1000, 500)
    edges[[3, 11, 20]] = arith.QMAX, -arith.QMAX, lo32
    near = rng.integers(lo32 - 1000, lo32 + 1, 500)
    q2 = np.stack([rng.integers(-1000, 1000, 500), edges, near,
                   rng.integers(-(1 << 20), 1 << 20, 500)])
    got, counts = _counts(lambda: jax_backend.encode_level_batch(q2, EB))
    assert [nbits for _, nbits, _ in got][1:3] == [32, 32]
    assert nbmod.to_negabinary(np.array([lo32 + 1]))[0] < 1 << 31
    _assert_tables(got, q2)
    assert counts["loss_tables_device"] == 4


def test_sharded_tables_match_batched():
    """The sharded entry point (a mesh over every local device, which pads
    the batch to a mesh multiple) gives the batched path's tables."""
    mesh = codec_mesh.codec_mesh()
    q2 = np.stack([_stream(3001, nbits, nbits) for nbits in (3, 17, 29)])
    got, counts = _counts(
        lambda: jax_backend.encode_level_sharded(q2, EB, mesh))
    _assert_tables(got, q2)
    bat = jax_backend.encode_level_batch(q2, EB)
    assert [d.tobytes() for _, _, d in got] == [d.tobytes()
                                                for _, _, d in bat]
    assert counts["loss_tables_device"] == 3


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(-(1 << 30), 1 << 30), min_size=1, max_size=300),
       st.floats(1e-9, 1e-1))
def test_tables_property(vals, eb):
    q = np.array(vals, np.int64)
    _assert_tables([jax_backend.encode_level(q, eb)], [q], eb)
