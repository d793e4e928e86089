"""The least-bytes functions equal a brute-force count over the phase
traversal, and the chunk grid is the program's."""
import itertools

import numpy as np
import pytest

from bench import roofline
from repro.core.pipeline.encode import chunk_bounds


def brute_force_bytes(shape):
    """Walk every (level, dim) phase and count targets and known points by
    testing each index of the grid."""
    L = roofline.levels(shape)
    idx = np.stack(np.meshgrid(*[np.arange(n) for n in shape],
                               indexing="ij"), -1).reshape(-1, len(shape))
    total = 0
    for level in range(L, 0, -1):
        s = 1 << (level - 1)
        for d in range(len(shape)):
            before = np.all(idx[:, :d] % s == 0, axis=1)
            after = np.all(idx[:, d + 1:] % (2 * s) == 0, axis=1)
            on_phase = before & after
            t = np.sum(on_phase & (idx[:, d] % (2 * s) == s))
            if not t:
                continue
            k = np.sum(on_phase & (idx[:, d] % (2 * s) == 0))
            total += 4 * (k + 2 * t)
    return total


@pytest.mark.parametrize("shape", [(16, 20, 18), (5, 33, 7), (1, 9, 64),
                                   (28, 12, 12), (17,)])
def test_sweep_bytes_equal_brute_force(shape):
    assert roofline.sweep_bytes(shape) == brute_force_bytes(shape)


def test_sweep_bytes_count_every_point_but_the_anchors_once_as_target():
    shape = (16, 20, 18)
    n = int(np.prod(shape))
    # targets: every point except the anchor; each adds 8 bytes
    known = (roofline.sweep_bytes(shape) - 8 * (n - 1)) // 4
    assert known > 0


@pytest.mark.parametrize("shape,elems", [((100, 500, 500), 1 << 22),
                                         ((256, 384, 384), 1 << 22),
                                         ((7, 3, 5), 16), ((9, 4), 1000)])
def test_chunk_rows_match_the_program(shape, elems):
    want = [b - a for a, b in chunk_bounds(shape, elems)]
    assert roofline.chunk_rows(shape, elems) == want


def test_published_chunk_grids():
    assert roofline.chunk_rows((100, 500, 500), 1 << 22) == [16] * 6 + [4]
    assert roofline.chunk_rows((256, 384, 384), 1 << 22) == [28] * 9 + [4]
    assert roofline.levels((16, 500, 500)) == 9
    assert roofline.levels((28, 384, 384)) == 9


def test_unknown_device_kind_is_an_error():
    assert roofline.peak_gbs("TPU v5 lite") == 819.0
    with pytest.raises(ValueError):
        roofline.peak_gbs("cpu")


def test_field_bytes_sum_over_chunks():
    cfg = {"field": {"shape": [9, 10, 11]}, "codec": {"chunk_elems": 220}}
    rows = roofline.chunk_rows([9, 10, 11], 220)
    assert rows == [2, 2, 2, 2, 1]
    assert roofline.field_sweep_bytes(cfg) == sum(
        roofline.sweep_bytes((r, 10, 11)) for r in rows)
    for a, b in itertools.pairwise(rows):
        assert a >= b
