"""The control and the planted faults of the correctness check.

Each entry patches a driver's session object after set-up, so that a
whole run goes through the harness with the timed path broken underneath
and must come out ``correct: false``:

``control``
    the reference put in the program's place, one precision below the
    configuration's float32: every answer is the field rounded to
    bfloat16 (a read returns it; a compress encodes it).
``altered``
    one element of every answer off by ten times its bound, where the
    answer is produced.
``half``
    the second half of each field's rows left out (zero) of every answer.
``unchanged``
    every call returns the state of the call before it: a refine returns
    the previous rung, a compress the previous archive (for the window's
    first compress, the archive of set-up's last warm-up compress).

``bench/control.py`` runs these on the chip at the cell's own size;
``bench/tests/test_bench_harness.py`` runs them on the CPU at a small one.
"""
from __future__ import annotations

import numpy as np


def bf16(x: np.ndarray) -> np.ndarray:
    """``x`` rounded to bfloat16 (nearest, ties to even), as float32."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    r = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return r.view(np.float32)


def _altered(y: np.ndarray, bound: float) -> np.ndarray:
    y = y.copy()
    y.reshape(-1)[y.size // 3] += np.float32(10 * bound)
    return y


def _half(y: np.ndarray) -> np.ndarray:
    y = y.copy()
    y[y.shape[0] // 2:] = 0
    return y


class _Codec:
    """A codec whose compress hands the program a changed field, or
    returns the archive of the call before."""

    def __init__(self, codec, change=None, unchanged=False):
        self.codec, self.change, self.unchanged = codec, change, unchanged
        self.last = None

    def compress(self, x, policy):
        if self.unchanged and self.last is not None:
            return self.last
        x = self.change(x) if self.change else x
        self.last = self.codec.compress(x, policy)
        return self.last


class _Reader:
    """A session whose answers are changed where they are produced:
    ``change(y, bound, last, x)`` gets the program's answer, the bound
    asked for, the session's previous answer and the field."""

    def __init__(self, reader, change, x, eb):
        self.reader, self.change, self.x, self.eb = reader, change, x, eb
        self.last = None

    def __getattr__(self, name):
        return getattr(self.reader, name)

    def _answer(self, fid):
        y = self.reader.read(fid)
        bound = self.eb if fid.value is None else fid.value
        out = self.change(y, bound, self.last, self.x)
        self.last = y
        return out

    read = refine = _answer


class _Archive:
    def __init__(self, archive, change, x, eb):
        self.archive, self.args = archive, (change, x, eb)

    def __getattr__(self, name):
        return getattr(self.archive, name)

    def open(self, policy):
        return _Reader(self.archive.open(policy), *self.args)


def _patch(sess, compress_change=None, read_change=None, unchanged=False):
    sess.codec = _Codec(sess.codec, compress_change, unchanged)
    sess.archives = [_Archive(a, read_change, x, eb) for a, x, eb in
                     zip(sess.archives, sess.fields, sess.ebs)]


def control(sess):
    _patch(sess, compress_change=bf16,
           read_change=lambda y, bound, last, x: bf16(x))


def altered(sess):
    eb = sess.ebs[0]
    _patch(sess, compress_change=lambda x: _altered(x, eb),
           read_change=lambda y, bound, last, x: _altered(y, bound))


def half(sess):
    _patch(sess, compress_change=_half,
           read_change=lambda y, bound, last, x: _half(y))


def unchanged(sess):
    codec = sess.codec
    _patch(sess, unchanged=True,
           read_change=lambda y, bound, last, x: y if last is None else last)
    if any(step["call"] == "compress" for step in sess.steps):
        # the call before the window: set-up's last warm-up compress
        sess.codec.last = codec.compress(sess.fields[-1], sess.policy)


FAULTS = {"control": control, "altered": altered, "half": half,
          "unchanged": unchanged}
