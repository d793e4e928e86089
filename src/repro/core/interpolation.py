"""Multi-level interpolation predictor (paper §4.1–§4.3).

The data grid is decomposed into L orthogonal levels.  Level ``l``
(l = L..1, finest = 1) predicts the points whose finest stride is
s = 2**(l-1) from the already-reconstructed points at stride 2*s, sweeping
dimension-by-dimension (Fig. 3).  Interpolation is used as a *prediction*
model: each level predicts from the lossy reconstruction ``xhat`` of the
previous level, so quantization error never amplifies (Eq. 4), unlike
transform models where ||T^-1||_inf can be O(n) (Eq. 3).

Formulas (paper Eq. 1/2):
  linear:  y_i = (x_{i-s} + x_{i+s}) / 2                        L_inf(P) = 1
  cubic:   y_i = (-x_{i-3s} + 9 x_{i-s} + 9 x_{i+s} - x_{i+3s})/16
                                                               L_inf(P) = 1.25
Boundary fallback: cubic -> linear -> copy-left.

Traversal order is shared verbatim by the compressor and the decompressor;
the quantized residual stream is the concatenation of every (level, phase)
target block in C order.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from .. import trace
from . import arith

LINEAR = "linear"
CUBIC = "cubic"

#: L_inf norm of the prediction operator, used by Theorem 1 (p^l factors).
PRED_NORM = {LINEAR: 1.0, CUBIC: 1.25}


def num_levels(shape: Sequence[int]) -> int:
    """L such that the anchor grid (stride 2^L) collapses to index 0 per dim."""
    m = int(max(shape))
    L = 1
    while (1 << L) < m:
        L += 1
    return L


def anchor_slices(shape: Sequence[int], L: int) -> Tuple[slice, ...]:
    s = 1 << L
    return tuple(slice(0, None, s) for _ in shape)


@dataclass(frozen=True)
class Phase:
    """One dimension-sweep inside a level."""
    level: int          # L..1
    stride: int         # 2**(level-1)
    dim: int            # axis being interpolated
    view: Tuple[slice, ...]   # restriction of the full array for this phase
    targets: np.ndarray       # target indices along `dim` (odd multiples of stride)
    n_dim: int                # full extent along `dim`
    count: int                # number of scalars predicted in this phase


def iter_phases(shape: Sequence[int], L: int) -> Iterator[Phase]:
    """Deterministic (level, dim) traversal shared by comp/decomp."""
    ndim = len(shape)
    for level in range(L, 0, -1):
        s = 1 << (level - 1)
        for d in range(ndim):
            targets = np.arange(s, shape[d], 2 * s)
            if targets.size == 0:
                continue
            view = tuple(
                slice(0, None, s) if dd < d else
                (slice(None) if dd == d else slice(0, None, 2 * s))
                for dd in range(ndim)
            )
            cnt = targets.size
            for dd in range(ndim):
                if dd < d:
                    cnt *= len(range(0, shape[dd], s))
                elif dd > d:
                    cnt *= len(range(0, shape[dd], 2 * s))
            yield Phase(level, s, d, view, targets, shape[d], cnt)


def level_sizes(shape: Sequence[int], L: int) -> List[int]:
    """Number of predicted scalars per level, index 0 = level L (coarsest)."""
    sizes = [0] * L
    for ph in iter_phases(shape, L):
        sizes[L - ph.level] += ph.count
    return sizes


def _bcast(mask: np.ndarray, axis: int, ndim: int) -> np.ndarray:
    shp = [1] * ndim
    shp[axis] = mask.size
    return mask.reshape(shp)


def predict_block(view: np.ndarray, axis: int, idx: np.ndarray, s: int,
                  n: int, interp: str, ftz: bool = False) -> np.ndarray:
    """Interpolate values at ``idx`` (odd multiples of s) along ``axis``.

    ``view`` holds the already-known values (previous level at 2s multiples).
    Gathers the four neighbours and applies :func:`arith.predict` — the
    formula the kernels run too; ``ftz`` selects the float32 contract's
    subnormal flushing.  Linear in the data up to rounding, which
    Algorithm 2 (incremental delta reconstruction) relies on.
    """
    nd = view.ndim
    r_ok = _bcast(idx + s <= n - 1, axis, nd)
    cubic_ok = _bcast((idx - 3 * s >= 0) & (idx + 3 * s <= n - 1), axis, nd) \
        & r_ok
    l1 = np.take(view, idx - s, axis=axis)
    r1 = np.take(view, np.minimum(idx + s, n - 1), axis=axis)
    l3 = r3 = None
    if interp != LINEAR:
        l3 = np.take(view, np.maximum(idx - 3 * s, 0), axis=axis)
        r3 = np.take(view, np.minimum(idx + 3 * s, n - 1), axis=axis)
    return arith.predict(np, l3, l1, r1, r3, cubic_ok, r_ok, interp, ftz)


def _assign(view: np.ndarray, axis: int, idx: np.ndarray, vals: np.ndarray) -> None:
    view[(slice(None),) * axis + (idx,)] = vals


def decorrelate(x: np.ndarray, eb: float, interp: str,
                phase_fn: Optional[Callable] = None,
                ) -> Tuple[np.ndarray, List[np.ndarray], List[List[Tuple]], np.ndarray]:
    """Compression-side sweep of one array (a batch of one, see
    :func:`decorrelate_batch`).

    Returns (xhat, per-level q arrays [index 0 = level L], per-level escape
    records with level-global indices, anchors).
    """
    return decorrelate_batch(np.asarray(x)[None], eb, interp, phase_fn)[0]


def decorrelate_batch(xs: np.ndarray, eb: float, interp: str,
                      phase_fn: Optional[Callable] = None) -> List[Tuple]:
    """Compression-side sweep over B stacked equal-shape arrays.

    Computes in the field's working dtype (``arith.work_dtype``: float32
    stays float32, everything else float64).  Per (level, dim) phase: the
    prediction and bins come from ``phase_fn(xv, hv, ph, c)`` — the backend
    seam, given the batched data and reconstruction views, the Phase and
    the quantizer constants, returning ``(q, pred)`` over the target block
    in original axis order — or, when None, from the numpy reference.  The
    escape screen (:func:`arith.screen`), the writeback and the stream
    bookkeeping stay here, shared by every backend.

    Escape records hold block-local flat indices and *absolute original
    values*: escapes are exact overwrites — storing residuals would lose
    the value to catastrophic cancellation when |pred| >> |x|.

    Returns B ``(xhat, qs, escs, anchors)`` tuples, bit-identical to B
    single-array sweeps (every operation is elementwise across the batch).
    """
    c = arith.consts(eb, xs.dtype)
    xs = np.asarray(xs, c.dtype)
    B, shape = xs.shape[0], xs.shape[1:]
    L = num_levels(shape)
    xhat = np.zeros(xs.shape, c.dtype)
    anc = (slice(None),) + anchor_slices(shape, L)
    anchors = np.array(xs[anc], np.float64)
    xhat[anc] = xs[anc]  # P_L(0) replaced by exact anchors (lossless channel)

    qs: List[List[List[np.ndarray]]] = [[[] for _ in range(L)] for _ in range(B)]
    escs: List[List[List[Tuple]]] = [[[] for _ in range(L)] for _ in range(B)]
    offsets = [0] * L
    for ph in iter_phases(shape, L):
        with trace.span("sweep.phase", level=ph.level, dim=ph.dim):
            ax = ph.dim + 1
            xv = xs[(slice(None),) + ph.view]
            hv = xhat[(slice(None),) + ph.view]
            with trace.span("sweep.layout", stage="sweep_layout"):
                tvals = np.take(xv, ph.targets, axis=ax)
            if phase_fn is None:
                pred = predict_block(hv, ax, ph.targets, ph.stride, ph.n_dim,
                                     interp, c.f32)
                q = arith.bins(np, tvals, pred, c, np.int64)
            else:
                q, pred = phase_fn(xv, hv, ph, c)
            with trace.span("sweep.screen", stage="screen"):
                q, block, esc = arith.screen(tvals, pred, q, c)
                _assign(hv, ax, ph.targets, block)
                li = L - ph.level
                for b in range(B):
                    flat = np.flatnonzero(esc[b].ravel())
                    trace.count("escapes", flat.size)
                    qs[b][li].append(q[b].ravel())
                    escs[b][li].append(
                        (flat + offsets[li],
                         tvals[b].ravel()[flat].astype(np.float64)))
                offsets[li] += ph.count
    # the screened streams, joined
    with trace.span("sweep.screen", stage="screen"):
        return [(xhat[b],
                 [np.concatenate(v) if v else np.zeros(0, np.int64)
                  for v in qs[b]],
                 escs[b], anchors[b]) for b in range(B)]


def reconstruct(shape: Sequence[int], interp: str, anchors: np.ndarray,
                yhat_per_level: List[np.ndarray],
                overrides: Optional[List[Tuple[np.ndarray, np.ndarray]]] = None,
                out_dtype=np.float64, block_fn: Optional[Callable] = None,
                dtype=np.float64) -> np.ndarray:
    """Decompression-side sweep (Algorithm 1 core) of one array — a batch
    of one, see :func:`reconstruct_batch`."""
    anchors = np.asarray(anchors)[None]
    yhat = [np.asarray(y)[None] for y in yhat_per_level]
    ovr = None if overrides is None else [overrides]
    return reconstruct_batch(shape, interp, anchors, yhat, overrides=ovr,
                             out_dtype=out_dtype, block_fn=block_fn,
                             dtype=dtype)[0]


def reconstruct_batch(shape: Sequence[int], interp: str, anchors: np.ndarray,
                      yhat_per_level: List[np.ndarray],
                      overrides: Optional[List[List[Tuple[np.ndarray, np.ndarray]]]] = None,
                      out_dtype=np.float64, block_fn: Optional[Callable] = None,
                      dtype=np.float64) -> np.ndarray:
    """Decompression-side sweep over B equal-``shape`` items.

    ``anchors`` is (B, *anchors_shape), ``yhat_per_level[i]`` the (B, n_i)
    dequantized residual streams of level L-i, ``overrides[b][i]`` =
    (stream_idx, values) the per-item positions whose output is set to
    ``values`` exactly instead of pred+res (the lossless escape channel;
    for Algorithm 2's delta cascade the values are zeros, since escaped
    points never change across refinements).  ``dtype`` is the working
    dtype of the arithmetic contract (``arith.work_dtype`` of the field).
    Aside from overrides and rounding, linear in (anchors, yhat): the same
    routine reconstructs incremental deltas by feeding zero anchors and
    residual *differences*.

    ``block_fn(hv, ph, res)`` is the backend seam: given the batched phase
    view, the Phase, and the (B, count) residual slice, return the
    reconstructed target block (pred + res) in original axis order as a
    writable C-order array.  None = the numpy reference.  Traversal,
    per-level offset accounting, and the override writeback stay here —
    shared by every backend — so the semantics cannot drift between
    substrates.  Every operation is elementwise across the batch, so
    results are bit-identical to B single-item sweeps.
    """
    dtype = np.dtype(dtype)
    ftz = dtype == np.float32
    B = anchors.shape[0]
    L = num_levels(shape)
    xhat = np.zeros((B,) + tuple(shape), dtype)
    xhat[(slice(None),) + anchor_slices(shape, L)] = anchors
    offs = [0] * L
    for ph in iter_phases(shape, L):
        hv = xhat[(slice(None),) + ph.view]
        li = L - ph.level
        lo = offs[li]
        res = np.asarray(yhat_per_level[li][:, lo: lo + ph.count], dtype)
        offs[li] += ph.count
        if block_fn is None:
            pred = predict_block(hv, ph.dim + 1, ph.targets, ph.stride,
                                 ph.n_dim, interp, ftz)
            tgt_shape = list(hv.shape)
            tgt_shape[ph.dim + 1] = ph.targets.size
            block = arith.recon(np, pred, res.reshape(tgt_shape), ftz)
        else:
            block = block_fn(hv, ph, res)
        if overrides is not None:
            for b in range(B):
                oidx, ovals = overrides[b][li]
                if oidx.size:
                    sel = (oidx >= lo) & (oidx < lo + ph.count)
                    if sel.any():
                        block[b].reshape(-1)[oidx[sel] - lo] = ovals[sel]
        _assign(hv, ph.dim + 1, ph.targets, block)
    return xhat.astype(out_dtype, copy=False)
