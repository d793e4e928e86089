"""JAX/Pallas codec backend: both codec hot paths on the accelerator.

``compress(..., backend="jax")`` routes the two inner loops of the paper's
compression pipeline through the Pallas TPU kernels instead of numpy:

  * ``kernels.interp_quant``  — fused interpolation-predict + quantize for
    every (level, dim) phase sweep (§4.1–§4.2 in one VMEM pass);
  * ``kernels.bitplane_pack`` — negabinary + 2-bit-prefix XOR + bitplane
    packing collapsed to three integer ops per element (§4.4), with the
    level's truncation-loss extrema reduced in the same program.

``retrieve``/``refine``/``decompress(..., backend="jax")`` route the decode
direction — the operation progressive compression exists to make fast
(Algorithms 1–2) — through the inverse kernel pair:

  * ``kernels.interp_recon``  — fused interpolation-predict + add-residual
    for every (level, dim) phase of the reconstruction sweep;
  * ``kernels.bitplane_pack.bitplane_unpack`` — plane-word unpack +
    closed-form XOR-undo + negabinary decode back to the int32 bins.

Backend selection (see ``pipeline.backends``):

  * ``backend="numpy"``  — the pure-numpy reference pipeline (default on CPU);
  * ``backend="jax"``    — this module; on CPU the kernels run in Pallas
    interpret mode, on TPU they compile to Mosaic;
  * ``backend=None``/``"auto"`` — "jax" on TPU only: the kernels compile
    via Mosaic there, while on GPU/CPU they would fall back to the (slow)
    Pallas interpreter, so "auto" keeps the numpy reference everywhere
    else rather than silently emulating.

Both backends emit byte-identical archives and bit-identical
reconstructions because both run one arithmetic contract (``core.arith``):
a float32 field computes in float32 — the only float the chip has — and
any other field in float64, which this backend runs only on the CPU (under
the Pallas interpreter, with x64 enabled for the call); on a TPU a float64
field raises and names the numpy backend.  The packed plane words are
truncated to the exact ``np.packbits`` byte stream
(``bitplane.blobs_from_packed``), and archives produced here are readable
anywhere numpy runs.

The traversal, the escape screen and the writeback are shared with the
numpy reference (``interpolation.decorrelate_batch`` /
``reconstruct_batch``); this module only supplies the per-phase kernel
seam.  The kernel returns (q, pred) and the host screen recomputes each
element's reconstruction exactly as the decoder will, escaping every
element that misses the bound.

Every primitive also has a ``*_batch`` twin over stacks of equal-shaped
chunk problems (the unit the v2 shape-group scheduler feeds): the stack
runs through the ``jax.vmap``-ed kernel entry points, so B chunks cost ONE
dispatch per phase / per level instead of B, with per-chunk outputs
bit-identical to B scalar calls.  On top of that, every ``*_batch`` twin
has a ``*_sharded`` twin (same stack, plus a 1-D device mesh): the stack
axis is split across the mesh via ``parallel.codec_mesh`` and every device
runs the vmapped kernel on its local chunks — data-parallel, collective-
free, and still bit-identical (``compress``/``retrieve``/``refine``/
``decompress`` expose this as ``shard="auto"`` / a device count / an
explicit mesh; see ``docs/architecture.md``).
"""
from __future__ import annotations

import contextlib
from typing import List, Tuple

import numpy as np

from .. import trace
from . import arith, bitplane, interpolation

NUMPY = "numpy"
JAX = "jax"
JAX_UNFUSED = "jax_unfused"
AUTO = "auto"


def resolve(backend) -> str:
    """Map a user-facing backend choice to a registered backend name.

    Compatibility alias for ``pipeline.backends.resolve_name`` (the
    registry owns selection now).  "auto" picks jax only where the kernels
    actually compile (TPU); on GPU/CPU they would run in interpret mode —
    valid for parity testing (request it explicitly with backend="jax")
    but far slower than numpy.
    """
    from .pipeline import backends
    return backends.resolve_name(backend)


def _x64(dtype):
    """Context for one call in ``dtype``'s arithmetic: float64 enables
    x64 for its duration (CPU only); float32 needs nothing."""
    if np.dtype(dtype) != np.float64:
        return contextlib.nullcontext()
    import jax

    if jax.default_backend() == "tpu":
        raise ValueError("float64 fields compute in float64, which the TPU "
                         "does not have: use backend='numpy' for them (or "
                         "compress a float32 field)")
    return jax.enable_x64(True)


def _to_lanes(v: np.ndarray, ax: int) -> Tuple[np.ndarray, tuple]:
    """(B, ...) phase view -> (B, R, C) with the sweep axis on lanes, plus
    the lead shape to undo it."""
    m = np.moveaxis(v, ax, -1)
    lead = m.shape[1:-1]
    return m.reshape((m.shape[0], int(np.prod(lead)), m.shape[-1])), lead


def _from_lanes(a, lead: tuple, ax: int) -> np.ndarray:
    """Inverse of :func:`_to_lanes` for a (B, R, T) kernel output."""
    a = np.asarray(a)
    return np.moveaxis(a.reshape((a.shape[0],) + lead + (a.shape[-1],)),
                       -1, ax)


def decorrelate(x: np.ndarray, eb: float, interp: str,
                interpret: bool | None = None,
                ) -> Tuple[np.ndarray, List[np.ndarray], List[List[Tuple]], np.ndarray]:
    """Kernel-backed twin of ``interpolation.decorrelate`` (one array)."""
    return decorrelate_batch(np.asarray(x)[None], eb, interp,
                             interpret=interpret)[0]


def decorrelate_batch(xs: np.ndarray, eb: float, interp: str,
                      interpret: bool | None = None,
                      mesh=None) -> List[Tuple]:
    """Batched twin of :func:`decorrelate` over stacked equal-shape chunks.

    ``xs`` is (B, *chunk_shape); returns B per-chunk ``(xhat, qs, escs,
    anchors)`` tuples, bit-identical to the numpy reference.  Every
    (level, dim) phase moves the sweep axis onto lanes and costs ONE
    vmapped ``interp_quant`` dispatch for the whole stack; the traversal
    and the escape screen are ``interpolation.decorrelate_batch``'s.

    With ``mesh`` (a 1-D codec mesh), each phase dispatch is additionally
    ``shard_map``-ed: the stack axis is split across the mesh devices and
    every device runs the vmapped kernel on its local chunks
    (:func:`decorrelate_sharded` is the registry-facing alias).
    """
    from ..kernels.interp_quant import interp_quant_batch

    def phase_fn(xv, hv, ph, c):
        ax = ph.dim + 1
        with trace.span("sweep.layout", stage="sweep_layout"):
            xm, lead = _to_lanes(xv, ax)
            hm, _ = _to_lanes(hv, ax)
        with trace.span("sweep.kernel", stage="kernel_io"):
            ins = (xm, hm) if mesh is None else _place((xm, hm), mesh)
            q, pred = interp_quant_batch(*ins, s=ph.stride, eb=eb,
                                         interp=interp, interpret=interpret,
                                         mesh=mesh)
            q, pred = np.asarray(q), np.asarray(pred)
            trace.count("h2d_bytes", sum(a.nbytes for a in ins))
            trace.count("d2h_bytes", q.nbytes + pred.nbytes)
            if mesh is not None:
                q, pred = _unpad((q, pred), xm.shape[0])
        with trace.span("sweep.layout", stage="sweep_layout"):
            return _from_lanes(q, lead, ax), _from_lanes(pred, lead, ax)

    with _x64(arith.work_dtype(xs.dtype)):
        return interpolation.decorrelate_batch(xs, eb, interp, phase_fn)


def _place(arrays, mesh) -> list:
    """The sharded path's kernel inputs: host stacks padded to a mesh
    multiple and put on the mesh (``codec_mesh.place``), with the real
    and pad elements that go to the chips counted."""
    from ..parallel import codec_mesh

    with trace.span("shard.layout", stage="shard"):
        placed, pad = codec_mesh.place(arrays, mesh)
    a = arrays[0]
    trace.count("shard_rows", a.size)
    trace.count("shard_pad_rows", pad * (a.size // a.shape[0]))
    return placed


def _unpad(outs, b: int) -> list:
    """The sharded path's host outputs without their pad rows."""
    with trace.span("shard.layout", stage="shard"):
        return [o[:b] for o in outs]


def decorrelate_sharded(xs: np.ndarray, eb: float, interp: str, mesh,
                        interpret: bool | None = None) -> List[Tuple]:
    """Sharded compression sweep: :func:`decorrelate_batch` with the chunk
    stack split over a 1-D device mesh (the ``CodecBackend`` sharded-slot
    signature: trailing ``mesh`` after the scalar arguments)."""
    return decorrelate_batch(xs, eb, interp, interpret=interpret, mesh=mesh)


def encode_level(q: np.ndarray, eb: float, interpret: bool | None = None,
                 ) -> Tuple[List[bitplane.Blob], int, np.ndarray]:
    """Kernel-backed twin of the numpy backend's ``encode_level`` slot:
    ``(blobs, nbits, delta_table)`` of one level stream.

    The Pallas kernel fuses negabinary conversion, XOR-predictive coding and
    bit-transposition, and the same device program reduces the stream to
    its truncation-loss extrema; the host only finishes the table
    (:func:`_loss_tables`) and hands each plane's pad truncation and
    deflate to the shared pool (``bitplane.blobs_from_packed``).  Byte-
    identical blobs and tables to the numpy encoder.
    """
    if q.size == 0:
        return [], 0, np.zeros(1)
    from ..kernels.bitplane_pack import bitplane_pack

    # 1-D input only: the wrapper's 2-D path pads *columns*, which would
    # interleave pad zeros mid-stream and break blobs_from_packed's
    # valid-prefix truncation (level streams are always 1-D anyway)
    with trace.span("pack.kernel", stage="kernel_io"):
        q1 = np.ascontiguousarray(q, np.int32).reshape(-1)
        packed, n, extrema = bitplane_pack(q1, interpret=interpret)
        packed, extrema = np.asarray(packed), np.asarray(extrema)
        trace.count("h2d_bytes", q1.nbytes)
        trace.count("d2h_bytes", packed.nbytes + extrema.nbytes)
    blobs, nbits = bitplane.blobs_from_packed(packed, int(n))
    (delta,) = _loss_tables(extrema[None], [nbits], eb)
    return blobs, nbits, delta


def encode_level_batch(
        q2: np.ndarray, eb: float, interpret: bool | None = None, mesh=None,
) -> List[Tuple[List[bitplane.Blob], int, np.ndarray]]:
    """Batched twin of :func:`encode_level`: (B, n) stacked level streams.

    One vmapped pack launch covers the whole stack; the host then submits
    each chunk's planes to the deflate pool independently (per-chunk
    ``nbits`` and blobs) and finishes each chunk's table, so every
    returned ``(blobs, nbits, delta_table)`` is byte-identical to an
    unbatched :func:`encode_level` call on that row.  With ``mesh``, the
    stack is split over the 1-D codec mesh first (one launch per device;
    :func:`encode_level_sharded` is the registry-facing alias).
    """
    B, n = q2.shape
    if n == 0:
        return [([], 0, np.zeros(1)) for _ in range(B)]
    from ..kernels.bitplane_pack import (bitplane_pack_batch,
                                         bitplane_pack_sharded)

    with trace.span("pack.kernel", stage="kernel_io"):
        q2i = np.ascontiguousarray(q2, np.int32)
        if mesh is not None:
            (q2d,) = _place((q2i,), mesh)
            packed, n_valid, extrema = bitplane_pack_sharded(
                q2d, mesh=mesh, interpret=interpret)
        else:
            q2d = q2i
            packed, n_valid, extrema = bitplane_pack_batch(
                q2i, interpret=interpret)
        packed, extrema = np.asarray(packed), np.asarray(extrema)
        trace.count("h2d_bytes", q2d.nbytes)
        trace.count("d2h_bytes", packed.nbytes + extrema.nbytes)
        if mesh is not None:
            packed, extrema = _unpad((packed, extrema), B)
    enc = [bitplane.blobs_from_packed(packed[b], int(n_valid))
           for b in range(B)]
    deltas = _loss_tables(extrema, [nbits for _, nbits in enc], eb)
    return [(blobs, nbits, delta)
            for (blobs, nbits), delta in zip(enc, deltas)]


def encode_level_sharded(
        q2: np.ndarray, eb: float, mesh, interpret: bool | None = None,
) -> List[Tuple[List[bitplane.Blob], int, np.ndarray]]:
    """Sharded per-level pack: :func:`encode_level_batch` over a mesh."""
    return encode_level_batch(q2, eb, interpret=interpret, mesh=mesh)


def _loss_tables(extrema: np.ndarray, nbits: List[int],
                 eb: float) -> List[np.ndarray]:
    """Each stream's ``negabinary.truncation_loss_table`` from its device
    ``loss_extrema``: entry b is max |low-b-digit value| * 2 eb, the
    reference's own float64 expression, so the tables are bit-identical
    for every stream of bins within +-``arith.QMAX``."""
    out = []
    with trace.span("pack.negabinary", stage="negabinary"):
        for (mx, mn), nb in zip(extrema, nbits):
            trace.count("loss_tables_device")
            delta = np.zeros(nb + 1, np.float64)
            for b in range(1, nb + 1):
                delta[b] = float(max(int(mx[b]), -int(mn[b]))) * 2.0 * eb
            out.append(delta)
    return out


# ----------------------------------------------------------------- decode

def _loaded_prefix(blobs) -> int:
    """Length of the loaded MSB-first plane prefix (None = not loaded)."""
    want = 0
    for blob in blobs:
        if blob is None:
            break  # prefix property: once a plane is missing, rest are too
        want += 1
    return want


def _inflate(blob) -> bytes:
    """Blob -> raw packed-bit stream (``bitplane.inflate``): b''/None pass
    through, :class:`~repro.core.bitplane.Raw` payloads skip zlib entirely
    (cache layers hand pre-inflated planes through this seam), stored
    blobs are decompressed."""
    return bitplane.inflate(blob)


def _fill_plane_words(words: np.ndarray, blobs, want: int,
                      nbits: int) -> None:
    """Inflate a loaded blob prefix into the unpack kernel's word rows.

    ``words`` is one stream's (32, nw) destination; row k holds negabinary
    digit k's packed words (32 consecutive elements per word, element 0 at
    the MSB — the ``np.packbits`` stream the archive stores).  Shared by
    the scalar and batched decoders so the b'' convention and padding
    cannot drift between them.
    """
    for i in range(want):
        raw = _inflate(blobs[i])  # np.packbits stream, element 0 at MSB
        if not raw:
            continue  # all-zero encoded plane: b'' convention
        if len(raw) % 4:
            raw += b"\0" * (4 - len(raw) % 4)
        w = np.frombuffer(raw, ">u4")
        words[nbits - 1 - i, : w.size] = w


def inflate_level(blobs, nbits: int, n: int) -> Tuple[np.ndarray, int]:
    """Host zlib stage of one level's decode, split out so it can run on a
    worker thread while the device decodes the PREVIOUS level (the two-slot
    prefetch in ``pipeline.state``).  Returns ``(words, want)``: the (32,
    ceil(n/32)) uint32 word grid the unpack/fused kernels consume and the
    loaded-prefix length.  Pure host work (zlib + numpy) — thread-safe.
    """
    want = _loaded_prefix(blobs)
    words = np.zeros((32, (n + 31) // 32), np.uint32)
    if nbits and n and want:
        _fill_plane_words(words, blobs, want, nbits)
    return words, want


def inflate_level_batch(blob_lists, nbits: int, n: int,
                        ) -> Tuple[np.ndarray, List[int]]:
    """Batched :func:`inflate_level`: B blob prefixes -> ((B, 32, nw) word
    stack, per-chunk prefix lengths)."""
    B = len(blob_lists)
    words = np.zeros((B, 32, (n + 31) // 32), np.uint32)
    wants = []
    for b, blobs in enumerate(blob_lists):
        want = _loaded_prefix(blobs)
        wants.append(want)
        if nbits and n and want:
            _fill_plane_words(words[b], blobs, want, nbits)
    return words, wants


def decode_level(blobs, nbits: int, n: int,
                 interpret: bool | None = None) -> np.ndarray:
    """Kernel-backed twin of ``bitplane.decode_level``.

    Takes the same MSB-first blob prefix (None = not loaded) and returns the
    same truncated negabinary words.  The host only unzlibs each loaded
    plane into its packed word stream; the bit unpack, XOR-undo and
    negabinary decode all happen in one ``bitplane_unpack`` kernel launch,
    which emits the truncated word alongside the bins — the progressive
    state stores exactly that word, so no host-side conversion remains.
    """
    from ..kernels.bitplane_pack import bitplane_unpack

    want = _loaded_prefix(blobs)
    if nbits == 0 or n == 0 or want == 0:
        return np.zeros(n, np.uint32)
    words = np.zeros((32, (n + 31) // 32), np.uint32)
    _fill_plane_words(words, blobs, want, nbits)
    _, nb = bitplane_unpack(words, n=n, low_zero=nbits - want,
                            with_nb=True, interpret=interpret)
    return np.asarray(nb, np.uint32)


def decode_level_batch(blob_lists, nbits: int, n: int,
                       interpret: bool | None = None,
                       mesh=None) -> List[np.ndarray]:
    """Batched twin of :func:`decode_level` for equal-``nbits`` groups.

    ``blob_lists`` holds B chunks' MSB-first blob prefixes with the same
    ``nbits``; the loaded-prefix length may DIFFER per chunk — ``low_zero``
    is a runtime operand of the unpack kernel, so every stream carries its
    own truncation mask inside the one vmapped launch (no more one launch
    per ``(nbits, prefix)`` bucket).  Each returned truncated negabinary
    array is bit-identical to an unbatched call.  With ``mesh``, the
    stream stack is split over the 1-D codec mesh (one launch per device;
    :func:`decode_level_sharded` is the registry-facing alias).
    """
    from ..kernels.bitplane_pack import (bitplane_unpack_batch,
                                         bitplane_unpack_sharded)

    B = len(blob_lists)
    words, wants = inflate_level_batch(blob_lists, nbits, n)
    if nbits == 0 or n == 0 or all(w == 0 for w in wants):
        return [np.zeros(n, np.uint32) for _ in range(B)]
    # a want-0 stream has all-zero words, so it decodes to zero whatever
    # its mask is; 31 keeps the shift within uint32 range
    lz = [nbits - w if w else 31 for w in wants]
    if mesh is not None:
        _, nb = bitplane_unpack_sharded(words, n=n, mesh=mesh, low_zero=lz,
                                        with_nb=True, interpret=interpret)
    else:
        _, nb = bitplane_unpack_batch(words, n=n, low_zero=lz,
                                      with_nb=True, interpret=interpret)
    nb = np.asarray(nb, np.uint32)
    return [nb[b] for b in range(B)]


def decode_level_sharded(blob_lists, nbits: int, n: int, mesh,
                         interpret: bool | None = None) -> List[np.ndarray]:
    """Sharded per-level unpack: :func:`decode_level_batch` over a mesh."""
    return decode_level_batch(blob_lists, nbits, n, interpret=interpret,
                              mesh=mesh)


def decode_level_fused(blobs, nbits: int, n: int, nb_old: np.ndarray,
                       eb: float, interpret: bool | None = None,
                       words=None, dtype=np.float64,
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Fused progressive decode of one level: ONE kernel launch replaces
    ``decode_level`` plus the host dequantization.

    Returns ``(nb_new, out)``.  ``dtype`` is the field's working dtype:
    float64 gives Algorithm 2's residual delta against ``nb_old``,
    ``(bin_new - bin_old) * 2 * eb``; float32 gives the full float32
    residual of the new truncation (``nb_old`` unused, may be None).  Both are
    bit-identical to the host arithmetic.  ``words`` optionally carries a
    pre-inflated ``(words, want)`` pair from :func:`inflate_level` (the
    two-slot prefetch hands the worker thread's result through here).
    """
    out = decode_level_fused_batch([blobs], nbits, n, [nb_old], [eb],
                                   interpret=interpret, dtype=dtype,
                                   words=None if words is None
                                   else (words[0][None], [words[1]]))
    return out[0]


def decode_level_fused_batch(blob_lists, nbits: int, n: int, nb_olds,
                             ebs, interpret: bool | None = None,
                             mesh=None, words=None, dtype=np.float64,
                             ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Batched twin of :func:`decode_level_fused` for equal-``nbits``
    groups with per-chunk prefixes AND per-chunk error bounds (both are
    runtime kernel operands).  Returns B ``(nb_new, out)`` pairs from
    one vmapped launch; with ``mesh``, the stack is split over the 1-D
    codec mesh.  ``words`` optionally carries the prefetched
    ``(word stack, wants)`` from :func:`inflate_level_batch`.
    """
    from ..kernels.decode_fused import decode_fused_batch

    B = len(blob_lists)
    if words is None:
        words = inflate_level_batch(blob_lists, nbits, n)
    wstack, wants = words
    olds = np.stack([np.zeros(n, np.uint32) if o is None
                     else np.asarray(o, np.uint32) for o in nb_olds])
    eb_list = list(ebs) if np.ndim(ebs) else [float(ebs)] * B
    zero = np.zeros(n, dtype)
    if nbits == 0 or n == 0 or all(w == 0 for w in wants):
        return [(olds[b], zero) for b in range(B)]
    lz = [nbits - w if w else 31 for w in wants]
    with _x64(dtype):
        nb_new, out = decode_fused_batch(
            wstack, None if np.dtype(dtype) == np.float32 else olds, n,
            eb=eb_list, low_zero=lz, interpret=interpret, mesh=mesh,
            dtype=dtype)
        nb_new = np.asarray(nb_new, np.uint32)
        out = np.asarray(out, dtype)
    # nothing loaded: state and contribution are untouched
    return [(olds[b], zero) if wants[b] == 0 else (nb_new[b], out[b])
            for b in range(B)]


def decode_level_fused_sharded(blob_lists, nbits: int, n: int, nb_olds,
                               ebs, mesh, interpret: bool | None = None,
                               words=None, dtype=np.float64,
                               ) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Sharded fused decode: :func:`decode_level_fused_batch` over a mesh."""
    return decode_level_fused_batch(blob_lists, nbits, n, nb_olds, ebs,
                                    interpret=interpret, mesh=mesh,
                                    words=words, dtype=dtype)


def reconstruct(shape, interp: str, anchors: np.ndarray,
                yhat_per_level: List[np.ndarray],
                overrides=None, out_dtype=np.float64,
                interpret: bool | None = None,
                dtype=np.float64) -> np.ndarray:
    """Kernel-backed twin of ``interpolation.reconstruct`` (Algorithm 1),
    one array: :func:`reconstruct_batch` over a batch of one."""
    return reconstruct_batch(
        shape, interp, np.asarray(anchors)[None],
        [np.asarray(y)[None] for y in yhat_per_level],
        overrides=None if overrides is None else [overrides],
        out_dtype=out_dtype, interpret=interpret, dtype=dtype)[0]


def reconstruct_batch(shape, interp: str, anchors: np.ndarray,
                      yhat_per_level: List[np.ndarray],
                      overrides=None, out_dtype=np.float64,
                      interpret: bool | None = None,
                      mesh=None, dtype=np.float64) -> np.ndarray:
    """Batched twin of ``interpolation.reconstruct_batch`` over B
    equal-``shape`` items.

    The traversal, offset accounting, and escape override writeback run
    in ``interpolation.reconstruct_batch`` itself; this function only
    supplies the per-phase block primitive — one vmapped ``interp_recon``
    launch per (level, dim) phase for the whole stack, ``shard_map``-ed
    over the 1-D codec mesh when one is given.  Bit-exact with the numpy
    sweep: both compute ``arith.recon`` from ``arith.predict``.
    """
    from ..kernels.interp_recon import interp_recon_batch

    def block_fn(hv, ph, res):
        ax = ph.dim + 1
        tgt_shape = list(hv.shape)
        tgt_shape[ax] = ph.targets.size
        hm, lead = _to_lanes(hv, ax)
        rm, _ = _to_lanes(np.asarray(res).reshape(tgt_shape), ax)
        out = interp_recon_batch(hm, rm, s=ph.stride, interp=interp,
                                 interpret=interpret, mesh=mesh)
        # order='C' copy: the override writeback addresses each item's
        # block by flat index in original-axis C order
        return np.array(_from_lanes(out, lead, ax), order="C")

    with _x64(dtype):
        return interpolation.reconstruct_batch(
            shape, interp, anchors, yhat_per_level, overrides=overrides,
            out_dtype=out_dtype, block_fn=block_fn, dtype=dtype)


def reconstruct_sharded(shape, interp: str, anchors: np.ndarray,
                        yhat_per_level: List[np.ndarray], mesh,
                        overrides=None, out_dtype=np.float64,
                        interpret: bool | None = None,
                        dtype=np.float64) -> np.ndarray:
    """Sharded reconstruction sweep: :func:`reconstruct_batch` over a 1-D
    codec mesh (the ``CodecBackend`` sharded-slot signature)."""
    return reconstruct_batch(shape, interp, anchors, yhat_per_level,
                             overrides=overrides, out_dtype=out_dtype,
                             interpret=interpret, mesh=mesh, dtype=dtype)
