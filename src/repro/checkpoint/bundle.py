"""Single-file checkpoint bundles (``IPCB``): a manifest-indexed
directory of per-leaf ``version=3`` archives.

One training step checkpoints to ONE file::

    b"IPCB" | u32 manifest_len | manifest JSON | leaf regions ...

The manifest maps each leaf id to its region ``[offset, offset+nbytes)``
(offsets relative to the data section, so the manifest never depends on
its own rendered length), the leaf's original shape/dtype, the shape it
was compressed as, a full-blob ``sha`` (sha256), and a verified-prefix
pair ``(pfx_size, pfx_sha)`` covering the archive's header + anchors +
escapes region — everything a coarse read touches before the bitplane
ladder — so integrity is checkable on *partial* reads too, not only
full ones.

Layout property the restore path relies on: each ``ipc`` leaf is a
self-contained IPC3 plane-major archive (single chunk by default), so a
coarse restore of the whole bundle reads one contiguous range per leaf
prefix — header, anchors, escapes, then the first ladder segments — and
a refine extends each leaf's range monotonically.  Opened through any
:class:`~repro.core.bytesource.ByteSource`, remote restore over
HTTP-range (``repro.core.remote.HTTPSource``, with its retry/backoff
semantics) is the same code path as a local mmap restore.

Writing is a **parallel partitioned encode**: ``workers`` encoder
threads each compress a deterministic partition of the leaves into a
private ``shard_<k>.bin`` + ``shard_<k>.json`` (the shard manifest);
the merge pass then streams the shards into the final bundle in
original leaf order and publishes it with one atomic ``os.replace`` —
bundle bytes are identical for any worker count.  This is the
single-host shape of per-host sharded encode.

This module is deliberately free of tree/framework concerns: it speaks
``(leaf_id, float32 array)`` pairs.  ``checkpoint.store`` owns the
pytree flattening and the ``LATEST`` pointer; ``checkpoint.restore``
owns progressive decode sessions over these bundles.
"""
from __future__ import annotations

import hashlib
import json
import os
import struct
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Union

import numpy as np

from ..core.bytesource import BufferSource, ByteSource, FileSource, as_source
from ..core.container import (CorruptArchiveError, _read_exact, parse_meta,
                              parse_v3_meta)

MAGIC = b"IPCB"
BUNDLE_VERSION = 1


def _sha(data) -> str:
    return hashlib.sha256(bytes(data)).hexdigest()


@dataclass
class LeafSpec:
    """One leaf handed to the bundle writer: the float32 payload plus
    the metadata needed to restore the original leaf exactly."""
    lid: str
    arr: np.ndarray            # float32, original (pre-compression) shape
    dtype: str                 # original dtype string (restored on read)
    raw_nbytes: int            # original in-memory footprint (accounting)


def _raw_entry(arr: np.ndarray, dtype: str):
    blob = np.ascontiguousarray(arr, np.float32).tobytes()
    digest = _sha(blob)
    entry = dict(kind="raw", shape=list(arr.shape), dtype=dtype,
                 comp_shape=None, nbytes=len(blob), sha=digest,
                 pfx_size=len(blob), pfx_sha=digest)
    return entry, blob


def encode_leaf(spec: LeafSpec, *, rel_eb: float, interp: str,
                lossless_small: int = 4096,
                chunk_elems: Optional[int] = None):
    """Compress one leaf; returns ``(entry, blob)``.

    Leaves smaller than ``lossless_small`` elements (norms, biases,
    scalars) are stored raw — compression metadata would dominate — and
    their verified prefix is the whole blob (raw leaves are always read
    whole).  Everything else is container-selected by measured size:

    * ``ipc``  — an IPC3 plane-major archive (single chunk unless
      ``chunk_elems`` splits it); the target container — coarse reads
      are one contiguous prefix per leaf.  Its verified prefix covers
      header + anchors + escapes (``V3Meta.base_end``).
    * ``ipc1`` — the compact v1 container, chosen when the v3
      plane-major segment directory does not pay for itself at this
      leaf's size (small leaves: the directory is per-(level, plane)
      metadata, near-constant in leaf size).  Still fully bitplane-
      progressive; its verified prefix covers the header (the blob
      index — the payload is verified by the full-read sha path).
    * ``raw``  — fallback when even v1 does not beat the float32 bytes
      (incompressible leaf at this eb): honesty over format purity.

    The choice is per-leaf and recorded in the manifest; restore
    dispatches on it.
    """
    arr = spec.arr
    if arr.size <= lossless_small or arr.ndim == 0:
        return _raw_entry(arr, spec.dtype)
    from ..api import Codec  # deferred: keep the format importable early
    a2 = arr.reshape(arr.shape[0], -1) if arr.ndim > 2 else arr
    raw_len = a2.size * 4
    # compressed as an (exact) float64 copy, i.e. under the float64
    # arithmetic contract: checkpoint bounds reach the float32 ulp and
    # below, where float32 arithmetic cannot meet them and would escape
    # nearly every element; restore casts back to the leaf's dtype
    a2 = a2.astype(np.float64)
    kind = "ipc"
    blob = Codec(eb=rel_eb, interp=interp, relative=True,
                 chunk_elems=chunk_elems, version=3).compress(a2).tobytes()
    if len(blob) >= raw_len:
        blob1 = Codec(eb=rel_eb, interp=interp,
                      relative=True).compress(a2).tobytes()
        if len(blob1) < len(blob):
            kind, blob = "ipc1", blob1
    if len(blob) >= raw_len:
        return _raw_entry(arr, spec.dtype)
    pfx = parse_v3_meta(BufferSource(blob)).base_end if kind == "ipc" \
        else parse_meta(BufferSource(blob)).header_end
    entry = dict(kind=kind, shape=list(arr.shape), dtype=spec.dtype,
                 comp_shape=list(a2.shape), nbytes=len(blob),
                 sha=_sha(blob), pfx_size=int(pfx), pfx_sha=_sha(blob[:pfx]))
    return entry, blob


def write_bundle(path: str, leaves: List[LeafSpec], *, step: int,
                 rel_eb: float, interp: str, treedef: Optional[str] = None,
                 lossless_small: int = 4096, workers: int = 1,
                 chunk_elems: Optional[int] = None,
                 shard_dir: Optional[str] = None) -> Dict:
    """Parallel partitioned encode + atomic merge; returns the manifest.

    ``workers`` encoder threads each take the deterministic partition
    ``leaves[k::n]``, write their blobs to ``shard_<k>.bin`` and publish
    a ``shard_<k>.json`` shard manifest in ``shard_dir`` (which the
    caller owns — typically a ``.step_*`` temp dir next to ``path``).
    The merge assigns final offsets in original leaf order — NOT shard
    order — so the published bundle is byte-identical for any worker
    count, then streams shard bytes into ``path + ".tmp"`` and
    ``os.replace``\\ s it into place (atomic on POSIX: readers see the
    old bundle or the new one, never a torn one).
    """
    workers = max(1, int(workers or 1))
    nshards = min(workers, max(1, len(leaves)))
    if shard_dir is None:
        shard_dir = os.path.dirname(os.path.abspath(path))
    parts = [leaves[k::nshards] for k in range(nshards)]

    def _encode_shard(k: int) -> Dict[str, Dict]:
        entries: Dict[str, Dict] = {}
        off = 0
        with open(os.path.join(shard_dir, f"shard_{k}.bin"), "wb") as f:
            for spec in parts[k]:
                entry, blob = encode_leaf(
                    spec, rel_eb=rel_eb, interp=interp,
                    lossless_small=lossless_small, chunk_elems=chunk_elems)
                f.write(blob)
                entries[spec.lid] = dict(entry=entry, local_offset=off)
                off += len(blob)
        with open(os.path.join(shard_dir, f"shard_{k}.json"), "w") as f:
            json.dump(entries, f)
        return entries

    if nshards == 1:
        shard_manifests = [_encode_shard(0)]
    else:
        with ThreadPoolExecutor(max_workers=nshards) as ex:
            shard_manifests = list(ex.map(_encode_shard, range(nshards)))

    where: Dict[str, tuple] = {}
    for k, ents in enumerate(shard_manifests):
        for lid, rec in ents.items():
            where[lid] = (k, rec)

    man_leaves: Dict[str, Dict] = {}
    order: List[str] = []
    off = 0
    for spec in leaves:
        entry = dict(where[spec.lid][1]["entry"])
        entry["offset"] = off          # relative to the data section
        man_leaves[spec.lid] = entry
        order.append(spec.lid)
        off += entry["nbytes"]
    manifest = dict(format="IPCB", version=BUNDLE_VERSION, step=int(step),
                    rel_eb=float(rel_eb), interp=interp, treedef=treedef,
                    order=order, leaves=man_leaves,
                    total_raw=int(sum(s.raw_nbytes for s in leaves)),
                    total_comp=int(off))
    mbytes = json.dumps(manifest, sort_keys=True).encode("utf-8")

    tmp_out = os.path.join(shard_dir, "bundle.tmp") \
        if os.path.isdir(shard_dir) else path + ".tmp"
    shard_fs = [open(os.path.join(shard_dir, f"shard_{k}.bin"), "rb")
                for k in range(nshards)]
    try:
        with open(tmp_out, "wb") as out:
            out.write(MAGIC)
            out.write(struct.pack("<I", len(mbytes)))
            out.write(mbytes)
            for spec in leaves:
                k, rec = where[spec.lid]
                shard_fs[k].seek(rec["local_offset"])
                out.write(shard_fs[k].read(man_leaves[spec.lid]["nbytes"]))
            out.flush()
            os.fsync(out.fileno())
    finally:
        for f in shard_fs:
            f.close()
    os.replace(tmp_out, path)          # atomic publish
    for k in range(nshards):
        for suffix in (".bin", ".json"):
            try:
                os.unlink(os.path.join(shard_dir, f"shard_{k}{suffix}"))
            except OSError:
                pass
    return manifest


class Bundle:
    """Read side of an ``IPCB`` bundle over any :class:`ByteSource`.

    The manifest is parsed ONCE at open and cached on the instance —
    every restore round (and every refinement round of a
    :class:`~repro.checkpoint.restore.RestoreSession` holding this
    bundle) reuses it; no path re-reads it per round.  Framing, extents
    and region tiling are validated here, so a truncated or rewritten
    bundle fails at open with :class:`CorruptArchiveError` instead of
    decoding garbage later.
    """

    def __init__(self, src: Union[bytes, ByteSource]):
        self.source = as_source(src)
        head = bytes(_read_exact(self.source, 0, 8, "bundle framing"))
        if head[:4] != MAGIC:
            raise CorruptArchiveError(
                f"not an IPCB checkpoint bundle: expected magic {MAGIC!r}, "
                f"got {head[:4]!r}")
        mlen = struct.unpack("<I", head[4:8])[0]
        if 8 + mlen > self.source.size:
            raise CorruptArchiveError(
                f"corrupt bundle: manifest claims {mlen} bytes but the "
                f"source holds {self.source.size}")
        mbytes = bytes(_read_exact(self.source, 8, mlen, "bundle manifest"))
        self.manifest_sha = _sha(mbytes)
        try:
            self.manifest: Dict[str, Any] = json.loads(mbytes)
        except ValueError as e:
            raise CorruptArchiveError(
                f"corrupt bundle: undecodable manifest ({e})") from e
        if self.manifest.get("format") != "IPCB":
            raise CorruptArchiveError(
                "corrupt bundle: manifest is not an IPCB manifest")
        self.data_start = 8 + mlen
        end = 0
        for lid in self.manifest["order"]:
            e = self.manifest["leaves"][lid]
            if e["offset"] != end:
                raise CorruptArchiveError(
                    f"corrupt bundle: leaf {lid!r} starts at {e['offset']}, "
                    f"expected {end} — leaf regions must tile the data "
                    "section contiguously in manifest order")
            end = e["offset"] + e["nbytes"]
        if self.data_start + end != self.source.size:
            raise CorruptArchiveError(
                f"corrupt bundle: leaf regions end at byte "
                f"{self.data_start + end} but the source holds "
                f"{self.source.size} (truncated or padded bundle)")

    # ------------------------------------------------------------ opening

    @classmethod
    def open(cls, path_or_url, **remote_opts) -> "Bundle":
        """Open a bundle from a local path, an ``http(s)://`` URL, or an
        already-built :class:`ByteSource`.  ``remote_opts`` forward to
        :class:`~repro.core.remote.HTTPSource` (``retries``, ``timeout``,
        ``backoff``, ...), so remote restores inherit the retry /
        degradation semantics of the remote retrieval layer."""
        if isinstance(path_or_url, ByteSource):
            return cls(path_or_url)
        target = os.fspath(path_or_url)
        if target.startswith(("http://", "https://")):
            from ..core.remote import HTTPSource
            return cls(HTTPSource(target, **remote_opts))
        return cls(FileSource(target))

    # ------------------------------------------------------------ manifest

    @property
    def step(self) -> int:
        return int(self.manifest["step"])

    @property
    def rel_eb(self) -> float:
        return float(self.manifest["rel_eb"])

    @property
    def interp(self) -> str:
        return self.manifest["interp"]

    @property
    def leaf_order(self) -> List[str]:
        return list(self.manifest["order"])

    def entry(self, lid: str) -> Dict:
        try:
            return self.manifest["leaves"][lid]
        except KeyError:
            raise KeyError(
                f"bundle for step {self.step} has no leaf {lid!r} "
                f"({len(self.manifest['leaves'])} leaves present)") from None

    # ------------------------------------------------------------ regions

    def leaf_region(self, lid: str):
        e = self.entry(lid)
        return self.data_start + e["offset"], e["nbytes"]

    def leaf_source(self, lid: str) -> ByteSource:
        """A windowed view of the leaf's region: position 0 is the leaf's
        first byte, reads land on the bundle source at absolute offsets
        (range accounting and HTTP Range requests see real bundle
        positions)."""
        off, size = self.leaf_region(lid)
        return self.source.window(off, size)

    def read_leaf_bytes(self, lid: str, verify: bool = True) -> bytes:
        """The leaf's full blob; with ``verify`` the manifest's sha256 is
        checked and a mismatch raises :class:`CorruptArchiveError` naming
        the leaf — on every path, local or remote."""
        off, size = self.leaf_region(lid)
        blob = bytes(_read_exact(self.source, off, size, f"leaf {lid!r}"))
        if verify and _sha(blob) != self.entry(lid)["sha"]:
            raise CorruptArchiveError(
                f"checkpoint leaf {lid!r} failed integrity check: stored "
                f"bytes do not match the manifest sha256 (corrupt or "
                "tampered bundle)")
        return blob

    def verify_leaf_prefix(self, lid: str) -> None:
        """Check the leaf's verified prefix (header + anchors + escapes
        for ``ipc`` leaves, the whole blob for ``raw``) against the
        manifest — the integrity gate for *partial* (progressive) reads,
        which never see the full blob."""
        e = self.entry(lid)
        off, _ = self.leaf_region(lid)
        pfx = bytes(_read_exact(self.source, off, e["pfx_size"],
                                f"leaf {lid!r} prefix"))
        if _sha(pfx) != e["pfx_sha"]:
            raise CorruptArchiveError(
                f"checkpoint leaf {lid!r} failed integrity check: archive "
                f"prefix ({e['pfx_size']} bytes) does not match the "
                "manifest sha256 (corrupt or tampered bundle)")

    # ------------------------------------------------------------ lifecycle

    def close(self) -> None:
        self.source.close()

    def __enter__(self) -> "Bundle":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __repr__(self) -> str:
        return (f"Bundle(step={self.step}, {len(self.manifest['leaves'])} "
                f"leaves, {self.source.size} bytes)")
