"""Float32 archives written before the float32 arithmetic contract.

``tests/data/pre_f32_contract.npz`` holds archives, a checkpoint bundle
and their reads as the codec produced them when every field computed in
float64: a 24x30 float32 field (one escaped outlier) as a v1 archive and
as a 3-chunk v3 archive at eb = 1e-3 x range, each read at 0.05 x range
and in full; and an IPCB bundle of one 80x64 float32 leaf at rel_eb 1e-4,
restored at weight_error 1e-2 and in full.  Their headers record no
``vmax``, so readers pick the float64 contract (``docs/format.md`` §6)
and must reproduce those reads bit for bit.
"""
import os
import tempfile
from pathlib import Path

import numpy as np
import pytest

from repro.api import Archive, Codec, ExecPolicy, Fidelity
from repro.checkpoint import Bundle, LeafSpec, RestoreSession
from repro.checkpoint.bundle import encode_leaf
from repro.core.bytesource import BufferSource
from repro.core.container import parse_meta, parse_v3_meta

DATA = np.load(Path(__file__).parent / "data" / "pre_f32_contract.npz")


def _bits(a, b):
    return a.dtype == b.dtype and a.shape == b.shape and \
        a.tobytes() == b.tobytes()


@pytest.mark.parametrize("backend", ["numpy", "jax"])
@pytest.mark.parametrize("version", ["v1", "v3"])
def test_pre_contract_archive_reads_bit_identical(version, backend):
    blob = DATA[version].tobytes()
    x = DATA["field"]
    vr = float(x.max()) - float(x.min())
    sess = Archive(blob).open(ExecPolicy(backend=backend))
    coarse = sess.read(Fidelity.error_bound(0.05 * vr))
    assert _bits(coarse, DATA[version + "_coarse"])
    assert sess.achieved_bound == float(DATA[version + "_coarse_bound"])
    assert sess.bytes_read == int(DATA[version + "_coarse_bytes"])
    full = sess.read(Fidelity.full())
    assert _bits(full, DATA[version + "_full"])
    assert sess.bytes_read == int(DATA[version + "_full_bytes"])
    assert full.dtype == np.float32


@pytest.mark.parametrize("version", ["v1", "v3"])
def test_pre_contract_header_selects_float64(version):
    src = BufferSource(DATA[version].tobytes())
    if version == "v1":
        metas = [parse_meta(src)]
    else:
        metas = parse_v3_meta(src).chunk_metas
    for m in metas:
        assert m.dtype == "float32" and m.vmax is None
        assert m.work_dtype == np.float64


def test_pre_contract_bundle_restores_bit_identical():
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "old.ipcb")
        Path(p).write_bytes(DATA["bundle"].tobytes())
        with RestoreSession(Bundle.open(p)) as s:
            coarse = s.restore(1e-2)["w"]
            full = s.restore(None)["w"]
    assert _bits(coarse, DATA["bundle_coarse"])
    assert _bits(full, DATA["bundle_full"])


def test_new_float32_archive_records_float32_contract():
    x = DATA["field"]
    m = parse_meta(BufferSource(
        Codec(eb=1e-3, relative=True).compress(x).tobytes()))
    assert m.vmax is not None and m.work_dtype == np.float32


def test_checkpoint_leaf_below_float32_ulp_still_compresses():
    """Leaves compress under the float64 contract: at rel_eb 1e-9 (below
    the float32 ulp of most weights) they stay bitplane-progressive
    instead of escaping every element and falling back to raw."""
    r = np.random.default_rng(10)
    w = (r.standard_normal((256, 768)) / np.sqrt(768)).astype(np.float32)
    spec = LeafSpec(lid="w", arr=w, dtype="float32", raw_nbytes=w.nbytes)
    entry, blob = encode_leaf(spec, rel_eb=1e-9, interp="cubic")
    assert entry["kind"] in ("ipc", "ipc1")
    assert len(blob) < w.nbytes


def test_non_numeric_vmax_is_corrupt():
    import json
    import struct

    from repro.core.container import CorruptArchiveError

    blob = Codec(eb=1e-3, relative=True).compress(DATA["field"]).tobytes()
    (hl,) = struct.unpack("<I", blob[4:8])
    h = json.loads(blob[8:8 + hl])
    h["vmax"] = "large"
    hj = json.dumps(h, separators=(",", ":")).encode()
    bad = blob[:4] + struct.pack("<I", len(hj)) + hj + blob[8 + hl:]
    with pytest.raises(CorruptArchiveError, match="vmax"):
        parse_meta(BufferSource(bad))
