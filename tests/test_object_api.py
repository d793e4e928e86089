"""The first-class object API: Codec / Archive / Fidelity / ExecPolicy.

Construction-time validation, serialization round-trips, parity with the
legacy free functions (same bytes, same bits), and the hardened container
error paths (CorruptArchiveError on unknown magic / truncation at every
header boundary).  Session behavior lives in
``test_progressive_reader.py``; policy invariance in
``test_policy_matrix.py``.
"""
import struct

import numpy as np
import pytest

from _fields import smooth_field
from repro import (Archive, Codec, CorruptArchiveError, ExecPolicy,
                   Fidelity, IPCompDeprecationWarning, ProgressiveReader)
from repro.core import CUBIC, LINEAR, compress, decompress, metrics, retrieve
from repro.core import container


X = smooth_field((40, 30))


def _legacy(fn, *a, **kw):
    """Run a legacy shim, swallowing exactly its deprecation warning."""
    with pytest.warns(IPCompDeprecationWarning):
        return fn(*a, **kw)


# ------------------------------------------------------------------- Codec

def test_codec_validation():
    with pytest.raises(ValueError, match="positive"):
        Codec(eb=0.0)
    with pytest.raises(ValueError, match="positive"):
        Codec(eb=-1e-3)
    with pytest.raises(ValueError, match="interpolator"):
        Codec(eb=1e-4, interp="quintic")
    with pytest.raises(ValueError, match="chunk_elems"):
        Codec(eb=1e-4, chunk_elems=0)
    # frozen + hashable: usable as a cache key
    assert Codec(eb=1e-4) == Codec(eb=1e-4)
    assert hash(Codec(eb=1e-4)) == hash(Codec(eb=1e-4))
    with pytest.raises(AttributeError):
        Codec(eb=1e-4).eb = 2e-4


@pytest.mark.parametrize("chunk_elems", [None, 300])
def test_codec_matches_legacy_bytes(chunk_elems):
    """Codec.compress is the legacy compress, re-housed: same bytes."""
    arc = Codec(eb=1e-5, chunk_elems=chunk_elems).compress(X)
    legacy = _legacy(compress, X, 1e-5, chunk_elems=chunk_elems)
    assert arc.tobytes() == legacy


def test_codec_relative_and_interp():
    rng = float(X.max() - X.min())
    arc = Codec(eb=1e-4, relative=True, interp=LINEAR).compress(X)
    assert arc.eb == pytest.approx(1e-4 * rng)
    assert arc.interp == LINEAR
    out = arc.open().read()
    assert metrics.linf(X, out) <= arc.eb


# ----------------------------------------------------------------- Archive

def test_archive_views_and_roundtrip(tmp_path):
    arc = Codec(eb=1e-5).compress(X)
    assert arc.shape == X.shape and arc.dtype == X.dtype
    assert arc.eb == 1e-5 and arc.interp == CUBIC
    assert not arc.chunked and arc.n_chunks == 1
    assert arc.nbytes == len(arc.tobytes()) == len(arc)

    assert Archive.frombytes(arc.tobytes()) == arc
    assert hash(Archive.frombytes(arc.tobytes())) == hash(arc)

    p = tmp_path / "field.ipc"
    arc.save(p)
    assert Archive.load(p) == arc

    v2 = Codec(eb=1e-5, chunk_elems=300).compress(X)
    assert v2.chunked and v2.n_chunks > 1
    assert v2 != arc
    assert "v2" in repr(v2) and "v1" in repr(arc)

    # sessions share the Archive's validated header (no re-parse) while
    # keeping independent byte accounting
    a, b = v2.open(), v2.open()
    assert a._reader.meta is b._reader.meta
    a.read(Fidelity.error_bound(1e-2))
    assert a.bytes_read > 0 and b.bytes_read == 0


def test_archive_readable_by_legacy_functions():
    """Archive bytes are ordinary container bytes: the legacy surface and
    any pre-existing archive interoperate both ways."""
    arc = Codec(eb=1e-5, chunk_elems=300).compress(X)
    out, _ = _legacy(retrieve, arc.tobytes(), error_bound=1e-3)
    assert metrics.linf(X, out) <= 1e-3
    legacy_buf = _legacy(compress, X, 1e-5)
    assert np.array_equal(Archive(legacy_buf).open().read(),
                          _legacy(decompress, legacy_buf))


# ---------------------------------------------------------------- Fidelity

def test_fidelity_sum_type():
    assert Fidelity.error_bound(1e-3).kind == "error_bound"
    assert Fidelity.max_bytes(100).value == 100
    assert Fidelity.bitrate(2.0).kind == "bitrate"
    assert Fidelity.full().value is None
    # over-specification is unrepresentable through constructors and a
    # clear error through the legacy-coercion path
    with pytest.raises(ValueError, match="at most one"):
        Fidelity.from_targets(error_bound=1e-3, max_bytes=100)
    assert Fidelity.from_targets() == Fidelity.full()
    assert Fidelity.from_targets(bitrate=2.0) == Fidelity.bitrate(2.0)

    with pytest.raises(ValueError, match="positive"):
        Fidelity.error_bound(0)
    with pytest.raises(ValueError, match="positive"):
        Fidelity.bitrate(-1)
    with pytest.raises(ValueError, match="non-negative integer"):
        Fidelity.max_bytes(-5)
    with pytest.raises(ValueError, match="no value"):
        Fidelity("full", 3.0)
    with pytest.raises(ValueError, match="needs a value"):
        Fidelity("error_bound")
    with pytest.raises(ValueError, match="unknown fidelity"):
        Fidelity("psnr", 40.0)

    # fractional byte budgets are rejected, not silently truncated, and
    # whole floats normalize so both spellings compare equal
    with pytest.raises(ValueError, match="non-negative integer"):
        Fidelity.max_bytes(1000.7)
    assert Fidelity.max_bytes(64.0) == Fidelity.max_bytes(64)
    assert Fidelity.max_bytes(64.0).value == 64

    # bitrate converts exactly as the legacy path did
    assert Fidelity.bitrate(2.0).target_bytes(1000) == 250
    assert Fidelity.max_bytes(77).target_bytes(10) == 77
    assert Fidelity.full().target_bytes(10) is None
    assert eval(repr(Fidelity.max_bytes(64)),
                {"Fidelity": Fidelity}) == Fidelity.max_bytes(64)


# -------------------------------------------------------------- ExecPolicy

def test_exec_policy_validation():
    with pytest.raises(ValueError, match="unknown backend"):
        ExecPolicy(backend="cuda")
    with pytest.raises(ValueError, match="batch_chunks"):
        ExecPolicy(batch_chunks="yes")
    with pytest.raises(ValueError, match="shard must be"):
        ExecPolicy(shard="always")
    # frozen: policies are shareable values
    with pytest.raises(AttributeError):
        ExecPolicy().backend = "jax"
    # "auto" backends/meshes are symbolic until bind time
    assert ExecPolicy(backend="auto").backend == "auto"
    assert ExecPolicy(shard="auto").unsharded().shard is None


def test_exec_policy_mesh_contradictions():
    jax = pytest.importorskip("jax")
    from repro.parallel import codec_mesh
    mesh = codec_mesh.codec_mesh(1)
    # the archive-independent contradiction fails at CONSTRUCTION
    with pytest.raises(ValueError, match="stacked shape-group"):
        ExecPolicy(shard=mesh, batch_chunks=False)
    # the archive-dependent rule fails at bind time: v1 has no chunk grid
    pol = ExecPolicy(backend="jax", shard=mesh)
    with pytest.raises(ValueError, match="chunk grid"):
        Archive(Codec(eb=1e-4).compress(X).tobytes()).open(pol).read()
    # "auto" degrades quietly in the same situation
    out = Codec(eb=1e-4).compress(X).open(
        ExecPolicy(backend="jax", shard="auto")).read(
        Fidelity.error_bound(1e-2))
    assert metrics.linf(X, out) <= 1e-2


@pytest.mark.parametrize("shard", [0, -1, "too_many", True])
def test_exec_policy_bad_device_count_raises(shard):
    """A device count is a hard request, checked at construction: a
    count outside 1..jax.device_count(), or a bool, is refused."""
    jax = pytest.importorskip("jax")
    if shard == "too_many":
        shard = jax.device_count() + 1
    with pytest.raises(ValueError):
        ExecPolicy(backend="jax", shard=shard)


@pytest.mark.parametrize("shard", [None, "auto", 1, "mesh"])
def test_exec_policy_device_count(shard):
    """``shard=n`` is ``codec_mesh(n)``; None, "auto" and a Mesh resolve
    as before."""
    jax = pytest.importorskip("jax")
    from repro.parallel import codec_mesh
    if shard == "mesh":
        shard = codec_mesh.codec_mesh(1)
    mesh = ExecPolicy(backend="jax", shard=shard).bind(
        chunked=True, encode=True).mesh
    if shard is None or (shard == "auto" and jax.device_count() == 1):
        assert mesh is None
    elif shard == 1:
        assert mesh == codec_mesh.codec_mesh(1)
        assert codec_mesh.shard_count(mesh) == 1
    elif shard == "auto":
        assert codec_mesh.shard_count(mesh) == jax.device_count()
    else:
        assert mesh is shard


# ------------------------------------------------- decompress signature fix

def test_decompress_accepts_retrieve_kwargs():
    """Signature-drift regression: decompress takes the same execution
    kwargs as retrieve (batch_chunks included) and routes through the
    object API."""
    buf = _legacy(compress, X, 1e-5, chunk_elems=300)
    base = _legacy(decompress, buf)
    assert np.array_equal(base, _legacy(decompress, buf,
                                        batch_chunks=False))
    assert np.array_equal(base, _legacy(decompress, buf, backend="numpy",
                                        shard=None, batch_chunks=None))
    assert metrics.linf(X, base) <= 1e-5


# ------------------------------------------------- hardened container paths

def _v1():
    return Codec(eb=1e-5).compress(X).tobytes()


def _v2():
    return Codec(eb=1e-5, chunk_elems=300).compress(X).tobytes()


@pytest.mark.parametrize("make", [_v1, _v2], ids=["v1", "v2"])
def test_truncation_at_each_header_boundary(make):
    """Every framing boundary fails as CorruptArchiveError, not struct /
    json noise: magic, header-length word, header body, blob section."""
    buf = make()
    (hlen,) = struct.unpack("<I", buf[4:8])
    boundaries = [0, 2,            # inside the magic
                  4, 6,            # inside the header-length word
                  8, 8 + hlen // 2,  # inside the header JSON
                  8 + hlen + 1]    # inside the blob section
    for cut in boundaries:
        with pytest.raises(CorruptArchiveError):
            Archive(buf[:cut])
        with pytest.raises(CorruptArchiveError):
            container.open_reader(buf[:cut])


def test_unknown_magic_and_garbage():
    for junk in (b"", b"IP", b"ZSTD" + b"\0" * 64, b"IPC9" + b"\0" * 64):
        with pytest.raises(CorruptArchiveError, match="magic|truncated"):
            Archive(junk)
    # undecodable header JSON
    bad = container.MAGIC + struct.pack("<I", 4) + b"\xff\xfe\xfd\xfc"
    with pytest.raises(CorruptArchiveError, match="undecodable"):
        Archive(bad)
    # decodable JSON, wrong schema
    bad = container.MAGIC + struct.pack("<I", 2) + b"[]"
    with pytest.raises(CorruptArchiveError, match="malformed|expected an"):
        Archive(bad)


def test_corrupt_archive_error_is_a_value_error():
    """Compatibility: pre-existing ``except ValueError`` handling (and
    the historical parse_meta v2-dispatch error) keep working."""
    assert issubclass(CorruptArchiveError, ValueError)
    with pytest.raises(ValueError):
        container.parse_meta(_v2())  # v2 buffer through the v1 parser


def test_header_internal_inconsistency():
    """A decodable header whose tables contradict each other (nbits vs
    plane lists vs delta table, anchors size vs shape) fails at Archive
    construction, not as an IndexError mid-retrieval."""
    import json
    buf = _v1()
    (hlen,) = struct.unpack("<I", buf[4:8])
    h = json.loads(buf[8:8 + hlen].decode())

    def rebuild(hh):
        hj = json.dumps(hh, separators=(",", ":")).encode()
        return container.MAGIC + struct.pack("<I", len(hj)) + hj \
            + buf[8 + hlen:]

    bad = json.loads(json.dumps(h))
    bad["levels"][0]["nbits"] += 1            # planes no longer match
    with pytest.raises(CorruptArchiveError, match="nbits"):
        Archive(rebuild(bad))
    bad = json.loads(json.dumps(h))
    bad["levels"][-1]["delta_table"] = bad["levels"][-1]["delta_table"][:-1]
    with pytest.raises(CorruptArchiveError, match="delta table"):
        Archive(rebuild(bad))
    bad = json.loads(json.dumps(h))
    bad["anchors_shape"] = [s + 1 for s in bad["anchors_shape"]]
    with pytest.raises(CorruptArchiveError, match="anchors"):
        Archive(rebuild(bad))


def test_read_rejects_bare_numbers():
    """The likeliest migration slip — session.read(1e-3) instead of
    read(Fidelity.error_bound(1e-3)) — is a clear TypeError at the
    session boundary, not an AttributeError inside the planner."""
    s = Codec(eb=1e-4).compress(X).open()
    with pytest.raises(TypeError, match="Fidelity"):
        s.read(1e-3)
    with pytest.raises(TypeError, match="Fidelity"):
        s.refine("full")


def test_corrupt_chunk_table_extents():
    """A v2 header whose chunk extent points outside the buffer fails at
    parse time, not as a short read mid-retrieval."""
    buf = _v2()
    # drop the last 8 bytes of the final chunk's archive
    with pytest.raises(CorruptArchiveError, match="extent"):
        Archive(buf[:-8])
