"""Sharded chunk-grid execution: shard_map parity with the single-device path.

The acceptance bar of the sharded subsystem: placing each shape group's
stacked chunk slab across a 1-D device mesh (``shard="auto"`` / an explicit
mesh) must emit archives byte-identical — and reconstructions, refine
deltas, and progressive accounting bit-identical — to the single-device
jax backend, with one *logical* kernel dispatch per phase whose device
fan-out equals the mesh size.  The mesh, like the batch axis, is an
execution detail, never a format change.

Every parity test here runs at any local device count (an explicit mesh
over all devices degenerates gracefully to 1 device); the tests marked
``skipif device_count < 8`` additionally pin the multi-device behaviour
and run in CI's sharded lane under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import jax
import numpy as np
import pytest

from _fields import smooth_field
from repro.core import (CUBIC, compress, decompress, metrics, open_archive,
                        refine, retrieve)
from repro.core.pipeline import backends
from repro.core.pipeline.encode import (MAX_BATCH_CHUNKS, group_cap,
                                        resolve_exec_mesh, shape_groups)
from repro.kernels import dispatch
from repro.parallel import codec_mesh

N_DEV = jax.device_count()

multi_device = pytest.mark.skipif(
    N_DEV < 8, reason="needs the forced 8-device host mesh "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def _chunky_field(shape=(50, 41), seed=0, rough=0.01):
    rng = np.random.default_rng(seed)
    return smooth_field(shape, seed) + rough * rng.standard_normal(shape)


def _mesh_all():
    return codec_mesh.codec_mesh()


# ----------------------------------------------------- mesh/axis plumbing

def test_codec_mesh_shape():
    mesh = _mesh_all()
    assert tuple(mesh.axis_names) == (codec_mesh.CODEC_AXIS,)
    assert codec_mesh.shard_count(mesh) == N_DEV
    with pytest.raises(ValueError):
        codec_mesh.codec_mesh(N_DEV + 1)
    with pytest.raises(ValueError):
        codec_mesh.codec_mesh(0)


def test_resolve_shard_contract():
    assert codec_mesh.resolve_shard(None) is None
    assert codec_mesh.resolve_shard(False) is None
    mesh = _mesh_all()
    assert codec_mesh.resolve_shard(mesh) is mesh
    auto = codec_mesh.resolve_shard("auto")
    if N_DEV > 1:  # "auto" shards only when there is something to shard
        assert codec_mesh.shard_count(auto) == N_DEV
    else:
        assert auto is None
    # a device count is codec_mesh(n), checked like an explicit mesh
    assert codec_mesh.resolve_shard(N_DEV) == mesh
    assert codec_mesh.shard_count(codec_mesh.resolve_shard(1)) == 1
    for bad in (0, -1, N_DEV + 1):
        with pytest.raises(ValueError, match="devices"):
            codec_mesh.resolve_shard(bad)
    with pytest.raises(ValueError, match="shard must be"):
        codec_mesh.resolve_shard("always")
    with pytest.raises(ValueError, match="shard must be"):
        codec_mesh.resolve_shard(True)


def test_resolve_shard_rejects_2d_mesh():
    from repro.parallel import compat
    mesh2 = compat.make_mesh((1, 1), ("a", "b"), devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="1-D mesh"):
        codec_mesh.resolve_shard(mesh2)


def test_pad_to_shards():
    mesh = _mesh_all()
    for b in (1, 3, N_DEV, 2 * N_DEV + 1):
        total = b + codec_mesh.pad_to_shards(b, mesh)
        assert total % N_DEV == 0 and total - b < N_DEV


def test_group_cap_scales_with_mesh():
    """MAX_BATCH_CHUNKS stays the per-device working-set bound: a mesh of
    n devices schedules n-times-larger stacks."""
    assert group_cap(None) == MAX_BATCH_CHUNKS
    mesh = _mesh_all()
    assert group_cap(mesh) == MAX_BATCH_CHUNKS * N_DEV
    rows = [3] * (MAX_BATCH_CHUNKS * N_DEV + 2)
    groups = shape_groups(rows, max_group=group_cap(mesh))
    assert [len(g) for g in groups] == [MAX_BATCH_CHUNKS * N_DEV, 2]


def test_backend_sharded_slots():
    """jax ships the sharded primitives; the numpy reference, like with
    batching, deliberately stays a per-chunk loop."""
    jx, np_ = backends.get("jax"), backends.get("numpy")
    assert jx.shards_encode and jx.shards_decode
    assert not np_.shards_encode and not np_.shards_decode


def test_exec_mesh_policy():
    mesh = _mesh_all()
    # explicit mesh without a chunk grid / without the stacked scheduler
    with pytest.raises(ValueError, match="chunk grid"):
        resolve_exec_mesh(mesh, True, chunked=False, batch_chunks=None)
    with pytest.raises(ValueError, match="stacked shape-group"):
        resolve_exec_mesh(mesh, True, chunked=True, batch_chunks=False)
    # "auto" degrades quietly in the same situations
    assert resolve_exec_mesh("auto", True, chunked=False,
                             batch_chunks=None) is None
    assert resolve_exec_mesh("auto", True, chunked=True,
                             batch_chunks=False) is None
    # backends without sharded primitives fall back to their own path
    assert resolve_exec_mesh(mesh, False, chunked=True,
                             batch_chunks=None) is None
    assert resolve_exec_mesh(mesh, True, chunked=True,
                             batch_chunks=None) is mesh


def test_shard_errors_through_public_api():
    x = _chunky_field((20, 10))
    mesh = _mesh_all()
    with pytest.raises(ValueError, match="chunk grid"):
        compress(x, 1e-4, backend="jax", shard=mesh)
    with pytest.raises(ValueError, match="stacked shape-group"):
        compress(x, 1e-4, backend="jax", chunk_elems=50, shard=mesh,
                 batch_chunks=False)
    v1 = compress(x, 1e-4)
    with pytest.raises(ValueError, match="chunk grid"):
        retrieve(v1, error_bound=1e-2, backend="jax", shard=mesh)
    # "auto" is a no-op on v1 rather than an error
    out, _ = retrieve(v1, error_bound=1e-2, backend="jax", shard="auto")
    assert metrics.linf(x, out) <= 1e-2


# --------------------------------------------------------- encode parity

@pytest.mark.slow
@pytest.mark.parametrize("shape,chunk", [((50, 41), 500),   # ragged tail
                                         ((3000,), 700),
                                         ((24, 20, 18), 2000)])
def test_sharded_compress_byte_identical(shape, chunk):
    """Sharded, batched, looped, and numpy archives are the same bytes —
    including ragged shape groups that pad up to the mesh size."""
    x = _chunky_field(shape)
    mesh = _mesh_all()
    b_shard = compress(x, 1e-5, CUBIC, backend="jax", chunk_elems=chunk,
                       shard=mesh)
    b_bat = compress(x, 1e-5, CUBIC, backend="jax", chunk_elems=chunk)
    b_np = compress(x, 1e-5, CUBIC, backend="numpy", chunk_elems=chunk)
    assert b_shard == b_bat == b_np


@pytest.mark.slow
def test_sharded_single_chunk_archive():
    """A one-chunk grid has nothing to split: the scheduler falls through
    to the scalar path and the archive still round-trips."""
    x = _chunky_field((16, 10))
    buf = compress(x, 1e-5, backend="jax", chunk_elems=10 ** 6,
                   shard=_mesh_all())
    assert buf == compress(x, 1e-5, backend="numpy", chunk_elems=10 ** 6)
    assert metrics.linf(x, decompress(buf, backend="jax",
                                      shard=_mesh_all())) <= 1e-5


def test_numpy_backend_shard_is_fallback():
    """Backends without sharded primitives fall back to the loop — bytes
    unchanged, no error, even for an explicit mesh (mirrors how missing
    *_batch slots fall back)."""
    x = _chunky_field((30, 20))
    a = compress(x, 1e-4, backend="numpy", chunk_elems=200,
                 shard=_mesh_all())
    b = compress(x, 1e-4, backend="numpy", chunk_elems=200)
    assert a == b


# --------------------------------------------------------- decode parity

@pytest.mark.slow
@pytest.mark.parametrize("mode", [dict(error_bound=1e-3),
                                  dict(max_bytes=3000), dict()])
def test_sharded_retrieve_bit_identical(mode):
    """Every plan mode: sharded == batched == numpy, bit for bit, with
    identical per-chunk progressive accounting."""
    x = _chunky_field((50, 41))
    buf = compress(x, 1e-5, chunk_elems=500)
    a, sa = retrieve(open_archive(buf), backend="jax", shard=_mesh_all(),
                     **mode)
    b, sb = retrieve(open_archive(buf), backend="jax", **mode)
    c, sc = retrieve(open_archive(buf), backend="numpy", **mode)
    assert np.array_equal(a, b) and np.array_equal(a, c)
    assert sa.bytes_read == sb.bytes_read == sc.bytes_read
    assert sa.err_bound == sb.err_bound == sc.err_bound
    for ca, cb in zip(sa.chunk_states, sb.chunk_states):
        assert ca.planes_loaded == cb.planes_loaded
        assert ca.bytes_read == cb.bytes_read
        assert np.array_equal(ca.xhat, cb.xhat)


@pytest.mark.slow
def test_sharded_refine_after_retrieve():
    """Algorithm 2 on the mesh: every rung of a sharded progressive ladder
    matches the single-device ladder bit-for-bit, refine still fetches
    only missing planes, and the state stays mesh-agnostic (sharded and
    unsharded calls interleave freely on one state)."""
    x = _chunky_field((80, 44), 2)
    buf = compress(x, 1e-6, CUBIC, chunk_elems=900)
    mesh = _mesh_all()
    r1, st1 = open_archive(buf), None
    r2, st2 = open_archive(buf), None
    for i, E in enumerate((1e-1, 1e-3, None)):
        kw = {} if E is None else dict(error_bound=E)
        o1, st1 = retrieve(r1, state=st1, backend="jax", shard=mesh, **kw)
        # interleave: even rungs unsharded, odd rungs sharded
        o2, st2 = retrieve(r2, state=st2, backend="jax",
                           shard=mesh if i % 2 else None, **kw)
        assert np.array_equal(o1, o2)
        assert st1.bytes_read == st2.bytes_read
    # repeating the final bound re-reads nothing and stays exact
    prev = st1.bytes_read
    out, st1 = refine(st1, backend="jax", shard=mesh)
    assert st1.bytes_read == prev
    assert metrics.linf(x, out) <= 1e-6


@pytest.mark.slow
def test_sharded_mixed_plane_prefixes():
    """Byte-budget plans give chunks different plane prefixes, so the
    (nbits, prefix) decode groups are ragged w.r.t. the mesh — sharded
    results must still match the loop exactly."""
    rng = np.random.default_rng(3)
    x = smooth_field((60, 33), 1)
    x[:20] += 0.5 * rng.standard_normal((20, 33))  # chunk 0 much rougher
    buf = compress(x, 1e-6, chunk_elems=700)
    for budget in (4000, 9000):
        a, sa = retrieve(open_archive(buf), max_bytes=budget, backend="jax",
                         shard=_mesh_all())
        b, sb = retrieve(open_archive(buf), max_bytes=budget, backend="jax")
        assert np.array_equal(a, b)
        assert sa.bytes_read == sb.bytes_read


@pytest.mark.slow
def test_sharded_with_escapes_bit_identical():
    """Escaped outliers land in specific chunks: per-chunk override
    writeback must hit the same points on the mesh."""
    x = smooth_field((40, 40), 1)
    x[13, 17] = 1e15
    x[35, 2] = -1e15
    with np.errstate(invalid="ignore"):
        buf = compress(x, 1e-7, CUBIC, chunk_elems=400, backend="jax",
                       shard=_mesh_all())
        assert buf == compress(x, 1e-7, CUBIC, chunk_elems=400,
                               backend="numpy")
    a, _ = retrieve(open_archive(buf), error_bound=1e-2, backend="jax",
                    shard=_mesh_all())
    b, _ = retrieve(open_archive(buf), error_bound=1e-2, backend="jax")
    assert np.array_equal(a, b)


# ------------------------------------------------------ dispatch accounting

@pytest.mark.slow
def test_sharded_dispatch_counts_per_device():
    """The two accounting invariants: sharding leaves the *logical*
    dispatch schedule of the batched engine untouched, and each sharded
    dispatch fans out to exactly one launch per mesh device.  The (48, 41)
    grid splits into 4 equal chunks — one shape group, no ragged tail,
    and 4 < MAX_BATCH_CHUNKS so the mesh-scaled group cap cannot merge
    groups differently — which is what makes the sharded and batched
    logical schedules provably coincide here (they need not in general;
    see kernels/dispatch.py)."""
    x = _chunky_field((48, 41))
    mesh = _mesh_all()
    buf = compress(x, 1e-5, backend="jax", chunk_elems=500)
    with dispatch.measure() as m_bat:
        compress(x, 1e-5, backend="jax", chunk_elems=500)
    with dispatch.measure() as m_sh, dispatch.measure_devices() as md_sh:
        buf_sh = compress(x, 1e-5, backend="jax", chunk_elems=500,
                          shard=mesh)
    assert buf_sh == buf
    assert m_sh == m_bat                      # same logical schedule
    assert md_sh == {k: v * N_DEV for k, v in m_sh.items()}

    retrieve(open_archive(buf), error_bound=1e-3, backend="jax")  # warm
    with dispatch.measure() as d_bat:
        retrieve(open_archive(buf), error_bound=1e-3, backend="jax")
    with dispatch.measure() as d_sh, dispatch.measure_devices() as dd_sh:
        retrieve(open_archive(buf), error_bound=1e-3, backend="jax",
                 shard=mesh)
    assert d_sh == d_bat
    # the reconstruction sweeps always run on the full stack -> exact
    # mesh fan-out; fused plane decodes group by (nbits,) — prefixes are
    # runtime operands — and singleton groups stay unsharded IN BOTH MODES
    # (that is why the logical counts match), so their fan-out is bounded,
    # not exact
    assert dd_sh["interp_recon"] == d_sh["interp_recon"] * N_DEV
    assert dd_sh["decode_fused"] <= d_sh["decode_fused"] * N_DEV
    if N_DEV > 1:  # at least one multi-chunk decode group got sharded
        assert dd_sh["decode_fused"] > d_sh["decode_fused"]


@pytest.mark.slow
def test_unsharded_device_counts_equal_logical():
    x = _chunky_field((48, 41))
    with dispatch.measure() as m, dispatch.measure_devices() as md:
        compress(x, 1e-5, backend="jax", chunk_elems=500)
    assert md == m


# ------------------------------------------------- forced 8-device lane

@multi_device
def test_eight_device_mesh_is_real():
    """CI's sharded lane forces 8 host devices; the auto mesh must span
    all of them and the device fan-out must show 8x."""
    mesh = codec_mesh.resolve_shard("auto")
    assert codec_mesh.shard_count(mesh) == 8
    x = _chunky_field((48, 41))
    with dispatch.measure() as m, dispatch.measure_devices() as md:
        compress(x, 1e-5, backend="jax", chunk_elems=500, shard="auto")
    assert md == {k: v * 8 for k, v in m.items()}


@multi_device
def test_eight_device_more_chunks_than_devices():
    """12 equal chunks over 8 devices: pad-to-mesh plus a 2-rows-per-device
    split, byte/bit-identical to single-device end to end."""
    x = _chunky_field((96, 41), 5)
    buf_sh = compress(x, 1e-5, backend="jax", chunk_elems=350, shard="auto")
    buf = compress(x, 1e-5, backend="numpy", chunk_elems=350)
    assert buf_sh == buf
    assert len(open_archive(buf).meta.chunks) >= 12
    a, _ = retrieve(open_archive(buf), error_bound=1e-4, backend="jax",
                    shard="auto")
    b, _ = retrieve(open_archive(buf), error_bound=1e-4, backend="numpy")
    assert np.array_equal(a, b)
