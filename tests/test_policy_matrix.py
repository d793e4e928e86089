"""The ExecPolicy structural guarantee, as a matrix.

``ExecPolicy`` bundles exactly the knobs that may never change archive
bytes or reconstruction bits — backend substrate, chunk batching, mesh
sharding.  This suite drives the *new* object API (Codec / Archive /
ProgressiveReader) across the full policy matrix on v1 and v2 archives
and pins:

  * byte-identical archives from ``Codec.compress`` under every policy;
  * bit-identical reconstructions and refine deltas from
    ``ProgressiveReader`` under every policy, at every fidelity kind;
  * identical progressive accounting (bytes_read, achieved_bound).

Runs warning-clean by construction (no legacy shims are touched); the CI
deprecation lane enforces that with
``-W error::repro.api.IPCompDeprecationWarning``.
"""
import numpy as np
import pytest

from _fields import smooth_field
from repro import Archive, Codec, ExecPolicy, Fidelity

X = smooth_field((50, 41), seed=3)
V1 = Codec(eb=1e-5)
V2 = Codec(eb=1e-5, chunk_elems=400)   # several equal slabs + ragged tail


def _policies():
    """The matrix: backend x batch_chunks x shard, plus an explicit
    single-device mesh (valid everywhere a mesh is representable)."""
    pols = [ExecPolicy()]                                    # the default
    for backend in ("numpy", "jax"):
        for batch in (None, True, False):
            pols.append(ExecPolicy(backend=backend, batch_chunks=batch))
        pols.append(ExecPolicy(backend=backend, shard="auto"))
    import jax  # noqa: F401  (explicit mesh needs a device)
    from repro.parallel import codec_mesh
    pols.append(ExecPolicy(backend="jax", shard=codec_mesh.codec_mesh(1)))
    pols.append(ExecPolicy(backend="jax", shard=1))   # a device count
    pols.append(ExecPolicy(backend="numpy",
                           shard=codec_mesh.codec_mesh(1)))  # falls back
    return pols


POLICIES = _policies()
_IDS = [f"{p.backend}-b{p.batch_chunks}-s{getattr(p.shard, 'shape', p.shard)}"
        for p in POLICIES]

LADDER = (Fidelity.error_bound(1e-2), Fidelity.max_bytes(2500),
          Fidelity.bitrate(4.0), Fidelity.full())


def _session_trace(codec, policy):
    """Compress + a full progressive session under one policy ->
    (archive bytes, [(data, bytes_read, achieved_bound) per rung])."""
    arc = codec.compress(X, policy=policy)
    session = arc.open(policy)
    trace = []
    for fid, out in session.ladder(LADDER):
        trace.append((out.copy(), session.bytes_read,
                      session.achieved_bound))
    return arc.tobytes(), trace


# reference: the numpy default policy, computed once per codec
_REF = {c: _session_trace(c, ExecPolicy()) for c in (V1, V2)}


@pytest.mark.parametrize("policy", POLICIES, ids=_IDS)
@pytest.mark.parametrize("codec", [V1, V2], ids=["v1", "v2"])
def test_policy_never_changes_bytes_or_bits(codec, policy):
    if codec.chunk_elems is None and policy.shard is not None \
            and policy.shard != "auto":
        pytest.skip("explicit mesh on a v1 archive raises by contract "
                    "(covered in test_object_api)")
    ref_bytes, ref_trace = _REF[codec]
    got_bytes, got_trace = _session_trace(codec, policy)
    assert got_bytes == ref_bytes, "archive bytes depend on ExecPolicy"
    for (out, rd, bound), (rout, rrd, rbound) in zip(got_trace, ref_trace):
        assert np.array_equal(out, rout), \
            "reconstruction bits depend on ExecPolicy"
        assert rd == rrd and bound == rbound, \
            "progressive accounting depends on ExecPolicy"


def test_mixed_policy_session_equals_fixed_policy_session():
    """Swapping the policy between rungs of one session is invisible in
    the bits: the state is policy-agnostic by design."""
    arc = V2.compress(X)
    fixed = arc.open(ExecPolicy())
    mixed = arc.open(ExecPolicy())
    swaps = (ExecPolicy(backend="jax"), ExecPolicy(batch_chunks=False),
             ExecPolicy(backend="jax", shard="auto"), ExecPolicy())
    for fid, pol in zip(LADDER, swaps):
        mixed.policy = pol
        assert np.array_equal(fixed.read(fid), mixed.read(fid))
        assert fixed.bytes_read == mixed.bytes_read
        assert fixed.achieved_bound == mixed.achieved_bound


@pytest.mark.parametrize("policy",
                         [ExecPolicy(), ExecPolicy(backend="jax"),
                          ExecPolicy(backend="jax", batch_chunks=True)],
                         ids=["default", "jax", "jax-batched"])
@pytest.mark.parametrize("codec", [V1, V2], ids=["v1", "v2"])
def test_plane_cache_never_changes_bits(codec, policy):
    """Caching is an ExecPolicy-class concern: a shared plane cache (the
    serving tier's cross-session reuse, ``repro.serving.PlaneCache``)
    must never change reconstruction bits or achieved bounds — only
    ``bytes_read`` may shrink, when a hit skips already-decoded plane
    fetches."""
    from repro.serving import PlaneCache
    ref_bytes, ref_trace = _REF[codec]
    cache = PlaneCache()
    arc = Archive.frombytes(ref_bytes)
    arc.open(policy, plane_cache=cache).read(Fidelity.full())  # warm peer
    session = arc.open(policy, plane_cache=cache)
    for fid, (rout, rrd, rbound) in zip(LADDER, ref_trace):
        out = session.read(fid)
        assert np.array_equal(out, rout), \
            "reconstruction bits depend on the plane cache"
        assert session.achieved_bound == rbound, \
            "achieved bound depends on the plane cache"
        assert session.bytes_read <= rrd, \
            "a cache hit may only shrink bytes_read"
    assert cache.hits > 0, "the warmed cache must actually serve the session"


def test_writer_reader_policy_independence():
    """An archive written under any policy is read identically under any
    other (the format records nothing about the writer's policy)."""
    arc_np = V2.compress(X, policy=ExecPolicy(backend="numpy"))
    arc_jx = V2.compress(X, policy=ExecPolicy(backend="jax",
                                              batch_chunks=True))
    assert arc_np == arc_jx
    out_np = arc_jx.open(ExecPolicy(backend="numpy")).read(
        Fidelity.error_bound(1e-3))
    out_jx = arc_np.open(ExecPolicy(backend="jax")).read(
        Fidelity.error_bound(1e-3))
    assert np.array_equal(out_np, out_jx)
