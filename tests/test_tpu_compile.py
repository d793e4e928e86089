"""Every Pallas kernel of the codec compiles for a TPU v5e, uninterpreted.

The chip's compiler (Mosaic) is installed beside JAX and compiles for a
described, unattached chip, so these tests run on the CPU and cost no chip
time.  They use ``chip_smoke.py``'s real shapes: SpeedX (100 x 500 x 500
float32) cut into 16 x 500 x 500 chunks, six of which share each batched
launch; the phase shapes are the finest level's sweeps along each axis,
and the bitplane kernels see that level's 3.5M-element stream (the fused
decode also the next level's, whose row count once overflowed VMEM).

Each kernel is compiled alone (one problem) and through its public batched
entry point; every compiled program must hold a Mosaic kernel
(``tpu_custom_call``) and no float64.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import arith, interpolation

CHUNK = (16, 500, 500)
BATCH = 6
EB = 1e-6 * 2.5            # absolute bound of the order chip_smoke uses


def _phases():
    """(R, C, stride) of the finest level's three sweeps of one chunk."""
    L = interpolation.num_levels(CHUNK)
    out = []
    for ph in interpolation.iter_phases(CHUNK, L):
        if ph.level == 1:
            dims = [len(range(*sl.indices(CHUNK[d])))
                    for d, sl in enumerate(ph.view)]
            C = dims[ph.dim]
            out.append((int(np.prod(dims)) // C, C, ph.stride))
    return out


PHASES = _phases()
#: the two finest levels' stream lengths (3.5M and 437500 elements)
N_STREAMS = interpolation.level_sizes(CHUNK,
                                      interpolation.num_levels(CHUNK))[-2:]
N_STREAM = N_STREAMS[-1]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *shapes):
    """jit + lower + compile for the described chip; returns HLO text."""
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    assert "f64" not in text
    return text


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("R,C,s", PHASES)
def test_interp_quant_compiles(one_chip, R, C, s):
    from repro.kernels.interp_quant import interp_quant_batch

    x = _spec(one_chip, (BATCH, R, C), jnp.float32)
    _compile(lambda a, b: interp_quant_batch(a, b, s=s, eb=EB,
                                             interpret=False), x, x)


@pytest.mark.parametrize("R,C,s", PHASES)
def test_interp_recon_compiles(one_chip, R, C, s):
    from repro.kernels.interp_recon import interp_recon_batch

    T = len(range(s, C, 2 * s))
    _compile(lambda a, b: interp_recon_batch(a, b, s=s, interpret=False),
             _spec(one_chip, (BATCH, R, C), jnp.float32),
             _spec(one_chip, (BATCH, R, T), jnp.float32))


def test_interp_kernels_compile_alone(one_chip):
    from repro.kernels.interp_quant.kernel import interp_quant_pallas
    from repro.kernels.interp_recon.kernel import interp_recon_pallas

    R, C, s = PHASES[-1]
    T = len(range(s, C, 2 * s))
    c = arith.consts(EB, np.float32)
    x = _spec(one_chip, (R, C), jnp.float32)
    _compile(lambda a, b: interp_quant_pallas(a, b, s=s, c=c,
                                              interpret=False), x, x)
    _compile(lambda a, b: interp_recon_pallas(a, b, s=s, interpret=False),
             x, _spec(one_chip, (R, T), jnp.float32))


@pytest.mark.parametrize("batch", [None, BATCH])
def test_bitplane_pack_compiles(one_chip, batch):
    from repro.kernels.bitplane_pack import bitplane_pack, bitplane_pack_batch

    if batch is None:
        _compile(lambda q: bitplane_pack(q, interpret=False)[0],
                 _spec(one_chip, (N_STREAM,), jnp.int32))
    else:
        _compile(lambda q: bitplane_pack_batch(q, interpret=False)[0],
                 _spec(one_chip, (batch, N_STREAM), jnp.int32))


@pytest.mark.parametrize("batch", [None, BATCH])
def test_bitplane_unpack_compiles(one_chip, batch):
    from repro.kernels.bitplane_pack import (bitplane_unpack,
                                             bitplane_unpack_batch)

    nw = -(-N_STREAM // 32)
    if batch is None:
        _compile(lambda w: bitplane_unpack(w, N_STREAM, low_zero=3,
                                           with_nb=True, interpret=False),
                 _spec(one_chip, (32, nw), jnp.uint32))
    else:
        _compile(lambda w: bitplane_unpack_batch(
            w, N_STREAM, low_zero=[3] * batch, with_nb=True,
            interpret=False), _spec(one_chip, (batch, 32, nw), jnp.uint32))


@pytest.mark.parametrize("n", N_STREAMS)
@pytest.mark.parametrize("batch", [None, BATCH])
def test_decode_fused_compiles(one_chip, batch, n):
    from repro.kernels.decode_fused import decode_fused, decode_fused_batch

    nw = -(-n // 32)
    if batch is None:
        _compile(lambda w: decode_fused(w, None, n, eb=EB, low_zero=2,
                                        interpret=False, dtype=np.float32),
                 _spec(one_chip, (32, nw), jnp.uint32))
    else:
        _compile(lambda w: decode_fused_batch(
            w, None, n, eb=[EB] * batch, low_zero=[2] * batch,
            interpret=False, dtype=np.float32),
            _spec(one_chip, (batch, 32, nw), jnp.uint32))


# ---------------------------------------------- no fallback on the chip path

def test_float64_field_on_tpu_names_numpy_backend(monkeypatch):
    """On a TPU the jax backend refuses float64 fields instead of moving
    them to the host; the error points at the numpy backend."""
    from repro.core import jax_backend

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="backend='numpy'"):
        jax_backend.decorrelate(np.zeros((8, 8)), 1e-3, "cubic")
    assert jax_backend._x64(np.float32) is not None  # float32 is fine


def test_xla_kernel_mode_refused_on_tpu(monkeypatch):
    from repro.kernels import mode

    monkeypatch.setenv(mode.ENV, mode.XLA)
    assert mode.use_xla()  # CPU: the compiled-twin lane still works
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="not allowed on a TPU"):
        mode.use_xla()
