"""Pallas kernel f64 sweep (x64 enabled per-test via context manager —
flipping the global flag would poison dtype expectations of the rest of
the suite running in the same process)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.interp_quant import interp_quant, interp_quant_ref
from repro.kernels.interp_recon import interp_recon, interp_recon_ref


@pytest.mark.parametrize("shape,s", [((8, 128), 1), ((16, 256), 4),
                                     ((8, 130), 1)])
@pytest.mark.parametrize("interp", ["linear", "cubic"])
def test_interp_quant_f64(shape, s, interp):
    with jax.enable_x64(True):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal(shape), jnp.float64)
        xh = jnp.asarray(rng.standard_normal(shape), jnp.float64)
        q, pred = interp_quant(x, xh, s=s, eb=1e-6, interp=interp)
        q_ref, pred_ref = interp_quant_ref(x, xh, s, 1e-6, interp)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
        np.testing.assert_allclose(np.asarray(pred), np.asarray(pred_ref),
                                   rtol=1e-12, atol=1e-12)


@pytest.mark.parametrize("shape,s", [((8, 128), 1), ((16, 256), 4),
                                     ((8, 130), 1)])
@pytest.mark.parametrize("interp", ["linear", "cubic"])
def test_interp_recon_f64(shape, s, interp):
    with jax.enable_x64(True):
        rng = np.random.default_rng(2)
        R, C = shape
        T = len(range(s, C, 2 * s))
        xh = jnp.asarray(rng.standard_normal(shape), jnp.float64)
        res = jnp.asarray(rng.standard_normal((R, T)), jnp.float64)
        out = interp_recon(xh, res, s=s, interp=interp)
        ref = interp_recon_ref(xh, res, s, interp)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
