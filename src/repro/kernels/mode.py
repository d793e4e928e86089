"""Kernel execution mode: Pallas launches vs jitted XLA twins.

Pallas on CPU only supports interpret mode (jax refuses ``interpret=False``
outside TPU), so CI cannot literally compile the kernels on its CPU
runners.  The ``compiled`` CI lane instead sets ``IPCOMP_KERNEL_MODE=xla``:
every public kernel wrapper then routes to a ``jax.jit``-ed pure-jnp twin
of the kernel body — genuinely compiled XLA CPU execution of the same
arithmetic (the twins share the kernel-body core functions, so bit parity
cannot drift), with dispatch accounting still recorded at the wrapper
layer (one wrapper call = one compiled dispatch, same invariant as one
``pallas_call``).

Modes:

  * ``pallas`` (default) — ``pl.pallas_call``; interpret mode on CPU/GPU,
    Mosaic-compiled on TPU;
  * ``xla``             — the jitted pure-jnp core, CPU/GPU only.  On a
    TPU it raises: the chip path runs the Pallas kernels or nothing, never
    a silent substitute.

The knob is read per wrapper call (cheap: one env lookup), so tests can
flip it with ``monkeypatch.setenv`` without reimporting anything.
"""
from __future__ import annotations

import os

PALLAS = "pallas"
XLA = "xla"

ENV = "IPCOMP_KERNEL_MODE"


def kernel_mode() -> str:
    """Resolve the active kernel execution mode from the environment."""
    m = os.environ.get(ENV, PALLAS).strip().lower() or PALLAS
    if m not in (PALLAS, XLA):
        raise ValueError(f"{ENV} must be '{PALLAS}' or '{XLA}', got {m!r}")
    return m


def use_xla() -> bool:
    """True when wrappers should dispatch the jitted XLA twin; raises
    ``RuntimeError`` if that is asked for on a TPU."""
    if kernel_mode() != XLA:
        return False
    import jax

    if jax.default_backend() == "tpu":
        raise RuntimeError(f"{ENV}={XLA} is not allowed on a TPU: the "
                           "codec runs its Pallas kernels there")
    return True
