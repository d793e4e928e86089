"""Kernel-dispatch accounting for the batched chunk engine.

Every public kernel wrapper (``interp_quant`` / ``interp_recon`` /
``bitplane_pack`` / ``bitplane_unpack`` and their ``*_batch`` twins)
records exactly one launch per call: a ``jax.vmap``-ed call is ONE launch
whose batch axis becomes an extra grid dimension, which is the whole point
of batching equal-shaped chunks — B chunks stop costing B dispatches.

The chunk-batching parity tests and ``benchmarks/backend_speed.py`` use
:func:`measure` to assert the batched codec path issues strictly fewer
dispatches than the per-chunk loop (< chunks x levels for the per-level
pack/unpack ops).  Counting happens at the Python wrapper layer, so it is
exact in both interpret mode (CPU) and compiled Mosaic (TPU): one wrapper
call = one ``pallas_call`` execution.  Each launch is also counted on the
innermost open ``repro.trace`` span (``launches``), so a recorded request
shows which stage issued it.

Sharded execution adds a second axis to the accounting: a sharded call is
ONE logical dispatch (one traced ``shard_map``, counted in ``_counts``
like any other wrapper call) that launches the vmapped kernel on EVERY
mesh device — ``record(..., devices=D)`` stores that fan-out separately
and :func:`device_counts` / :func:`measure_devices` expose it (unsharded
calls record ``devices=1``).  The invariant is strictly per dispatch;
per-RUN totals follow from the *schedule*, which sharding may itself
change (the shape-group cap scales with the mesh size, and decode groups
that stay singleton take the scalar path in every mode), so run-level
claims like "sharded logical count == batched logical count" hold only
when the two schedules coincide — the sharded parity tests construct
chunk grids where they provably do.
"""
from __future__ import annotations

from collections import Counter
from contextlib import contextmanager
from typing import Dict, Iterator

from .. import trace

#: cumulative launches per kernel name since process start (or reset())
_counts: Counter = Counter()
#: cumulative per-device launches (launches weighted by mesh size; equals
#: _counts for unsharded calls)
_device_counts: Counter = Counter()
#: cumulative launches that ran in the Pallas interpreter (the CPU path);
#: a run on the chip must record none — ``chip_smoke.py`` checks this
_interpreted: Counter = Counter()


def record(name: str, devices: int = 1, interpret: bool = False) -> None:
    """Count one kernel launch.

    ``devices`` is the mesh fan-out of the launch: a ``shard_map``-ed call
    is one *logical* dispatch that runs on ``devices`` devices at once
    (1 = unsharded, the default).  ``interpret`` marks a launch that runs
    in the Pallas interpreter instead of a compiled kernel.
    """
    _counts[name] += 1
    _device_counts[name] += devices
    if interpret:
        _interpreted[name] += 1
    trace.count("launches")


def counts() -> Dict[str, int]:
    """Launches per kernel since start/reset (copy)."""
    return dict(_counts)


def device_counts() -> Dict[str, int]:
    """Per-device launches per kernel since start/reset (copy).

    Each logical dispatch contributes its mesh size (1 when unsharded), so
    this is the number of kernel executions actual hardware performs.
    """
    return dict(_device_counts)


def total() -> int:
    """Total launches across all kernels since start/reset."""
    return sum(_counts.values())


def interpreted_counts() -> Dict[str, int]:
    """Interpreted (non-compiled) launches per kernel since start/reset."""
    return dict(_interpreted)


def reset() -> None:
    _interpreted.clear()
    _counts.clear()
    _device_counts.clear()


@contextmanager
def measure() -> Iterator[Dict[str, int]]:
    """Collect the launches recorded inside the ``with`` block.

    Yields a dict that is filled in when the block exits:
    ``{kernel_name: launches}`` (kernels not dispatched are absent, so
    ``sum(d.values())`` is the block's total dispatch count).  Nesting and
    interleaving with the global counters are safe — the block only diffs
    snapshots.
    """
    before = Counter(_counts)
    out: Dict[str, int] = {}
    try:
        yield out
    finally:
        out.update((_counts - before))


@contextmanager
def measure_devices() -> Iterator[Dict[str, int]]:
    """Like :func:`measure`, but collecting *per-device* launches.

    The yielded dict maps kernel name to the number of on-device kernel
    executions inside the block: a sharded dispatch over a D-device mesh
    counts D, an unsharded one counts 1.  Pairs with :func:`measure` to
    assert both invariants of the sharded path at once — logical
    dispatches unchanged, device launches = logical x mesh size.
    """
    before = Counter(_device_counts)
    out: Dict[str, int] = {}
    try:
        yield out
    finally:
        out.update((_device_counts - before))
