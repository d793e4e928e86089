"""Pallas TPU kernels for IPComp's compute hot spots.

A kernel *quintet* covers the profile of the paper's pipeline — two per
codec direction plus a fused decode megakernel (everything else is
metadata-sized):

  interp_quant    — fused interpolation-predict + quantize for one dimension
                    sweep (the O(n) inner loop of §4.1); returns (q, pred) so
                    the escape screen and writeback stay with the host
                    (``core.arith.screen``, shared by every backend).
  interp_recon    — its exact inverse: fused predict + add-residual for one
                    reconstruction sweep (the hot loop of retrieval,
                    Algorithms 1–2); shares the prediction code with
                    interp_quant so both directions are bit-identical.
  bitplane_pack   — negabinary conversion + 2-bit-prefix XOR predictive
                    coding + cross-lane bitplane packing (§4.4) in a single
                    VMEM pass (three integer ops per element).
  bitplane_unpack — the inverse: plane-word unpack + closed-form XOR-undo
                    ((1+x+x^2)^-1 over GF(2) = 22 shift/XORs) + negabinary
                    decode back to int32 bins.  The truncation mask
                    (``low_zero``) is a RUNTIME operand, so batched streams
                    with different loaded-plane prefixes share one launch.
  decode_fused    — the progressive-decode megakernel: bitplane_unpack +
                    negabinary dequantize in the field's arithmetic (the
                    float32 residual, or Algorithm 2's float64 delta against
                    the session's previous truncation), one launch per
                    level; ``low_zero`` and the scale ride along as runtime
                    per-row operands.

All five are wired into ``core.jax_backend`` behind the
``core.pipeline.backends`` registry and drive ``compress`` / ``retrieve`` /
``refine`` / ``decompress`` with ``backend="jax"``; blobs, bins, and
reconstructions are byte/bit-identical to the numpy reference pipeline
(enforced by tests/test_backend_parity.py, tests/test_decode_parity.py and
tests/test_fused_decode.py).  Each wrapper also ships a ``jax.vmap``-ed
``*_batch`` entry point over stacks of equal-shaped problems — the
chunk-batch engine's unit: B chunks, one launch — and a ``*_sharded``
entry point that splits the same stack over a 1-D device mesh via
``parallel.codec_mesh.shard_vmap`` (every device runs the vmapped kernel
on its local rows; one logical dispatch, mesh-size device launches).
Every launch is counted by ``kernels.dispatch`` (the batched-vs-looped
reduction and the sharded accounting are asserted in tests;
``benchmarks/backend_speed.py`` records throughput); rooflines come from
the device trace (``bench/roofline.py``).

Each kernel ships with ops.py (jit'd public wrapper, interpret-mode
switch) and ref.py or a pure-jnp XLA twin in kernel.py (the oracle for
the parity sweeps).  ``kernels.mode`` selects the substrate per call:
``IPCOMP_KERNEL_MODE=xla`` routes every wrapper to its jitted pure-jnp
twin — the same core functions, compiled by XLA on any backend — which is
what CI's ``compiled`` lane runs on CPU, where Pallas itself is
interpret-only; on a TPU that mode raises.  Every kernel body is built
from operations the chip's compiler (Mosaic) accepts — elementwise math
over (rows, lanes) blocks, no strided or unaligned lane slices, no lane
reshapes, no unsigned reductions, no float64 — and
``tests/test_tpu_compile.py`` compiles each for a v5e at real widths.
Arithmetic follows ``core.arith``: a float32 field computes in float32.
"""
