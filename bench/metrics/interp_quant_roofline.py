"""Kernels: ``interp_quant``'s share of the HBM roofline, in percent.

The least bytes of one encode sweep over the field (``bench.roofline``)
times the compresses in the traced window, over the device time of the
kernel's operations times the chip's peak bandwidth."""
from bench import roofline

KERNELS = ("interp_quant",)


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace.kernel_s(KERNELS)
    n = len(ctx.window.calls("compress"))
    if t <= 0 or not n:
        return None
    need = n * roofline.field_sweep_bytes(ctx.config)
    return 100.0 * need / (t * roofline.peak_gbs(ctx.device["kind"]) * 1e9)
