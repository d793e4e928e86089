"""Encode sweep: percent of the traced window in which the chip idled
under the program's spans of stage ``kernel_io`` (``sweep.kernel``,
``pack.kernel``: uploads, dispatch, downloads around the ``interp_quant``
and ``bitplane_pack`` launches)."""
from bench import stages


def read(ctx):
    return stages.idle_share(ctx, "kernel_io")
