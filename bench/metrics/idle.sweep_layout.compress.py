"""Encode sweep: percent of the traced window in which the chip idled
under the program's spans of stage ``sweep_layout`` (``sweep.layout``:
each phase's gather of its targets, and the moveaxis and reshape copies
that put its sweep axis on lanes and back)."""
from bench import stages


def read(ctx):
    return stages.idle_share(ctx, "sweep_layout")
