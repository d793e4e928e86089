"""Entropy coding: percent of the traced window in which the chip idled
under the program's spans of stage ``negabinary`` (``pack.negabinary``:
negabinary conversion and the truncation-loss tables)."""
from bench import stages


def read(ctx):
    return stages.idle_share(ctx, "negabinary")
