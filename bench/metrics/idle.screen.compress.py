"""Encode sweep: percent of the traced window in which the chip idled
under the program's spans of stage ``screen`` (``sweep.screen``: escape
screen, writeback, stream bookkeeping)."""
from bench import stages


def read(ctx):
    return stages.idle_share(ctx, "screen")
