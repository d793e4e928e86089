"""Device: percent of the traced window in which no operation ran on the
chip (1 - union of operation intervals / window)."""


def read(ctx):
    return None if ctx.trace is None else 100.0 * ctx.trace.idle_share()
