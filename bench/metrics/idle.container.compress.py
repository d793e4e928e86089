"""Container: percent of the traced window in which the chip idled under
the program's spans of stage ``container`` (``encode.prepare``,
``encode.container``: bound, chunk plan, slab stack, archive framing and
validation)."""
from bench import stages


def read(ctx):
    return stages.idle_share(ctx, "container")
