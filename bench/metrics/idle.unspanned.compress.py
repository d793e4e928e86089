"""Host pipeline: percent of the traced window in which the chip idled
under no staged program span: the harness between calls, and host work
inside a compress that no stage names (the own time of the ``encode``
request and of each ``sweep.phase``, or of any span a later program
adds without a stage and outside every staged span)."""
from bench import stages


def read(ctx):
    return stages.idle_share(ctx, stages.UNSPANNED)
