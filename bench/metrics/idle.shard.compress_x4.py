"""Sharded chunk grid: percent of the traced window in which the chip
idled under the program's spans of stage ``shard`` (``shard.layout``:
padding a chunk stack to a mesh multiple, placing it over the mesh,
slicing the pad rows off the outputs)."""
from bench import stages


def read(ctx):
    return stages.idle_share(ctx, "shard")
