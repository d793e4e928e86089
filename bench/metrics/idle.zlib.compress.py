"""Entropy coding: percent of the traced window in which the chip idled
under the program's spans of stage ``zlib`` (``pack.zlib``: plane
truncation and zlib, escape blobs)."""
from bench import stages


def read(ctx):
    return stages.idle_share(ctx, "zlib")
