"""Compression ratio over the window: field bytes over archive bytes,
each summed over every compress."""


def read(ctx):
    calls = ctx.window.calls("compress")
    if not calls:
        return None
    return sum(c["field_bytes"] for c in calls) / \
        sum(c["archive_bytes"] for c in calls)
