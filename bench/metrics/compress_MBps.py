"""Field megabytes (1e6 bytes) compressed per second over the whole
window: host float32 array in, archive bytes in host memory out."""


def read(ctx):
    calls = ctx.window.calls("compress")
    if not calls:
        return None
    return sum(c["field_bytes"] for c in calls) / 1e6 / ctx.window.window_s
