"""Host pipeline: kernel launches (the program's dispatch counter) in the
window per field megabyte (1e6 bytes) compressed."""


def read(ctx):
    mb = sum(c["field_bytes"] for c in ctx.window.calls("compress")) / 1e6
    return sum(ctx.launches.values()) / mb if mb else None
