"""Set-up seconds: from the start of the process to the start of the
window (imports, device, fields, archives, warm-up, compilation)."""


def read(ctx):
    return ctx.setup_s
