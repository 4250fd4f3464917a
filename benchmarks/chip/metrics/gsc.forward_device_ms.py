"""Model: device time of one forward program over a batch, averaged
over its runs in the traced stretch."""


def read(ctx):
    return ctx.trace.program_ms("gsc.forward") if ctx.trace else None
