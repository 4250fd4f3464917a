"""Model step: device time of one prefill-chunk program, averaged over
its runs in the traced stretch."""


def read(ctx):
    return ctx.trace.program_ms("prefill.chunk") if ctx.trace else None
