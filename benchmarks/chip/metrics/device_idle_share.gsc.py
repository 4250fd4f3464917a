"""Device: share of the traced stretch in which no operation ran on the
chip, while the classifier streamed."""


def read(ctx):
    return ctx.trace.idle_pct() if ctx.trace else None
