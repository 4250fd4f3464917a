"""Device: share of the traced stretch in which the chip sat idle while
the host was in none of the program's spans (``chipbench.idle_split``)."""

from chipbench import idle_split


def read(ctx):
    return idle_split.share(ctx, "no_span")
