"""Scheduler: share of the traced stretch in which the chip sat idle
while the host ran the serve loop's own work: sampling, admission, page
growth and tables, retirement, the loop between them, a call's set-up
and drain (``chipbench.idle_split``)."""

from chipbench import idle_split


def read(ctx):
    return idle_split.share(ctx, "host_loop")
