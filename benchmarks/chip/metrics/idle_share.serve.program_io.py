"""Model step: share of the traced stretch in which the chip sat idle
while the host prepared a device program's inputs, enqueued it or
fetched its results (``decode.*``, ``prefill.*``, ``kv.cow`` spans;
``chipbench.idle_split``)."""

from chipbench import idle_split


def read(ctx):
    return idle_split.share(ctx, "program_io")
