"""Whole step: sparse-sparse operations per utterance times the
utterances classified per second of the window, as a share of the
chip's peak."""


def read(ctx):
    d = ctx.record.data
    return 100.0 * d["utterance_flops"] * d["words"] / ctx.record.window_s \
        / ctx.peaks["flops_per_s"]
