"""Whole step: operations the window's served requests need at the
least (``chipbench.counts.lm_window_flops``) per second of the window,
as a share of the chip's peak."""


def read(ctx):
    rec = ctx.record
    return 100.0 * rec.data["window_flops"] / rec.window_s \
        / ctx.peaks["flops_per_s"]
