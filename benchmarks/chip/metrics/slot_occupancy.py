"""Scheduler: share of the decode batch's slots that emitted a token,
over every decode step of the window (``Engine.serve`` stats)."""


def read(ctx):
    d = ctx.record.data
    if not d.get("decode_steps"):
        return None
    return 100.0 * d["decode_tokens"] / (d["decode_steps"] * d["n_slots"])
