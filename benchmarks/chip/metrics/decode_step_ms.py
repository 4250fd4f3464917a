"""Model step: median of the program's ``serve.decode_step_s`` over the
window (host clock around the step and its logits' fetch)."""

import numpy as np


def read(ctx):
    steps = ctx.record.data.get("decode_step_s")
    if not steps:
        return None
    return 1e3 * float(np.median(steps))
