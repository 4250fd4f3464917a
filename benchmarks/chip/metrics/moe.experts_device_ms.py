"""Expert layer: device time per decode step of the ops traced under the
``moe.experts`` scope (the held experts' FFNs and their combine; the
router and the shared experts are outside it), from the ops' name stack
in the trace (``chipbench.scopes``)."""


def read(ctx):
    return ctx.record.data.get("scope_ms", {}).get("moe.experts")
