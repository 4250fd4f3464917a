"""Attention: device time per decode step of the ops traced under the
``mla.attn`` scope (the absorbed latent attention over the paged cache,
from the latent query to the up-projected mix; the projections into and
out of it are outside), from the ops' name stack in the trace
(``chipbench.scopes``)."""


def read(ctx):
    return ctx.record.data.get("scope_ms", {}).get("mla.attn")
