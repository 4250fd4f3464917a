"""Mixture-of-Experts layer holding one chip's share of the experts.

MoE routing is itself coarse-grained activation sparsity: the router is a
learned top-k over expert units, as k-WTA is a top-k over neurons.
Complementary sparsity composes inside each expert's FFN (packed weights,
k-WTA on the hidden units), so the two sparsities act at two
granularities.

Under expert parallelism each chip holds a contiguous range of the routed
experts (``cfg.held_expert_start``, ``cfg.n_held_experts``).  The router
keeps its full width: every token is scored against all ``n_experts`` and
takes its top ``experts_per_token`` of them.  This chip then computes the
contributions of its own experts, for every (token, held expert) pair
routed, and never drops one: each held expert runs on every token of the
call and its output is weighted by the token's router weight for it,
which is zero where the pair was not routed.  At a decode batch or a
prefill chunk (tens of rows) that costs no more than a dispatch buffer
with room for every token, and needs no sort, scatter or capacity.  The
shared experts run on every chip.  What experts held elsewhere add is
left out here; no code stands in for the absent chips or their exchange.

Expert projections go through ``packed_linear_apply`` (vmapped over the
held experts), so they take the same path rule as the dense FFN.  One
route table per projection serves every expert: the gather of the shared
input runs once, not once per expert.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.api import SparsityConfig
from repro.core.layers import apply_kwta, packed_linear_apply
from .common import normal_init
from .ffn import ffn_apply, ffn_init


def moe_init(key, d_model: int, d_ff: int, n_experts: int,
             n_shared: int, act: str, cfg_sp: SparsityConfig,
             n_held: int = 0):
    """Router ``(d_model, n_experts)``, the ``n_held`` held experts'
    stacked SwiGLU weights (``n_held`` 0: all ``n_experts``) and the
    shared experts as one FFN of width ``n_shared * d_ff``.

    When cfg_sp.weight_sparse, expert weights are stored packed:
    (E, G, P, N) with one route table per projection shared across
    experts (per-expert connectivity diversity comes from the weights).
    """
    n_held = n_held or n_experts
    ks = jax.random.split(key, 5)
    params, specs = {}, {}
    params["router"] = normal_init(ks[0], (d_model, n_experts), 0.02)
    specs["router"] = (None, None)

    def mk_expert(key, d_in, d_out, seed):
        if cfg_sp.weight_sparse and d_in % cfg_sp.n == 0 and d_out % cfg_sp.n == 0:
            from repro.core.masks import CSLayout, make_routes
            lay = CSLayout(d_in, d_out, cfg_sp.n, cfg_sp.perm_kind)
            g = lay.groups
            r = g if cfg_sp.route_share == 0 else min(cfg_sp.route_share, g)
            while g % r:
                r -= 1
            route = make_routes(
                CSLayout(d_in, cfg_sp.n * (g // r), cfg_sp.n,
                         cfg_sp.perm_kind), seed)
            scale = np.sqrt(cfg_sp.n / d_in)
            w = jax.random.uniform(key, (n_held, g, lay.partitions, cfg_sp.n),
                                   jnp.float32, -scale, scale)
            return ({"packed": w, "route": jnp.asarray(route)},
                    {"packed": ("experts", "mlp", None, None),
                     "route": ("mlp", None, None)})
        scale = 1.0 / np.sqrt(d_in)
        w = jax.random.uniform(key, (n_held, d_in, d_out), jnp.float32,
                               -scale, scale)
        return {"w": w}, {"w": ("experts", None, "mlp" if d_out == d_ff else None)}

    params["up"], specs["up"] = mk_expert(ks[1], d_model, d_ff, 31)
    if act == "silu":
        params["gate"], specs["gate"] = mk_expert(ks[2], d_model, d_ff, 32)
    params["down"], specs["down"] = mk_expert(ks[3], d_ff, d_model, 33)
    if n_shared:
        params["shared"], specs["shared"] = ffn_init(
            ks[4], d_model, n_shared * d_ff, cfg_sp, act)
    return params, specs


def _experts_matmul(p, x, sp: SparsityConfig, x_is_sparse: bool = False):
    """Every held expert's projection.  x: (T, d_in), shared by all
    experts, or (E, T, d_in), one input per expert.  Returns
    (E, T, d_out)."""
    if "packed" not in p:
        eq = "td,edf->etf" if x.ndim == 2 else "etd,edf->etf"
        return jnp.einsum(eq, x, p["w"].astype(x.dtype))

    def one(packed, xe):
        return packed_linear_apply({"packed": packed, "route": p["route"]},
                                   xe, sp, x_is_sparse=x_is_sparse)

    return jax.vmap(one, in_axes=(0, None if x.ndim == 2 else 0))(
        p["packed"], x)


def route(router, x, cfg):
    """Top-k routing over all ``n_experts``.  x: (T, d).  Returns the
    router probabilities (T, E) and the top-k weights and expert ids
    (T, k).  Scores are taken in float32 at full precision, as
    published: a rounding of the logits would change which experts a
    near-tie picks."""
    logits = jnp.matmul(x.astype(jnp.float32), router.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = lax.top_k(probs, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return probs, top_p, top_e


def moe_apply(params, x, cfg, cfg_sp: SparsityConfig
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (..., D).  Returns (y, aux_loss, held) where ``held`` (...,)
    counts each token's routed pairs that went to an expert held here."""
    lead, d = x.shape[:-1], x.shape[-1]
    e, k = cfg.n_experts, cfg.experts_per_token
    lo, n_held = cfg.held_expert_start, cfg.n_held_experts
    xt = x.reshape(-1, d)
    t = xt.shape[0]
    with jax.named_scope("moe.route"):
        probs, top_p, top_e = route(params["router"], xt, cfg)
        # Load-balancing auxiliary loss (Switch-style, over all experts).
        me = probs.mean(axis=0)
        ce = jnp.zeros((e,), jnp.float32).at[top_e.reshape(-1)].add(
            1.0 / (t * k))
        aux = e * jnp.sum(me * ce)
        # (E_held, T) weight of each held expert for each token: its
        # router weight where the pair was routed, else zero.
        held_ids = lo + jnp.arange(n_held, dtype=top_e.dtype)
        hit = top_e[None, :, :] == held_ids[:, None, None]   # (E, T, k)
        gate_w = jnp.sum(jnp.where(hit, top_p[None], 0.0), axis=-1)
        held = jnp.sum(hit, axis=(0, 2)).astype(jnp.int32)

    with jax.named_scope("moe.experts"):
        up = _experts_matmul(params["up"], xt, cfg_sp)       # (E, T, ff)
        if "gate" in params:
            h = jax.nn.silu(_experts_matmul(params["gate"], xt, cfg_sp)) * up
        else:
            h = jax.nn.gelu(up)
        h = apply_kwta(h, cfg_sp)
        out = _experts_matmul(params["down"], h, cfg_sp,
                              x_is_sparse=cfg_sp.activation_sparse)
        y = jnp.einsum("et,etd->td", gate_w.astype(out.dtype), out)

    if "shared" in params:
        with jax.named_scope("moe.shared"):
            y = y + ffn_apply(params["shared"], xt, cfg_sp, "silu")
    return y.reshape(*lead, d), aux, held.reshape(lead)
