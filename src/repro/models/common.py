"""Shared model components: norms, RoPE, embeddings, init and param-spec
conventions.

Params are nested dicts of arrays; every ``*_init`` returns ``(params,
specs)`` where ``specs`` mirrors the params tree with tuples of *logical*
axis names (resolved to mesh axes by repro.sharding).  Logical axes used:

  "embed"   — d_model            (replicated)
  "heads"   — attention heads    -> model axis
  "kv"      — kv heads           -> model axis if divisible else replicated
  "mlp"     — ffn hidden / CS group dim -> model axis
  "vocab"   — vocabulary         -> model axis
  "experts" — MoE experts        -> model axis (EP)
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding.context import constrain


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


def uniform_init(key, shape, scale, dtype=jnp.float32):
    return jax.random.uniform(key, shape, dtype, -scale, scale)


def normal_init(key, shape, std, dtype=jnp.float32):
    return std * jax.random.normal(key, shape, dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int):
    return {"scale": jnp.ones((d,), jnp.float32)}, {"scale": (None,)}


def rmsnorm_apply(params, x, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


def layernorm_init(d: int):
    return ({"scale": jnp.ones((d,), jnp.float32),
             "bias": jnp.zeros((d,), jnp.float32)},
            {"scale": (None,), "bias": (None,)})


def layernorm_apply(params, x, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"] + params["bias"]).astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope_freqs(d_head: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32)
                            / d_head))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term (DeepSeek-V2's
    ``yarn_get_mscale``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_freqs(d_head: int, theta: float, factor: float, original: int,
               beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN inverse frequencies, as DeepSeek-V2's YaRN rotary embedding
    computes them: the fast-rotating dimensions keep ``theta``'s
    frequencies, the slow ones are divided by ``factor``, with a linear
    ramp between the dimensions that turn ``beta_fast`` and ``beta_slow``
    times over the ``original`` context."""
    def dim_of(rot):
        return (d_head * math.log(original / (rot * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(dim_of(beta_fast)), 0)
    hi = min(math.ceil(dim_of(beta_slow)), d_head - 1)
    if lo == hi:
        hi += 0.001
    extra = 1.0 / theta ** (np.arange(0, d_head, 2, dtype=np.float64)
                            / d_head)
    ramp = np.clip((np.arange(d_head // 2) - lo) / (hi - lo), 0.0, 1.0)
    keep = 1.0 - ramp
    return (extra / factor * (1 - keep) + extra * keep).astype(np.float32)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               freqs=None, scale: float = 1.0) -> jax.Array:
    """x: (..., S, H, Dh) or (..., S, Dh); positions: (..., S).  Rotates
    the two halves of the last axis against each other; ``freqs``
    (Dh/2,) replaces the plain ``theta`` frequencies and ``scale``
    multiplies cos and sin (YaRN)."""
    d_head = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(d_head, theta)                # (Dh/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    if x.ndim == ang.ndim + 1:                           # head axis present
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------

def embedding_init(key, vocab: int, d: int):
    params = {"table": normal_init(key, (vocab, d), 0.02)}
    return params, {"table": ("vocab", "embed")}


def embedding_apply(params, tokens, compute_dtype):
    y = jnp.take(params["table"].astype(compute_dtype), tokens, axis=0)
    return constrain(y, "batch", "seq", None)


def lm_head_apply(params, x, compute_dtype):
    """Project to vocab logits; table may be tied (vocab, d)."""
    logits = x @ params["table"].astype(compute_dtype).T
    return constrain(logits, "batch", "seq", "vocab")


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token cross-entropy in fp32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        m = mask.astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)
