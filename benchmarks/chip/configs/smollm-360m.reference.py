"""Plain reference of smollm-360m with the paper's sparse FFN, and the
weights both it and the program serve.

A decoder of ``n_layers`` pre-norm blocks: RMSNorm, grouped-query
attention with rotary positions (15 query heads over 5 key/value heads,
head size 64, theta 10000), RMSNorm, then the gated FFN
``down(kWTA(silu(gate x) * up x))`` whose three projections are
complementary-sparse (each output keeps one weight in every partition of
N inputs) and whose k-WTA keeps the activations at or above a threshold
found by 16 rounds of bisection on the value axis (at least K of d_ff).
Final RMSNorm and the output head, which is the embedding table itself
(tied, as in the source).

Written from that description in plain ``jax.numpy`` at float32 with
the highest matmul precision, one sequence at a time, with no cache, no
batching and no kernel; it imports nothing of the program.  The packed
weights are expanded to dense masked matrices here, one layer at a time.

:func:`make_weights` builds the parameters from a key in the layout the
program serves (leaves stacked over the ``n_layers / 2`` scanned units of
two blocks each), in one jitted call on the device.
"""

from __future__ import annotations

import functools
import json
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

BISECT_ROUNDS = 16
EMBED_STD = 0.002
FP8_MAX = 448.0


def _dims(m: Dict):
    sp = m["ffn_sparsity"]
    n = sp["n"]
    h_pad = -(-m["n_heads"] // m["head_pad"]) * m["head_pad"] \
        if m["head_pad"] else m["n_heads"]
    k = min(m["d_ff"], max(1, int(round(m["d_ff"] * sp["k_frac"]))))
    return n, h_pad, k


def _route_groups(groups: int, route_share: int) -> int:
    share = groups if route_share == 0 else min(route_share, groups)
    while groups % share:
        share -= 1
    return groups // share


def make_weights(key, m: Dict):
    """Parameters of the model ``m`` (the config file's ``model``) in
    the program's layout: dense attention projections ``{"w": (in,
    out)}``, packed FFN projections ``{"packed": (G, P, N), "route":
    (Gr, P, N) int8}`` with ``route[u, p, :]`` a permutation of
    ``range(N)``, norm scales and the embedding table, which is also
    the head.

    The table's scale is ``EMBED_STD``: at 0.02 the residual stream
    keeps so much of the input token's own row that the tied head puts
    each next token first by a wide margin, and the float8 control
    flipped no served token on some seeds of the long-prompt mix (TPU
    v5e), so the check could not tell it from the program."""
    d, h, hkv, dh = m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_head"]
    d_ff, vocab = m["d_ff"], m["vocab_size"]
    n, h_pad, _ = _dims(m)
    units = m["n_layers"] // len(m["block_pattern"])
    keys = iter(jax.random.split(key, 64))

    def uni(shape, scale):
        return jax.random.uniform(next(keys), (units, *shape), jnp.float32,
                                  -scale, scale)

    def norm(shape):
        return jax.random.uniform(next(keys), shape, jnp.float32, 0.8, 1.2)

    def packed(d_in, d_out):
        g, p = d_out // n, d_in // n
        gr = _route_groups(g, m["ffn_sparsity"]["route_share"])
        route = jnp.argsort(jax.random.uniform(next(keys), (units, gr, p, n)),
                            axis=-1).astype(jnp.int8)
        return {"packed": uni((g, p, n), float(np.sqrt(n / d_in))),
                "route": route}

    def block():
        return {
            "norm1": {"scale": norm((units, d))},
            "mixer": {"q": {"w": uni((d, h * dh), d ** -0.5)},
                      "k": {"w": uni((d, hkv * dh), d ** -0.5)},
                      "v": {"w": uni((d, hkv * dh), d ** -0.5)},
                      "o": {"w": uni((h_pad * dh, d), (h_pad * dh) ** -0.5)}},
            "norm2": {"scale": norm((units, d))},
            "ffn": {"up": packed(d, d_ff), "gate": packed(d, d_ff),
                    "down": packed(d_ff, d)},
        }

    return {
        "embed": {"table": EMBED_STD * jax.random.normal(next(keys),
                                                         (vocab, d))},
        "units": {f"b{i}": block() for i in range(len(m["block_pattern"]))},
        "final_norm": {"scale": norm((d,))},
    }


def unpack(packed, route):
    """Dense ``(P·N, G·N)`` weight of a packed layer: output ``g·N + s``
    keeps input ``p·N + route[u, p, s]`` with the weight
    ``packed[g, p, s]``, where ``u`` is the route group of ``g``."""
    g, p, n = packed.shape
    gr = route.shape[0]
    hit = route[:, :, :, None] == jnp.arange(n, dtype=route.dtype)
    hit = jnp.repeat(hit, g // gr, axis=0)               # (G, P, s, i)
    w = jnp.where(hit, packed.astype(jnp.float32)[..., None], 0.0)
    return w.transpose(1, 3, 0, 2).reshape(p * n, g * n)  # (p i, g s)


def fp8(x):
    """Round to float8 e4m3 with one scale for the tensor (its largest
    magnitude lands on the format's largest value)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    s, _, dh = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def _kwta_bisect(x, k):
    """Keep the entries at or above the largest of the bisection's
    thresholds that at least ``k`` entries reach (per row)."""
    def round_(_, bounds):
        lo, hi = bounds
        mid = 0.5 * (lo + hi)
        up = jnp.sum(x >= mid, -1, keepdims=True) >= k
        return jnp.where(up, mid, lo), jnp.where(up, hi, mid)

    lo, _ = jax.lax.fori_loop(0, BISECT_ROUNDS, round_,
                              (jnp.min(x, -1, keepdims=True),
                               jnp.max(x, -1, keepdims=True)))
    return jnp.where(x >= lo, x, 0.0)


def logits(params, tokens, m: Dict, low: bool = False):
    """Next-token logits ``(S, vocab)`` of one sequence ``tokens (S,)``;
    ``low`` rounds every matmul operand to float8 (the control)."""
    d, h, hkv, dh = m["d_model"], m["n_heads"], m["n_kv_heads"], m["d_head"]
    eps, theta = m["norm_eps"], m["rope_theta"]
    n, _, k = _dims(m)
    rnd = fp8 if low else (lambda a: a)
    hi = jax.lax.Precision.HIGHEST

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b), precision=hi)

    s = tokens.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def block(x, p):
        a = _rms(x, p["norm1"]["scale"], eps)
        q = _rope(mm(a, p["mixer"]["q"]["w"]).reshape(s, h, dh), theta)
        kk = _rope(mm(a, p["mixer"]["k"]["w"]).reshape(s, hkv, dh), theta)
        v = mm(a, p["mixer"]["v"]["w"]).reshape(s, hkv, dh)
        kk, v = (jnp.repeat(t, h // hkv, axis=1) for t in (kk, v))
        scores = jnp.einsum("qhd,khd->hqk", rnd(q), rnd(kk),
                            precision=hi) / np.sqrt(dh)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        att = jnp.einsum("hqk,khd->qhd", rnd(probs), rnd(v),
                         precision=hi).reshape(s, h * dh)
        x = x + mm(att, p["mixer"]["o"]["w"][:h * dh])
        a = _rms(x, p["norm2"]["scale"], eps)
        f = p["ffn"]
        act = (jax.nn.silu(mm(a, unpack(**f["gate"])))
               * mm(a, unpack(**f["up"])))
        return x + mm(_kwta_bisect(act, k), unpack(**f["down"]))

    def unit(x, up):
        for i in range(len(m["block_pattern"])):
            x = block(x, up[f"b{i}"])
        return x, None

    x = params["embed"]["table"][tokens]
    x, _ = jax.lax.scan(unit, x, params["units"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    return mm(x, params["embed"]["table"].T)


@functools.partial(jax.jit, static_argnames=("m_json",))
def _gaps(params, tokens, targets, m_json):
    ref = logits(params, tokens, json.loads(m_json))
    return jnp.max(ref, -1) - jnp.take_along_axis(ref, targets[:, None],
                                                  -1)[:, 0]


@functools.partial(jax.jit, static_argnames=("m_json",))
def _control_gaps(params, tokens, m_json):
    m = json.loads(m_json)
    ref = logits(params, tokens, m)
    first = jnp.argmax(logits(params, tokens, m, low=True), -1)
    return jnp.max(ref, -1) - jnp.take_along_axis(ref, first[:, None],
                                                  -1)[:, 0]


def served_gaps(params, m: Dict, prompt, served, length: int,
                control: bool = False) -> np.ndarray:
    """For each served token, how far its reference logit lies below the
    reference's best at its position; with ``control``, the same for
    the token that the float8 reference puts first.  The sequence is
    padded to ``length`` so that every request shares one program."""
    seq = list(prompt) + list(served)
    if len(seq) > length:
        raise ValueError(f"sequence of {len(seq)} exceeds {length}")
    tokens = np.zeros(length, np.int32)
    tokens[:len(seq)] = seq
    targets = np.zeros(length, np.int32)
    first = len(prompt) - 1
    targets[first:first + len(served)] = served
    m_json = json.dumps(m, sort_keys=True)
    if control:
        gap = _control_gaps(params, jnp.asarray(tokens), m_json)
    else:
        gap = _gaps(params, jnp.asarray(tokens), jnp.asarray(targets),
                    m_json)
    return np.asarray(gap)[first:first + len(served)]
