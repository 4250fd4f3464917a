"""Plain reference of DeepSeek-V2-Lite with the paper's sparse FFNs, one
chip's share of its experts, and the weights both it and the program
serve.

A decoder of ``n_layers`` pre-norm blocks (RMSNorm at ``norm_eps``):

* latent attention: ``q = x Wq`` split per head into a 128-wide part
  without position and a 64-wide rope part; the latent
  ``c = RMSNorm(x Wdkv)`` (512 wide) and one shared rope key
  ``k_pe = rope(x Wkpe)``; per head ``k = [c Wuk, k_pe]`` and
  ``v = c Wuv``; causal softmax at ``192^-1/2 · mscale²`` with
  ``mscale = 0.1 · mscale_all_dim · ln(factor) + 1``; output ``Wo``.
  Rope is YaRN's (DeepSeek-V2's frequencies: ``theta``'s for the fast
  dimensions, divided by ``factor`` for the slow ones, a linear ramp
  between the dimensions that turn ``beta_fast`` and ``beta_slow`` times
  over the original context), rotating the two halves of the rope
  columns against each other.  DeepSeek-V2 stores those columns
  interleaved; under random weights that is a fixed permutation of the
  ``q`` and ``kpe`` weights' rope columns, and the program uses halves
  too.
* then, in the first ``n_dense_layers`` blocks, one gated FFN
  ``down(kWTA(silu(gate x) * up x))`` of width ``dense_d_ff``; in the
  others the expert layer: router scores ``softmax(x Wr)`` over all
  ``n_experts``, each token takes its top ``experts_per_token`` (weights
  renormalised only when ``norm_topk_prob``), and the experts held here,
  ``[held_expert_start, held_expert_start + held_experts)``, add their
  gated FFN of width ``d_ff`` times the token's weight for them; the
  experts held elsewhere add nothing.  Two shared experts form one gated
  FFN of width ``n_shared_experts · d_ff``, added for every token.

Every FFN projection is complementary-sparse (each output keeps one
weight in every partition of N inputs) and every k-WTA keeps the
activations at or above a threshold found by 16 rounds of bisection on
the value axis (at least K of the width).  Final RMSNorm and an untied
output head.

Written from that description in plain ``jax.numpy`` at float32 with the
highest matmul precision, one sequence at a time, with no cache, no
batching and no kernel; it imports nothing of the program.  The packed
weights are expanded to dense masked matrices here, one layer at a time,
and each held expert runs on every token (weighted by zero where it was
not routed).

:func:`make_weights` builds the parameters from a key in the layout the
program serves (the leading dense layers and the expert layers each
stacked on a layer axis), in one jitted call on the device.
"""

from __future__ import annotations

import functools
import json
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

BISECT_ROUNDS = 16
EMBED_STD = 0.02
#: Router weights are uniform in ±ROUTER_GAIN·d^-1/2: sharper scores than
#: a fan-in scale, as a trained router's are, so that a token's sixth and
#: seventh experts are seldom within rounding of each other.
ROUTER_GAIN = 3.0
FP8_MAX = 448.0


def _k(width: int, m: Dict) -> int:
    return min(width, max(1, int(round(width * m["ffn_sparsity"]["k_frac"]))))


def make_weights(key, m: Dict):
    """Parameters of the model ``m`` (the config file's ``model``) in the
    program's layout: latent-attention matrices as plain arrays, packed
    FFN projections ``{"packed": (G, P, N), "route": (1, P, N) int8}``
    (one route table per projection) with ``route[0, p, :]`` a
    permutation of ``range(N)``, the held experts' packed weights stacked
    ``(E_held, G, P, N)`` beside one route table per projection, norm
    scales, the embedding and the head."""
    d, h, dh = m["d_model"], m["n_heads"], m["d_head"]
    r, dr = m["kv_lora_rank"], m["rope_head_dim"]
    n = m["ffn_sparsity"]["n"]
    vocab = m["vocab_size"]
    held = m["held_experts"] or m["n_experts"]
    # The device's own generator, seeded from ``key``: threefry takes
    # seconds on the chip for 1.8 G numbers, and the run draws them twice
    # (for the program and for the check).
    key = jax.random.wrap_key_data(
        jax.random.bits(key, (4,), jnp.uint32), impl="rbg")
    keys = iter(jax.random.split(key, 128))

    def uni(shape, scale):
        return jax.random.uniform(next(keys), shape, jnp.float32, -scale,
                                  scale)

    def norm(shape):
        return jax.random.uniform(next(keys), shape, jnp.float32, 0.8, 1.2)

    def packed(lead, d_in, d_out):
        g, p = d_out // n, d_in // n
        route = jnp.argsort(jax.random.uniform(next(keys),
                                               (lead[0], 1, p, n)),
                            axis=-1).astype(jnp.int8)
        return {"packed": uni((*lead, g, p, n), float(np.sqrt(n / d_in))),
                "route": route}

    def ffn(lead, width):
        return {"up": packed(lead, d, width), "gate": packed(lead, d, width),
                "down": packed(lead, width, d)}

    def block(layers, dense):
        mixer = {"q": uni((layers, d, h * (dh + dr)), d ** -0.5),
                 "dkv": uni((layers, d, r), d ** -0.5),
                 "kpe": uni((layers, d, dr), d ** -0.5),
                 "uk": uni((layers, r, h * dh), r ** -0.5),
                 "uv": uni((layers, r, h * dh), r ** -0.5),
                 "o": uni((layers, h * dh, d), (h * dh) ** -0.5),
                 "kv_norm": {"scale": norm((layers, r))}}
        out = {"norm1": {"scale": norm((layers, d))}, "mixer": mixer,
               "norm2": {"scale": norm((layers, d))}}
        if dense:
            out["ffn"] = ffn((layers,), m["dense_d_ff"])
        else:
            out["moe"] = {
                "router": uni((layers, d, m["n_experts"]),
                              ROUTER_GAIN * d ** -0.5),
                **ffn((layers, held), m["d_ff"]),
                "shared": ffn((layers,), m["n_shared_experts"] * m["d_ff"])}
        return out

    n_lead = m["n_dense_layers"]
    return {
        "embed": {"table": EMBED_STD * jax.random.normal(next(keys),
                                                         (vocab, d))},
        "lead": block(n_lead, dense=True),
        "units": {"b0": block(m["n_layers"] - n_lead, dense=False)},
        "final_norm": {"scale": norm((d,))},
        "head": {"table": uni((vocab, d), d ** -0.5)},
    }


def unpack(packed, route):
    """Dense ``(P·N, G·N)`` weight of a packed layer: output ``g·N + s``
    keeps input ``p·N + route[u, p, s]`` with the weight
    ``packed[g, p, s]``, where ``u`` is the route group of ``g``.  Built
    as ``(P, N, G·N)``, input offset by output column, so that no
    intermediate has a trailing axis of N."""
    g, p, n = packed.shape
    gr = route.shape[0]
    cols = np.arange(g * n)
    w = packed.astype(jnp.float32).transpose(1, 0, 2).reshape(p, g * n)
    rc = route.transpose(1, 0, 2)[:, cols // n // (g // gr), cols % n]
    hit = rc[:, None, :] == jnp.arange(n, dtype=route.dtype)[None, :, None]
    return jnp.where(hit, w[:, None, :], 0.0).reshape(p * n, g * n)


def fp8(x):
    """Round to float8 e4m3 with one scale for the tensor (its largest
    magnitude lands on the format's largest value)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_freqs(m: Dict) -> np.ndarray:
    dim, base = m["rope_head_dim"], m["rope_theta"]
    factor, orig = m["yarn_factor"], m["yarn_original_max_pos"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    lo = max(math.floor(corr(m["yarn_beta_fast"])), 0)
    hi = min(math.ceil(corr(m["yarn_beta_slow"])), dim - 1)
    if lo == hi:
        hi += 0.001
    plain = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    return (plain / factor * ramp + plain * (1.0 - ramp)).astype(np.float32)


def _rope(x, m: Dict):
    """Rope over the positions ``0..S-1`` of ``x (S, ..., dr)``."""
    dr = x.shape[-1]
    cs = (_mscale(m["yarn_factor"], m["yarn_mscale"])
          / _mscale(m["yarn_factor"], m["yarn_mscale_all_dim"]))
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
           * jnp.asarray(_yarn_freqs(m)))
    ang = ang.reshape(x.shape[0], *([1] * (x.ndim - 2)), dr // 2)
    cos, sin = jnp.cos(ang) * cs, jnp.sin(ang) * cs
    x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


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


def gated(x, f, m: Dict, mm):
    """The gated sparse FFN ``down(kWTA(silu(gate x) * up x))`` of the
    packed projections ``f``, with matrix product ``mm``."""
    act = (jax.nn.silu(mm(x, unpack(**f["gate"])))
           * mm(x, unpack(**f["up"])))
    return mm(_kwta_bisect(act, _k(act.shape[-1], m)), unpack(**f["down"]))


def experts(x, p, m: Dict, mm):
    """The expert layer of ``x (S, d)``: the held experts' part for the
    tokens routed to them, plus the shared experts."""
    lo, top = m["held_expert_start"], m["experts_per_token"]
    probs = jax.nn.softmax(mm(x, p["router"]), -1)
    top_p, top_e = jax.lax.top_k(probs, top)
    if m["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    y = gated(x, p["shared"], m, mm) if "shared" in p else 0.0
    for j in range(m["held_experts"] or m["n_experts"]):
        w = jnp.sum(jnp.where(top_e == lo + j, top_p, 0.0), -1)
        f = {name: {"packed": p[name]["packed"][j], "route": p[name]["route"]}
             for name in ("up", "gate", "down")}
        y = y + w[:, None] * gated(x, f, m, mm)
    return y


def logits(params, tokens, m: Dict, low: bool = False):
    """Next-token logits ``(S, vocab)`` of one sequence ``tokens (S,)``;
    ``low`` rounds every matmul operand to float8 (the control)."""
    d, h, dh = m["d_model"], m["n_heads"], m["d_head"]
    dr, eps = m["rope_head_dim"], m["norm_eps"]
    rnd = fp8 if low else (lambda a: a)
    hi = jax.lax.Precision.HIGHEST
    scale = (dh + dr) ** -0.5 * _mscale(m["yarn_factor"],
                                        m["yarn_mscale_all_dim"]) ** 2

    def mm(a, b):
        return jnp.matmul(rnd(a), rnd(b), precision=hi)

    s = tokens.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def attention(x, p):
        q = mm(x, p["q"]).reshape(s, h, dh + dr)
        q_nope, q_pe = q[..., :dh], _rope(q[..., dh:], m)
        c = _rms(mm(x, p["dkv"]), p["kv_norm"]["scale"], eps)
        k_pe = _rope(mm(x, p["kpe"]), m)
        k_nope = mm(c, p["uk"]).reshape(s, h, dh)
        v = mm(c, p["uv"]).reshape(s, h, dh)
        scores = (jnp.einsum("qhd,khd->hqk", rnd(q_nope), rnd(k_nope),
                             precision=hi)
                  + jnp.einsum("qhd,kd->hqk", rnd(q_pe), rnd(k_pe),
                               precision=hi)) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        att = jnp.einsum("hqk,khd->qhd", rnd(probs), rnd(v),
                         precision=hi).reshape(s, h * dh)
        return mm(att, p["o"])

    def layer(x, p):
        x = x + attention(_rms(x, p["norm1"]["scale"], eps), p["mixer"])
        a = _rms(x, p["norm2"]["scale"], eps)
        ffn = (gated(a, p["ffn"], m, mm) if "ffn" in p
               else experts(a, p["moe"], m, mm))
        return x + ffn, None

    x = params["embed"]["table"][tokens]
    x, _ = jax.lax.scan(layer, x, params["lead"])
    x, _ = jax.lax.scan(layer, x, params["units"]["b0"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    return mm(x, params["head"]["table"].T)


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
