"""Operations that a model with latent attention and experts needs at the
least, for the ``mfu.serve`` of its cells (DeepSeek-V2-Lite).

Counted as ``chipbench.counts`` counts the dense models: from shapes and
the tokens processed, packed weights at 1/N of dense, K winners of the
FFN width after k-WTA.  Per layer and token:

* latent attention: the dense projections (``q``, the latent, the rope
  key, the output); per head the key and value up-projections of the
  token's latent row, or in the absorbed form the query's and the
  output's (the same count either way); then per position of the
  context the scores and the mix.  A prompt token attends at the least
  of the two forms, the expanded one (``nope + rope`` wide scores, a
  ``v`` wide mix); a decoded token reads the latent cache, so it
  attends in the absorbed form (``rank + rope`` wide scores, a ``rank``
  wide mix).
* FFN: the leading dense layers' gated FFN of their own width; in the
  others the router, the shared experts' FFN and the routed experts'
  at ``experts_per_token · held / routed`` FFNs a token, the share of
  the routed work that the held experts do.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

from .counts import k_for


def sparse_ffn(d: int, width: int, sp: Dict) -> float:
    """A gated FFN with packed weights and the down projection's input
    at K of ``width``."""
    n = sp["n"]
    return 2 * (2 * d * width / n) + 2 * k_for(width, sp["k_frac"]) * d / n


def attention_fixed(m: Dict) -> float:
    """Latent attention's operations that do not grow with the context."""
    d, h, dh = m["d_model"], m["n_heads"], m["d_head"]
    r, dr = m["kv_lora_rank"], m["rope_head_dim"]
    proj = 2 * d * h * (dh + dr) + 2 * d * (r + dr) + 2 * h * dh * d
    return proj + 2 * (2 * r * h * dh)


def attention_per_position(m: Dict, absorbed: bool) -> float:
    """Latent attention's operations per position of the context."""
    h, dh = m["n_heads"], m["d_head"]
    r, dr = m["kv_lora_rank"], m["rope_head_dim"]
    if absorbed:
        return 2 * h * (r + dr) + 2 * h * r
    return 2 * h * (dh + dr) + 2 * h * dh


def ffn_per_token(m: Dict) -> float:
    """FFN operations of one token through every layer."""
    d, sp = m["d_model"], m["ffn_sparsity"]
    lead = m["n_dense_layers"]
    held = m["held_experts"] or m["n_experts"]
    routed = (m["experts_per_token"] * held / m["n_experts"]
              * sparse_ffn(d, m["d_ff"], sp))
    expert_layer = (2 * d * m["n_experts"] + routed
                    + sparse_ffn(d, m["n_shared_experts"] * m["d_ff"], sp))
    return (lead * sparse_ffn(d, m["dense_d_ff"], sp)
            + (m["n_layers"] - lead) * expert_layer)


def _positions(first: int, last: int) -> Tuple[int, float]:
    """How many positions lie in [first, last] and their sum."""
    count = max(0, last - first + 1)
    return count, count * (first + last) / 2


def lm_request_flops(m: Dict, prompt_len: int, served: int) -> float:
    """Operations one served request needs: every prompt token through
    every layer (attending to itself and the tokens before it), the head
    once for the first token, then ``served - 1`` decode steps each
    through every layer and the head."""
    layers, head = m["n_layers"], 2 * m["d_model"] * m["vocab_size"]
    per_token = layers * attention_fixed(m) + ffn_per_token(m)
    n_pre, ctx_pre = _positions(1, prompt_len)
    n_dec, ctx_dec = _positions(prompt_len + 1, prompt_len + served - 1)
    attend = layers * (ctx_pre * attention_per_position(m, absorbed=False)
                       + ctx_dec * attention_per_position(m, absorbed=True))
    return (n_pre + n_dec) * per_token + attend + head * served


def lm_window_flops(m: Dict, requests: Iterable[Tuple[int, int]]) -> float:
    """Operations of a window's served requests ``(prompt_len, served)``."""
    return sum(lm_request_flops(m, p, s) for p, s in requests)
