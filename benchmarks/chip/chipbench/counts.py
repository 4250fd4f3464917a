"""Operations and bytes that a configuration's work needs at the least.

Counted from shapes and from the tokens or utterances processed, never
from XLA's cost analysis of the compiled program.  Each count is the
least that any implementation of the configured model must do: packed
weights at 1/N of dense, K winners of D after k-WTA, weights and routes
read once in their stored dtypes.  A widening or a second copy that an
implementation makes along the way is waste and is not counted, so a
later implementation that removes it shows as a gain and none can read
above 100% of a roofline.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterable, Tuple

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1,
               "int32": 4}


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def seconds_at(self, flops_per_s: float, bytes_per_s: float) -> float:
        """The least time a chip with these peaks could take."""
        return max(self.flops / flops_per_s, self.bytes / bytes_per_s)


def topk_gather(b: int, k: int, p: int, g: int, n: int, route_groups: int,
                weight_bytes: int, route_bytes: int, value_bytes: int,
                index_bytes: int, out_bytes: int) -> Work:
    """One sparse-sparse contraction of a batch of ``b`` k-sparse rows
    against packed weights ``(G, P, N)`` with routes ``(Gr, P, N)``.

    Each of the ``b·k`` non-zeros meets exactly one weight of each of
    the ``G`` groups: ``2·b·k·G`` operations.  Bytes are counted at the
    least the batch must touch whatever its supports: ``k`` distinct
    weight rows of ``G`` weights (the batch's supports may coincide,
    so only ``k`` are certain), the routes of the ``ceil(k/N)``
    partitions those rows lie in, the support itself (a value and an
    index per non-zero) and the ``b·G·N`` outputs.
    """
    if not 1 <= k <= p * n:
        raise ValueError(f"k={k} outside [1, P·N={p * n}]")
    flops = 2 * b * k * g
    weights = k * g * weight_bytes
    routes = math.ceil(k / n) * route_groups * n * route_bytes
    support = b * k * (value_bytes + index_bytes)
    out = b * g * n * out_bytes
    return Work(flops, weights + routes + support + out)


def lm_layer_flops(cfg: Dict, context: int) -> float:
    """Operations of one decoder layer for one token that attends to
    ``context`` positions (itself included): dense attention
    projections, attention over the context, the gated sparse FFN with
    weights at 1/N and the down projection's input at K of d_ff."""
    d, h, hkv = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    dh, d_ff = cfg["d_head"], cfg["d_ff"]
    sp = cfg["ffn_sparsity"]
    n = sp["n"]
    k = k_for(d_ff, sp["k_frac"])
    proj = 2 * d * h * dh + 2 * 2 * d * hkv * dh + 2 * h * dh * d
    attn = 2 * 2 * context * h * dh
    ffn = 2 * (2 * d * d_ff / n) + 2 * k * d / n
    return proj + attn + ffn


def k_for(dim: int, k_frac: float) -> int:
    """Winners of k-WTA over ``dim`` features at keep-fraction
    ``k_frac`` (rounded, at least 1)."""
    return min(dim, max(1, int(round(dim * k_frac))))


def lm_request_flops(cfg: Dict, prompt_len: int, served: int) -> float:
    """Operations one served request needs: every prompt token through
    every layer, the head once for the first token, then ``served - 1``
    decode steps each through every layer and the head."""
    n_layers, d, vocab = cfg["n_layers"], cfg["d_model"], cfg["vocab_size"]
    head = 2 * d * vocab
    positions = prompt_len + max(0, served - 1)
    # sum over positions i of lm_layer_flops(cfg, i + 1): linear in i
    first, last = lm_layer_flops(cfg, 1), lm_layer_flops(cfg, positions)
    layers = n_layers * positions * (first + last) / 2
    return layers + head * served


def lm_window_flops(cfg: Dict, requests: Iterable[Tuple[int, int]]) -> float:
    """Operations of a window's served requests ``(prompt_len, served)``."""
    return sum(lm_request_flops(cfg, p, s) for p, s in requests)


def gsc_macs(cfg: Dict) -> Dict[str, float]:
    """Per-utterance multiply-accumulates of the paper's GSC network
    (Table 1, Fig. 1 accounting), dense and with both sparsities: the
    weight sparsity divides each layer by its pack factor, the
    activation sparsity of a layer's input by D/K."""
    c, hidden, n_cls = cfg["channels"], cfg["hidden"], cfg["n_classes"]
    hp = -(-hidden // cfg["linear_n"]) * cfg["linear_n"]
    dense = {"conv1": 28 * 28 * c * 25, "conv2": 10 * 10 * c * 25 * c,
             "linear": 1600 * hidden, "out": hidden * n_cls}
    w = {"conv1": cfg["conv1_n"], "conv2": cfg["conv2_n"],
         "linear": cfg["linear_n"], "out": 1}
    a = {"conv1": 1.0, "conv2": c / cfg["conv_k"],
         "linear": c / cfg["conv_k"], "out": hp / cfg["linear_k"]}
    return {"dense": sum(dense.values()),
            "sparse_sparse": sum(v / (w[k] * a[k]) for k, v in dense.items())}


def gsc_utterance_flops(cfg: Dict) -> float:
    return 2 * gsc_macs(cfg)["sparse_sparse"]
