"""The general traffic generator: reads a traffic file's parameters.

Every seed gets the same sizes: they come from the file alone
(``layout_seed``), and the seed draws only the token ids, the inputs and
the order in which a pool of inputs is sent.  So two seeds ask for the
same work and differ in its content.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import numpy as np


def seed_words(seed: int, *salt: int) -> np.random.SeedSequence:
    """A seed sequence from ``seed`` (any size of whole number) and
    optional salts; negative seeds are folded into the non-negative
    range the sequence accepts."""
    return np.random.SeedSequence([abs(int(seed)), int(seed < 0), *salt])


def log_uniform_grid(lo: int, hi: int, count: int,
                     rng: np.random.Generator) -> np.ndarray:
    """``count`` whole numbers in [lo, hi], log-uniform: one draw in
    each of ``count`` equal slices of [log lo, log hi]."""
    if not 1 <= lo <= hi:
        raise ValueError(f"length range [{lo}, {hi}] must satisfy "
                         "1 <= lo <= hi")
    u = (np.arange(count) + rng.random(count)) / count
    vals = np.exp(np.log(lo) + u * (np.log(hi + 1) - np.log(lo)))
    return np.clip(np.floor(vals), lo, hi).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class LMRequest:
    uid: int
    prompt: List[int]
    max_new_tokens: int


class OfflineBatches:
    """Successive offline batches of ``requests_per_batch`` requests.

    The (prompt length, output length) pairs of a batch and their order
    are fixed by the traffic file, so that every batch of every seed
    asks for the same work and is scheduled alike; batch ``i`` of seed
    ``s`` draws its token ids from ``(s, i)``.
    """

    def __init__(self, traffic: Dict, vocab: int, seed: int):
        self.r = int(traffic["requests_per_batch"])
        layout = np.random.default_rng(int(traffic["layout_seed"]))
        prompts = log_uniform_grid(*traffic["prompt_len"], self.r, layout)
        outputs = log_uniform_grid(*traffic["output_len"], self.r, layout)
        self.pairs: List[Tuple[int, int]] = list(
            zip(prompts.tolist(), layout.permutation(outputs).tolist()))
        if max(p + o for p, o in self.pairs) > int(traffic["max_seq"]):
            raise ValueError("a request of the mix exceeds max_seq")
        self.vocab = int(vocab)
        self.seed = seed

    def batch(self, i: int) -> List[LMRequest]:
        rng = np.random.default_rng(seed_words(self.seed, 1, i))
        out = []
        for j, (p, o) in enumerate(self.pairs):
            out.append(LMRequest(uid=i * self.r + j,
                                 prompt=rng.integers(0, self.vocab,
                                                     p).tolist(),
                                 max_new_tokens=o))
        return out


class InputPool:
    """A pool of ``pool_batches`` input batches made from the seed at
    set-up, and the order in which calls send them."""

    def __init__(self, traffic: Dict, seed: int):
        rng = np.random.default_rng(seed_words(seed, 2))
        shape = (int(traffic["pool_batches"]), int(traffic["batch"]),
                 *traffic["input_shape"])
        self.batches = rng.standard_normal(shape, dtype=np.float32)
        self.order = rng.permutation(shape[0])

    def index(self, call: int) -> int:
        return int(self.order[call % len(self.order)])


def reservoir(rng: np.random.Generator, size: int):
    """Uniform sample of ``size`` items from a stream of unknown length
    (Algorithm R); ``offer(i, item)`` for each item in order."""
    kept: List = []

    def offer(i: int, item) -> None:
        if i < size:
            kept.append(item)
            return
        j = int(rng.integers(0, i + 1))
        if j < size:
            kept[j] = item

    return kept, offer


def sample_ids(ids: Sequence[int], size: int, rng: np.random.Generator,
               must: Sequence[int] = ()) -> List[int]:
    """``size`` ids drawn from ``rng``, holding every id of ``must``."""
    rest = [i for i in ids if i not in set(must)]
    extra = max(0, size - len(must))
    picked = list(rng.choice(rest, size=min(extra, len(rest)),
                             replace=False)) if rest and extra else []
    return list(must) + [int(i) for i in picked]
