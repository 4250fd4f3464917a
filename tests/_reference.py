"""The plain references beside the benchmark's configurations
(``benchmarks/chip/configs/<name>.reference.py``), loaded read-only, and
the program's configs at small widths whose weights they make.

A reference is plain float32 ``jax.numpy`` at the highest matmul
precision and imports nothing of the program; it is the one oracle the
serving tests hold the program to: every served greedy token must be
the reference's first choice at its position (:func:`assert_greedy`)."""

import dataclasses
import functools
import importlib.util
import json
import pathlib

import jax
import numpy as np

from repro.configs import get_config
from repro.core.api import SparsityConfig

CHIP = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
#: A served token may lie this far below the reference's best logit (the
#: program and the reference round differently; a near-tie may go
#: either way).
GAP = 1e-4


@functools.cache
def reference(name):
    """The plain reference of configuration ``name``
    (``configs/<name>.reference.py``), loaded once."""
    path = CHIP / "configs" / f"{name}.reference.py"
    spec = importlib.util.spec_from_file_location(
        "ref_" + name.replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_dict(name):
    """The ``model`` dict of the config file ``configs/<name>.json``."""
    return json.loads((CHIP / "configs" / f"{name}.json").read_text())[
        "model"]


def program_config(program: str, m):
    """The program's config ``program`` with every size of ``m``."""
    return dataclasses.replace(
        get_config(program),
        **{k: (tuple(v) if k == "block_pattern" else
               SparsityConfig(**v) if k == "ffn_sparsity" else v)
           for k, v in m.items()}, remat=False)


#: Small widths of smollm-360m, float32 compute; the FFN sparsity is the
#: config file's (n 4, k-WTA 1/8 by bisection).  Three query heads over
#: one KV head, padded to four, as the published 15 are to 16.
SMOLLM_SMALL = dict(n_layers=2, d_model=64, n_heads=3, n_kv_heads=1,
                    d_head=16, head_pad=4, d_ff=256, vocab_size=256,
                    compute_dtype="float32", kv_cache_dtype="float32")


def smollm_small(seed: int = 0, **over):
    """(model dict, program config, reference weights) of smollm-360m at
    small widths."""
    m = dict(model_dict("smollm-360m"), **dict(SMOLLM_SMALL, **over))
    cfg = program_config("smollm_360m", m)
    return m, cfg, reference("smollm-360m").make_weights(
        jax.random.PRNGKey(seed), m)


#: Small widths of deepseek-v2-lite; 1 dense layer and 2 expert layers
#: holding experts [3, 5) of 8, 2 a token; float32 compute so that the
#: program meets the reference to rounding.
DEEPSEEK_SMALL = dict(n_layers=3, n_dense_layers=1, d_model=64, n_heads=4,
                      n_kv_heads=4, d_head=16, d_ff=32, dense_d_ff=96,
                      vocab_size=256, n_experts=8, held_experts=2,
                      held_expert_start=3, experts_per_token=2,
                      n_shared_experts=2, kv_lora_rank=32, rope_head_dim=8,
                      compute_dtype="float32", kv_cache_dtype="float32")


def deepseek_small(seed: int = 7, **over):
    """(model dict, program config, reference weights) of
    deepseek-v2-lite at small widths."""
    m = dict(model_dict("deepseek-v2-lite"), **dict(DEEPSEEK_SMALL, **over))
    cfg = program_config("deepseek_v2_lite_16b", m)
    return m, cfg, reference("deepseek-v2-lite").make_weights(
        jax.random.PRNGKey(seed), m)


def assert_greedy(name, params, m, requests, outputs, length: int):
    """Every greedy token served for ``requests`` (``outputs``: uid ->
    tokens) is the first choice of configuration ``name``'s reference at
    its position, to within :data:`GAP`; sequences are padded to
    ``length`` so that they share one compiled reference."""
    ref = reference(name)
    for r in requests:
        gaps = ref.served_gaps(params, m, r.prompt, outputs[r.uid], length)
        assert np.all(gaps < GAP), (r.uid, gaps)
