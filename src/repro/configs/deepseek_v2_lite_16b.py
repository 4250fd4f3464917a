"""deepseek-v2-lite-16b [moe]: 27 layers, d_model 2048, 16 heads, vocab
102400; latent attention (MLA, no q-LoRA) with kv_lora_rank 512, nope/rope
head sizes 128/64 and value head 128, YaRN rope (factor 40 over 4096
positions, mscale 0.707 on both terms); layer 0 has a dense SwiGLU FFN of
width 10944, layers 1-26 route each token to 6 of 64 experts of width 1408
(softmax scores, greedy top-k, weights not renormalised) beside 2 shared
experts.  [arXiv:2405.04434;
hf:deepseek-ai/DeepSeek-V2-Lite/config.json]

Served as one chip's share of a four-chip host with 4-way expert
parallelism: the router keeps its 64 outputs, this chip holds experts
[0, 16) of every expert layer, and everything else is whole.

The paper's technique on every FFN (dense, shared and expert): weights
packed at 1/4, k-WTA keeping 1/8 of the hidden units.  Attention is dense.
"""

from repro.core.api import SparsityConfig
from .base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_head=128,
    d_ff=1408,
    vocab_size=102400,
    act="silu",
    norm_eps=1e-6,
    rope_theta=10000.0,
    n_experts=64,
    n_shared_experts=2,
    experts_per_token=6,
    norm_topk_prob=False,
    held_experts=16,
    n_dense_layers=1,
    dense_d_ff=10944,
    use_mla=True,
    kv_lora_rank=512,
    rope_head_dim=64,
    mla_latent_norm=True,
    yarn_factor=40.0,
    yarn_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    ffn_sparsity=SparsityConfig(n=4, k_frac=0.125, route_share=0,
                                kwta_impl="bisect"),
    block_pattern=("attn",),       # 26 scanned units of 1 layer
)
