"""Block-level model tests: flash==materialized attention, SSD scan vs
naive recurrence, MoE dispatch conservation, MLA cache equivalence."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, st

from repro.configs import get_config
from repro.models import ssm as S
from repro.models.attention import _causal_attn, _flash_attn


def test_flash_equals_materialized():
    key = jax.random.PRNGKey(0)
    b, s, h, dh = 2, 64, 4, 16
    q, k, v = (jax.random.normal(kk, (b, s, h, dh))
               for kk in jax.random.split(key, 3))
    out_ref = _causal_attn(q, k, v, 0.25)
    for block in [8, 16, 32]:
        out = _flash_attn(q, k, v, 0.25, block)
        np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                                   atol=1e-4)


def test_flash_unroll_equals_scan():
    key = jax.random.PRNGKey(1)
    q, k, v = (jax.random.normal(kk, (1, 32, 2, 8))
               for kk in jax.random.split(key, 3))
    a = _flash_attn(q, k, v, 0.3, 8, unroll=False)
    b = _flash_attn(q, k, v, 0.3, 8, unroll=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def _ssd_naive(q, k, v, log_a):
    """O(T) reference recurrence for the chunked SSD scan."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    S_ = np.zeros((b, h, dk, dv), np.float32)
    ys = []
    for i in range(t):
        a = np.exp(np.asarray(log_a[:, i], np.float32))[:, :, None, None]
        S_ = a * S_ + np.einsum("bhd,bhe->bhde", np.asarray(k[:, i]),
                                np.asarray(v[:, i]))
        ys.append(np.einsum("bhd,bhde->bhe", np.asarray(q[:, i]), S_))
    return np.stack(ys, axis=1), S_


@given(st.sampled_from([4, 8, 16]), st.integers(0, 20))
@settings(max_examples=10, deadline=None)
def test_ssd_scan_matches_recurrence(chunk, seed):
    key = jax.random.PRNGKey(seed)
    b, t, h, dk, dv = 2, 32, 2, 4, 6
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, t, h, dk))
    k = jax.random.normal(ks[1], (b, t, h, dk))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    log_a = -jnp.abs(jax.random.normal(ks[3], (b, t, h))) * 0.1
    y, S_fin = S.ssd_scan(q, k, v, log_a, chunk)
    y_ref, S_ref = _ssd_naive(q, k, v, log_a)
    np.testing.assert_allclose(np.asarray(y), y_ref, atol=2e-3)
    np.testing.assert_allclose(np.asarray(S_fin), S_ref, atol=2e-3)


def test_ssd_step_continues_scan():
    """decode step from the scan's final state == scan over T+1."""
    key = jax.random.PRNGKey(5)
    b, t, h, dk, dv = 1, 16, 2, 4, 4
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (b, t + 1, h, dk))
    k = jax.random.normal(ks[1], (b, t + 1, h, dk))
    v = jax.random.normal(ks[2], (b, t + 1, h, dv))
    log_a = -jnp.abs(jax.random.normal(ks[3], (b, t + 1, h))) * 0.1
    y_full, _ = S.ssd_scan(q, k, v, log_a, chunk=t + 1)
    _, S_t = S.ssd_scan(q[:, :t], k[:, :t], v[:, :t], log_a[:, :t], chunk=t)
    y_step, _ = S.ssd_step(S_t, q[:, t], k[:, t], v[:, t], log_a[:, t])
    np.testing.assert_allclose(np.asarray(y_step), np.asarray(y_full[:, t]),
                               atol=2e-3)


def test_moe_conserves_tokens_and_balances():
    """Every token's output is its router-weighted expert mix, with no
    capacity to drop any; every routed pair lands on a held expert when
    the layer holds them all."""
    from repro.models.moe import moe_apply
    cfg = get_config("qwen3_moe_235b_a22b").reduced(
        n_experts=4, experts_per_token=2)
    d, ff = cfg.d_model, cfg.d_ff
    key = jax.random.PRNGKey(0)
    from repro.models.moe import moe_init
    params, _ = moe_init(key, d, ff, 4, 0, "silu", cfg.ffn_sparsity)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, d), jnp.float32)
    y, aux, held = moe_apply(params, x, cfg, cfg.ffn_sparsity)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    assert 0.5 < float(aux) < 10.0  # aux ~ 1 for near-uniform routing
    np.testing.assert_array_equal(np.asarray(held), 2)


def test_moe_group_vs_global_equivalence():
    """The layer is a function of each token alone: a (4, 8) batch gives
    what the same 32 tokens give as one row."""
    from repro.models.moe import moe_apply, moe_init
    cfg = get_config("qwen3_moe_235b_a22b").reduced(
        n_experts=4, experts_per_token=2)
    d, ff = cfg.d_model, cfg.d_ff
    params, _ = moe_init(jax.random.PRNGKey(0), d, ff, 4, 0, "silu",
                         cfg.ffn_sparsity)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, d), jnp.float32)
    y4, _, _ = moe_apply(params, x, cfg, cfg.ffn_sparsity)
    y1, _, _ = moe_apply(params, x.reshape(1, 32, d), cfg, cfg.ffn_sparsity)
    np.testing.assert_allclose(np.asarray(y4).reshape(1, 32, d),
                               np.asarray(y1), atol=1e-4)


def test_mla_cache_decode_matches_full():
    cfg = get_config("deepseek_v2_lite_16b").reduced(
        remat=False, n_experts=0, n_shared_experts=0, experts_per_token=0,
        d_ff=64)
    from repro.models import forward, init_cache, init_model, serve_step
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              cfg.vocab_size)
    full, _ = forward(params, {"tokens": toks}, cfg)
    cache, _ = init_cache(cfg, 2, 8)
    for pos in range(8):
        logits, cache = serve_step(params, cache,
                                   {"tokens": toks[:, pos:pos + 1]}, pos, cfg)
    np.testing.assert_allclose(np.asarray(logits, np.float32),
                               np.asarray(full[:, -1], np.float32),
                               atol=0.15, rtol=0.05)


def test_mla_cache_is_compressed():
    """MLA cache per token (r + rope_dim) must be much smaller than a GQA
    cache (2 * kv * dh) — the latent-compression claim."""
    cfg = get_config("deepseek_v2_lite_16b")
    mla_per_tok = cfg.kv_lora_rank + cfg.rope_head_dim
    gqa_per_tok = 2 * cfg.n_kv_heads * cfg.head_dim
    assert mla_per_tok * 7 < gqa_per_tok
