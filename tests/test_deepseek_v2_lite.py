"""DeepSeek-V2-Lite at a small size on the CPU, against the benchmark's
plain reference (``benchmarks/chip/configs/deepseek-v2-lite.reference.py``)
on seeded random weights: paged prefill and decode logits, the engine's
greedy tokens, absorbed against expanded latent attention, the chip's
share of the experts, dropless routing; and what the change must leave
as it was (Qwen3's router, smollm's dispatch sites)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _reference as R
from repro.configs import get_config
from repro.core.api import observe_dispatch
from repro.launch.mesh import make_mesh
from repro.launch.serve import Engine
from repro.models import attention as A
from repro.models import moe as M
from repro.models import transformer as T
from repro.obs import DispatchStats
from repro.runtime.scheduler import Request

HI = jax.lax.Precision.HIGHEST
REF = R.reference("deepseek-v2-lite")
small = R.deepseek_small


def ref_logits(params, tokens, m):
    return np.asarray(REF.logits(params, jnp.asarray(tokens, jnp.int32), m))


def test_weights_match_program_layout():
    m, cfg, params = small()
    want = jax.eval_shape(lambda k: T.init_model(k, cfg)[0],
                          jax.random.PRNGKey(0))
    assert (jax.tree.map(lambda a: (a.shape, a.dtype), params)
            == jax.tree.map(lambda a: (a.shape, a.dtype), want))


def test_paged_prefill_and_decode_logits_match_reference():
    """Two page-aligned prompt chunks, then decode steps, through the
    paged pool (leading dense layer included): each step's logits equal
    the reference's full forward at that position."""
    m, cfg, params = small()
    rng = np.random.default_rng(0)
    seq = rng.integers(0, cfg.vocab_size, 21)
    prompt, page, chunk = 13, 8, 8
    want = ref_logits(params, seq, m)
    n_blocks = 4
    cache, _ = T.init_paged_cache(cfg, n_pages=n_blocks + 1, page_size=page)
    pages = jnp.arange(1, n_blocks + 1, dtype=jnp.int32)[None]
    got = []
    for start in range(0, prompt, chunk):
        ln = min(chunk, prompt - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :ln] = seq[start:start + ln]
        logits, cache = T.prefill_chunk(params, cache, {"tokens": toks},
                                        start, ln, cfg, pages)
    got.append(np.asarray(logits[0, ln - 1]))
    for pos in range(prompt, len(seq) - 1):
        logits, cache, held = T.serve_step(
            params, cache, {"tokens": jnp.asarray([[seq[pos]]])},
            jnp.asarray([pos]), cfg, pages=pages, moe_counts=True)
        got.append(np.asarray(logits[0]))
        assert 0 <= int(held[0]) <= 2 * cfg.experts_per_token
    np.testing.assert_allclose(np.stack(got), want[prompt - 1:-1],
                               atol=2e-4, rtol=2e-4)


def test_engine_serves_the_reference_greedy_tokens():
    """Greedy requests through ``Engine`` on the paged cache: every
    served token is the reference's first choice at its position."""
    m, cfg, params = small()
    rng = np.random.default_rng(1)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab_size,
                                               n).tolist(),
                    max_new_tokens=g)
            for i, (n, g) in enumerate([(11, 5), (4, 6), (19, 4)])]
    eng = Engine(cfg, make_mesh((1, 1), ("data", "model")), max_seq=32,
                 n_slots=2, params=params, page_size=8, prefill_chunk=8)
    out, _ = eng.serve(reqs)
    for r in reqs:
        seq = r.prompt + out[r.uid]
        ref = ref_logits(params, seq, m)
        first = len(r.prompt) - 1
        gaps = (ref.max(-1) - ref[np.arange(len(seq)), np.r_[seq[1:], 0]])
        assert np.all(gaps[first:first + len(out[r.uid])] < 1e-4), gaps


def test_absorbed_decode_matches_expanded_attention():
    """The cache's absorbed form (uk in the query, uv in the output)
    equals the expanded form of the full forward, position by position."""
    _, cfg, params = small()
    mp = jax.tree.map(lambda a: a[0], params["lead"]["mixer"])
    s = 9
    x = jax.random.normal(jax.random.PRNGKey(3), (2, s, cfg.d_model))
    full = A.mla_apply(mp, x, cfg, jnp.broadcast_to(
        jnp.arange(s), (2, s)))
    cache = A.mla_cache_init(cfg, 2, s, jnp.float32)
    for pos in range(s):
        y, cache = A.mla_decode(mp, x[:, pos:pos + 1], cfg, cache, pos)
        np.testing.assert_allclose(np.asarray(y[:, 0]),
                                   np.asarray(full[:, pos]), atol=1e-5,
                                   rtol=1e-4)


def _moe_params(params, lo, n):
    """The expert layer of the first scanned unit, holding the experts
    ``[lo, lo + n)`` of ``params``' stack (which holds ``[0, E)``)."""
    p = jax.tree.map(lambda a: a[0], params["units"]["b0"]["moe"])
    for name in ("up", "gate", "down"):
        p[name] = dict(p[name], packed=p[name]["packed"][lo:lo + n])
    return p


def test_chip_shares_sum_to_the_uncut_layer():
    """Four chips' shares of 8 experts, with the shared experts counted
    once, add up to the reference's layer with every expert held."""
    m, cfg, params = small(held_experts=8, held_expert_start=0)
    x = jax.random.normal(jax.random.PRNGKey(4), (12, cfg.d_model))
    whole = _moe_params(params, 0, 8)
    shared = REF.gated(x, whole["shared"], m, _mm)
    total = -3 * shared
    for lo in (0, 2, 4, 6):
        c = dataclasses.replace(cfg, held_experts=2, held_expert_start=lo)
        y, _, held = M.moe_apply(_moe_params(params, lo, 2), x, c,
                                 c.ffn_sparsity)
        total = total + y
    want = REF.experts(x, whole, dict(m, held_experts=8,
                                      held_expert_start=0), _mm)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5, rtol=1e-4)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def test_dropless_when_every_token_routes_to_one_expert():
    """A chunk whose tokens all put one held expert first: every token
    keeps it (no capacity), as in the reference."""
    m, cfg, params = small()
    p = _moe_params(params, 0, 2)
    x = 1.0 + 0.01 * jax.random.normal(jax.random.PRNGKey(5),
                                       (64, cfg.d_model))
    router = np.asarray(p["router"]).copy()
    router[:, 3] = 1.0        # expert 3, the first held one, wins for all
    p["router"] = jnp.asarray(router)
    _, top_p, top_e = M.route(p["router"], x, cfg)
    assert np.all(np.asarray(top_e)[:, 0] == 3)
    y, _, held = M.moe_apply(p, x, cfg, cfg.ffn_sparsity)
    assert np.all(np.asarray(held) >= 1)
    want = REF.experts(x, p, m, _mm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5,
                               rtol=1e-4)


def test_qwen3_router_still_renormalises():
    """Qwen3 keeps its renormalised top-k weights: the layer equals the
    reference's expert layer with ``norm_topk_prob`` on."""
    cfg = get_config("qwen3_moe_235b_a22b").reduced(
        n_experts=4, experts_per_token=2, compute_dtype="float32")
    assert cfg.norm_topk_prob and cfg.n_held_experts == 4
    params, _ = M.moe_init(jax.random.PRNGKey(0), cfg.d_model, cfg.d_ff, 4,
                           0, "silu", cfg.ffn_sparsity)
    x = jax.random.normal(jax.random.PRNGKey(1), (10, cfg.d_model))
    _, top_p, _ = M.route(params["router"], x, cfg)
    np.testing.assert_allclose(np.asarray(top_p).sum(-1), 1.0, rtol=1e-6)
    y, _, _ = M.moe_apply(params, x, cfg, cfg.ffn_sparsity)
    m = dict(n_experts=4, experts_per_token=2, held_experts=0,
             held_expert_start=0, norm_topk_prob=True,
             ffn_sparsity={"k_frac": cfg.ffn_sparsity.k_frac})
    want = REF.experts(x, params, m, _mm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5,
                               rtol=1e-4)


def _site_counts(cfg, batch):
    """Sparse-layer sites per path of one staged paged decode step."""
    params = jax.eval_shape(lambda k: T.init_model(k, cfg)[0],
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: T.init_paged_cache(cfg, 9, 16)[0])
    stats = DispatchStats()
    with observe_dispatch(stats.on_event):
        jax.make_jaxpr(lambda p, c, t, q, g: T.serve_step(
            p, c, {"tokens": t}, q, cfg, pages=g))(
            params, cache, jnp.zeros((batch, 1), jnp.int32),
            jnp.zeros((batch,), jnp.int32),
            jnp.zeros((batch, 8), jnp.int32))
    return stats.summary()


def test_smollm_dispatch_sites_unchanged():
    """smollm-360m's decode step at its cell's batch (4 slots) stages the
    same sparse-layer sites per path: per scanned unit of two blocks, the
    up and gate projections on the hadamard path, the down projection on
    the topk path (B·K = 1280 < 2560)."""
    assert _site_counts(get_config("smollm_360m"), 4) == {
        "hadamard[jnp]": 4, "topk[jnp]": 2}


@pytest.mark.parametrize("batch", [16])
def test_deepseek_dispatch_sites(batch):
    """At the cell's 16 slots every FFN projection of DeepSeek-V2-Lite
    takes the hadamard path: the dense layer's and the shared experts'
    three, and the held experts' three (each staged once, vmapped over
    the experts)."""
    assert _site_counts(get_config("deepseek_v2_lite_16b"), batch) == {
        "hadamard[jnp]": 9}


def test_prefix_sharing_and_cow_cover_the_leading_layer():
    """A duplicate prompt admitted after its parent's prefill adopts the
    parent's pages (the leading layer's pool leaf with the scanned ones)
    and breaks the last one by copy-on-write; an extension adopts them
    too.  Every served token is the reference's first choice."""
    m, cfg, params = small()
    base = np.random.default_rng(7).integers(0, cfg.vocab_size, 12).tolist()
    # the parent decodes long enough to stay alive while the others land;
    # two budget-1 fillers hold the other slots until the parent's pages
    # are registered
    spec = [(base, 6), ([5, 6, 7], 1), ([8, 9, 10], 1), (base, 6),
            (base + [3, 1, 4], 5)]
    reqs = [Request(uid=i, prompt=list(p), max_new_tokens=g)
            for i, (p, g) in enumerate(spec)]
    mesh = make_mesh((1, 1), ("data", "model"))
    out, stats = Engine(cfg, mesh, max_seq=32, n_slots=3, params=params,
                        page_size=4, prefill_chunk=4).serve(reqs)
    assert stats["prefix_hit_pages"] >= 3 and stats["cow_copies"] >= 1, stats
    assert [len(out[r.uid]) for r in reqs] == [g for _, g in spec]
    R.assert_greedy("deepseek-v2-lite", params, m, reqs, out, 32)
