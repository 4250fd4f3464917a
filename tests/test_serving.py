"""Serving-engine tests: scheduler slot admission/retirement, sampling,
chunked paged prefill against stepwise decode, and the engine's served
tokens and logits against the plain reference
(``benchmarks/chip/configs/smollm-360m.reference.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _reference as R
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.launch.serve import Engine
from repro.models import transformer as T
from repro.runtime.scheduler import (Request, SamplingParams, Scheduler,
                                     sample_token)


# ---------------------------------------------------------------------------
# scheduler (pure policy, no jax)
# ---------------------------------------------------------------------------

def _req(uid, p_len=4, max_new=8, **kw):
    return Request(uid=uid, prompt=list(range(p_len)),
                   max_new_tokens=max_new, **kw)


def test_scheduler_fifo_admission():
    s = Scheduler(2)
    s.submit_many([_req(0), _req(1), _req(2)])
    admitted = s.admit()
    assert [sl.request.uid for sl in admitted] == [0, 1]
    assert [sl.index for sl in admitted] == [0, 1]
    assert s.admit() == []           # no free slots
    assert [r.uid for r in s.queue] == [2]
    assert s.has_work


def test_scheduler_positions_start_at_prompt_len():
    s = Scheduler(1)
    s.submit(_req(7, p_len=5))
    (slot,) = s.admit()
    assert slot.pos == 5 and slot.generated == []


def test_scheduler_retire_frees_slot_and_readmits():
    s = Scheduler(1)
    s.submit_many([_req(0, max_new=2), _req(1, max_new=1)])
    (slot,) = s.admit()
    s.record_token(slot, 11)
    assert not slot.done
    s.record_token(slot, 12)
    assert slot.done
    retired = s.retire_done()
    assert [r.request.uid for r in retired] == [0]
    assert s.finished[0] == [11, 12]
    assert not s.slots[0].busy
    (slot2,) = s.admit()              # the queued request takes the slot
    assert slot2.request.uid == 1 and slot2.index == 0
    s.record_token(slot2, 3)
    s.retire_done()
    assert s.finished[1] == [3]
    assert not s.has_work


def test_scheduler_eos_retires_early():
    s = Scheduler(1)
    s.submit(_req(0, max_new=100, eos_id=42))
    (slot,) = s.admit()
    s.record_token(slot, 5)
    s.record_token(slot, 42)
    assert slot.done
    s.retire_done()
    assert s.finished[0] == [5, 42]


def test_sampling_greedy_and_topk():
    logits = np.asarray([0.0, 5.0, 1.0, 4.0])
    assert sample_token(logits, SamplingParams(), None) == 1
    rng = np.random.default_rng(0)
    picks = {sample_token(logits, SamplingParams(temperature=1.0, top_k=2),
                          rng) for _ in range(50)}
    assert picks <= {1, 3}            # top-2 filter holds
    assert len(picks) == 2            # and it actually samples
    # per-request seeds are deterministic
    a = [sample_token(logits, SamplingParams(temperature=0.7, seed=3),
                      np.random.default_rng(3)) for _ in range(5)]
    b = [sample_token(logits, SamplingParams(temperature=0.7, seed=3),
                      np.random.default_rng(3)) for _ in range(5)]
    assert a == b


# ---------------------------------------------------------------------------
# chunked paged prefill: against stepwise decode and against the reference
# ---------------------------------------------------------------------------

def _cfg(**overrides):
    base = dict(head_pad=0, compute_dtype="float32", param_dtype="float32")
    base.update(overrides)
    return get_config("smollm-360m").reduced(**base)


def _chunked_prefill(params, cfg, toks, chunk, page):
    """Prefill one prompt through the paged pool in chunks of ``chunk``
    rows over pages of ``page``; returns (each chunk's logits over its
    true rows, concatenated; the pool; the slot's page table)."""
    n_blocks = -(-len(toks) // page) + 1
    cache, _ = T.init_paged_cache(cfg, n_pages=n_blocks + 1, page_size=page)
    pages = jnp.arange(1, n_blocks + 1, dtype=jnp.int32)[None]
    run = jax.jit(lambda p, c, t, s0, n: T.prefill_chunk(
        p, c, {"tokens": t}, s0, n, cfg, pages))
    rows = []
    for start in range(0, len(toks), chunk):
        ln = min(chunk, len(toks) - start)
        buf = np.zeros((1, chunk), np.int32)
        buf[0, :ln] = toks[start:start + ln]
        logits, cache = run(params, cache, buf, start, ln)
        rows.append(np.asarray(logits[0, :ln]))
    return np.concatenate(rows), cache, pages


def test_fused_prefill_matches_stepwise_cache():
    """Chunked prefill over the paged pool (three chunks, the last one
    partial) writes the KV rows and gives the logits that stepwise decode
    through the contiguous cache does, position by position."""
    cfg = _cfg()
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    p_len, chunk, page = 19, 8, 4
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, p_len)
    logits_c, pool, pages = _chunked_prefill(params, cfg, toks, chunk, page)
    cache_s, _ = T.init_cache(cfg, 1, 24)
    step = jax.jit(lambda p, c, t, q: T.serve_step(p, c, {"tokens": t}, q,
                                                    cfg))
    logits_s = []
    for pos in range(p_len):
        lg, cache_s = step(params, cache_s, toks[None, pos:pos + 1], pos)
        logits_s.append(np.asarray(lg[0]))
    np.testing.assert_allclose(logits_c, np.stack(logits_s), atol=1e-4)
    flat = np.asarray(pages[0])
    for lp, ls in zip(jax.tree.leaves(pool), jax.tree.leaves(cache_s)):
        # the slot's rows, read through its page table: (n_units, S, ...)
        view = np.asarray(lp, np.float32)[:, flat].reshape(
            lp.shape[0], -1, *lp.shape[3:])
        np.testing.assert_allclose(
            view[:, :p_len], np.asarray(ls, np.float32)[:, 0, :p_len],
            atol=1e-4)


def test_paged_prefill_and_decode_logits_match_reference(small):
    """smollm-360m at small widths, the weights made by its reference:
    two page-aligned prompt chunks, then decode steps through the paged
    pool; each position's logits equal the reference's full forward."""
    m, cfg, params = small
    seq = np.random.default_rng(0).integers(0, cfg.vocab_size, 21)
    prompt = 13
    want = np.asarray(R.reference("smollm-360m").logits(
        params, jnp.asarray(seq), m))
    got, cache, pages = _chunked_prefill(params, cfg, seq[:prompt], 8, 8)
    got = list(got)
    step = jax.jit(lambda p, c, t, q: T.serve_step(p, c, {"tokens": t}, q,
                                                    cfg, pages=pages))
    for pos in range(prompt, len(seq)):
        logits, cache = step(params, cache, seq[None, pos:pos + 1],
                             np.asarray([pos]))
        got.append(np.asarray(logits[0]))
    np.testing.assert_allclose(np.stack(got), want, atol=1e-6, rtol=1e-5)


def test_fused_prefill_rejects_ssm_patterns():
    """A recurrent block pattern has no paged cache: the Engine refuses
    it when built, before any weight is made."""
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(NotImplementedError, match="attention-only"):
        Engine(get_config("zamba2-1p2b"), mesh, max_seq=16)


def test_decode_vector_positions_match_scalar():
    """A (B,) position vector with equal entries must equal the scalar-pos
    decode — the continuous-batching kernel contract."""
    cfg = _cfg()
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    b, max_seq = 3, 16
    cache, _ = T.init_cache(cfg, b, max_seq)
    toks = np.random.default_rng(1).integers(0, cfg.vocab_size, (b, 1))
    for pos in range(4):
        batch = {"tokens": jnp.asarray(toks)}
        l1, c1 = T.serve_step(params, cache, batch, pos, cfg)
        l2, c2 = T.serve_step(params, cache, batch,
                              jnp.full((b,), pos, jnp.int32), cfg)
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-5)
        for a, bb in zip(jax.tree.leaves(c1), jax.tree.leaves(c2)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(bb, np.float32), atol=1e-5)
        cache = c1


# ---------------------------------------------------------------------------
# engine: continuous batching against the plain reference
# ---------------------------------------------------------------------------

MAX_SEQ = 48


@pytest.fixture(scope="module")
def small():
    """smollm-360m at small widths: (model dict, config, weights made by
    its reference)."""
    return R.smollm_small()


@pytest.fixture(scope="module")
def engine(small):
    _, cfg, params = small
    mesh = make_mesh((1, 1), ("data", "model"))
    return Engine(cfg, mesh, max_seq=MAX_SEQ, n_slots=4, params=params,
                  page_size=8, prefill_chunk=8)


def _check(small, reqs, out):
    m, _, params = small
    R.assert_greedy("smollm-360m", params, m, reqs, out, MAX_SEQ)


def test_continuous_matches_static_greedy(engine, small):
    prompts = np.random.default_rng(0).integers(
        0, engine.cfg.vocab_size, (4, 9)).astype(np.int32)
    reqs = [Request(uid=i, prompt=prompts[i].tolist(), max_new_tokens=12)
            for i in range(4)]
    out, stats = engine.serve(reqs)
    assert all(len(out[i]) == 12 for i in range(4))
    _check(small, reqs, out)
    # one chunk per loop iteration and two chunks a prompt: the last
    # prompt's first token comes at iteration 8, its 11 decode steps run
    # in iterations 8-18, and the first slot decodes from iteration 2
    assert stats["decode_steps"] == 17
    assert len(stats["ttft_s"]) == 4


def test_one_prefill_call_per_prompt(engine):
    """Each prompt counts one prefill (its last chunk) and takes
    ceil(len / chunk) chunk forwards; prompts and chunks of other lengths
    reuse the compiled chunk program (the true length is a traced
    scalar).  The first chunk of a serve reads the fresh pool, laid out
    by its specs, and later ones the pool as the programs return it, so
    jit may hold an executable for each layout, but no more."""
    rng = np.random.default_rng(2)
    chunk = engine.prefill_chunk
    sizes = []
    for lens in ([9, 17, 3], [5, 12, 23]):
        before = engine.prefill_calls
        _, stats = engine.serve([Request(
            uid=i, prompt=rng.integers(0, engine.cfg.vocab_size, n).tolist(),
            max_new_tokens=4) for i, n in enumerate(lens)])
        assert engine.prefill_calls - before == 3
        assert stats["prefill_chunks"] == sum(-(-n // chunk) for n in lens)
        cache_size = getattr(engine._chunk_jit, "_cache_size", None)
        if cache_size is not None:
            sizes.append(cache_size())
    if sizes:
        assert sizes[0] == sizes[1] <= 2, sizes


def test_slot_reuse_midflight_matches_oracle(engine, small):
    """More requests than slots with mixed budgets: freed slots are
    refilled mid-flight and every request still serves the reference's
    greedy tokens."""
    rng = np.random.default_rng(3)
    budgets = [3, 8, 5, 13, 2, 7]
    reqs = [Request(uid=i, prompt=rng.integers(
        0, engine.cfg.vocab_size, 9).tolist(), max_new_tokens=b)
        for i, b in enumerate(budgets)]
    out, _ = engine.serve(reqs)
    assert sorted(out) == list(range(6))
    assert [len(out[i]) for i in range(6)] == budgets
    _check(small, reqs, out)


def test_engine_rejects_oversized_request(engine):
    with pytest.raises(ValueError, match="exceeds max_seq"):
        engine.serve([Request(uid=0, prompt=[1] * 40, max_new_tokens=40)])


# ---------------------------------------------------------------------------
# sparse-sparse decode through the batched Pallas kernel (interpret on CPU)
# ---------------------------------------------------------------------------

def _sparse_engine(small, use_pallas):
    _, cfg, params = small
    mesh = make_mesh((1, 1), ("data", "model"))
    return Engine(cfg, mesh, max_seq=32, n_slots=4, params=params,
                  use_pallas=use_pallas)


def test_sparse_sparse_continuous_matches_static_with_pallas(small):
    """Continuous batching through the batched topk_gather kernel (forced,
    interpret fallback on CPU; 4 slots x K 32 < d_ff 256 puts the down
    projection on the topk path) must serve the reference's greedy tokens
    and match the jnp-executor engine token-for-token."""
    eng_pl = _sparse_engine(small, "force")
    assert eng_pl.cfg.ffn_sparsity.use_pallas == "force"
    prompts = np.random.default_rng(5).integers(
        0, eng_pl.cfg.vocab_size, (4, 9)).astype(np.int32)
    reqs = [Request(uid=i, prompt=prompts[i].tolist(), max_new_tokens=10)
            for i in range(4)]
    out_pl, stats = eng_pl.serve(reqs)
    out_jnp, _ = _sparse_engine(small, "off").serve(reqs)
    assert out_jnp == out_pl
    # one whole-prompt chunk per iteration: the last slot's first token
    # comes at iteration 4, its 9 decode steps run in iterations 4-12
    assert stats["decode_steps"] == 12
    m, _, params = small
    R.assert_greedy("smollm-360m", params, m, reqs, out_pl, 32)
