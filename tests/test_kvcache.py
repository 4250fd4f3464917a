"""Paged KV-cache tests: block-allocator invariants (unit + property),
PagedKV geometry/layout ops, page-gated admission policy, and the paged
engine's greedy tokens against the plain reference — including chunked
prefill of prompts longer than one chunk, a page pool smaller than full
backing, the latent cache of MLA, and the int8 cache (against stepwise
decode, which the reference cannot judge).

ISSUE 10 additions: the grow-on-demand path (lazy ``extend`` at page
boundaries, LRU preemption with recompute-on-resume, ref-counted
prefix sharing with copy-on-write) — allocator- and scheduler-level
here; the engine-level differential fuzz harness lives in
``tests/test_kvcache_fuzz.py``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _reference as R
from _hypothesis_compat import given, settings, st
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.launch.serve import Engine
from repro.models import transformer as T
from repro.runtime.kvcache import (NULL_PAGE, BlockAllocator, PagedKV,
                                   paged_view, paged_write_chunk,
                                   paged_write_rows, prefix_keys)
from repro.runtime.scheduler import Request, SamplingParams, Scheduler


# ---------------------------------------------------------------------------
# BlockAllocator: unit tests (pure Python, no jax)
# ---------------------------------------------------------------------------

def test_allocator_basics_and_accounting():
    a = BlockAllocator(n_pages=9, page_size=4)
    assert a.capacity == 8 and a.free_pages == 8 and a.used_pages == 0
    chain = a.allocate(0, 3)
    assert len(chain) == 3 and NULL_PAGE not in chain
    assert a.used_pages == 3 and a.occupancy == pytest.approx(3 / 8)
    assert a.chain(0) == chain
    assert a.live_uids() == [0]
    freed = a.release(0)
    assert sorted(freed) == sorted(chain)
    assert a.free_pages == 8
    a.check()


def test_allocator_pages_needed_rounds_up():
    a = BlockAllocator(n_pages=4, page_size=8)
    # ISSUE 10 regression: zero tokens need zero pages — the old
    # max(1, ...) made every empty-prompt admit burn a page for nothing
    assert a.pages_needed(0) == 0
    assert a.pages_needed(1) == 1
    assert a.pages_needed(8) == 1
    assert a.pages_needed(9) == 2
    assert a.pages_needed(17) == 3


def test_allocator_rejects_double_alloc_and_overflow():
    a = BlockAllocator(n_pages=4, page_size=2)  # capacity 3
    a.allocate(1, 2)
    with pytest.raises(ValueError):
        a.allocate(1, 1)             # uid already holds a chain
    assert not a.can_allocate(2)
    with pytest.raises(MemoryError):
        a.allocate(2, 2)             # only 1 page free
    with pytest.raises(ValueError):
        a.allocate(3, -1)            # negative page count
    assert a.allocate(3, 0) == []    # empty chain is legal (grow policy)
    with pytest.raises(KeyError):
        a.release(99)                # never allocated
    a.release(3)
    a.check()


def test_allocator_extend_grows_chain():
    a = BlockAllocator(n_pages=6, page_size=2)
    first = a.allocate(0, 2)
    more = a.allocate(1, 1)
    grown = a.extend(0, 2)
    assert a.chain(0) == first + grown
    assert not (set(grown) & set(first)) and not (set(grown) & set(more))
    with pytest.raises(MemoryError):
        a.extend(0, 1)               # pool exhausted
    with pytest.raises(KeyError):
        a.extend(7, 1)
    a.check()


def test_allocator_extend_exhaustion_keeps_chain_intact():
    """The grow-on-demand failure mode: a failed extend must raise
    MemoryError and leave the chain exactly as it was (the engine
    preempts a victim and retries)."""
    a = BlockAllocator(n_pages=5, page_size=2)       # 4 usable
    chain = a.allocate(0, 3)
    a.allocate(1, 1)
    with pytest.raises(MemoryError):
        a.extend(0, 2)               # only 0 free
    assert a.chain(0) == chain       # untouched by the failed extend
    a.check()
    a.release(1)
    assert a.extend(0, 1)            # now it fits
    a.check()


def test_allocator_free_list_is_lifo():
    """Recently freed pages are re-issued first — keeps the hot set
    small and makes use-after-free loud."""
    a = BlockAllocator(n_pages=8, page_size=2)
    a.allocate(0, 2)
    mid = a.allocate(1, 2)
    a.allocate(2, 2)
    freed = a.release(1)
    assert freed == mid
    # LIFO: the re-issue pops the most recently freed page last-in-first
    assert a.extend(0, 2) == mid[::-1]
    a.check()


def test_allocator_interleaved_extend_release_invariants():
    a = BlockAllocator(n_pages=10, page_size=2)
    a.allocate(0, 1)
    a.allocate(1, 2)
    for _ in range(3):
        a.extend(0, 1)
        a.check()
    a.release(1)
    a.check()
    a.extend(0, 2)
    a.check()
    assert a.chain_len(0) == 6
    a.release(0)
    a.check()
    assert a.free_pages == a.capacity


def test_allocator_refcounts_shared_and_fork():
    a = BlockAllocator(n_pages=8, page_size=2)
    parent = a.allocate(0, 3)
    child = a.allocate(1, 1, shared=parent[:2])      # adopt 2 pages
    assert child[:2] == parent[:2]
    assert a.page_ref(parent[0]) == 2
    assert a.page_shared(0, 0) and a.page_shared(1, 0)
    assert not a.page_shared(0, 2)
    a.check()
    # releasing the parent keeps the shared pages alive for the child
    freed = a.release(0)
    assert freed == [parent[2]]
    assert a.page_ref(parent[0]) == 1
    a.check()
    # fork clones the whole chain by reference
    forked = a.fork(1, 2)
    assert forked == a.chain(1)
    assert all(a.page_ref(p) == 2 for p in forked)
    with pytest.raises(ValueError):
        a.fork(1, 2)                 # child already holds a chain
    with pytest.raises(KeyError):
        a.fork(99, 3)
    a.release(1)
    a.release(2)
    a.check()
    assert a.free_pages == a.capacity


def test_allocator_cow_page():
    a = BlockAllocator(n_pages=6, page_size=2)       # 5 usable
    chain = a.allocate(0, 2)
    a.fork(0, 1)
    # shared page: cow swaps in a fresh one, old stays with the peer
    old_new = a.cow_page(0, 0)
    assert old_new is not None
    old, new = old_new
    assert old == chain[0] and new not in chain
    assert a.chain(0)[0] == new and a.chain(1)[0] == old
    assert a.page_ref(old) == 1 and a.page_ref(new) == 1
    a.check()
    # uniquely-held page: no copy needed
    assert a.cow_page(0, 0) is None
    assert a.cow_page(0, 1) is not None    # break the remaining share
    a.check()
    # exhausted pool: cow must raise, not corrupt
    a.allocate(2, 1)                 # takes the last free page
    a.fork(2, 3)
    with pytest.raises(MemoryError):
        a.cow_page(2, 0)             # shared, but 0 pages free
    a.check()


def test_prefix_keys_page_aligned_and_tail():
    toks = list(range(10))
    keys = prefix_keys(toks, page_size=4)
    assert len(keys) == 3            # 2 full pages + tail
    # full-page keys depend only on the token prefix through the page
    assert keys[:2] == prefix_keys(toks[:8] + [99, 98], 4)[:2]
    # the tail key is exact-length/exact-content
    assert keys[2] != prefix_keys(toks + [0], 4)[2]
    assert prefix_keys(toks[:8], 4) == keys[:2]      # no tail when aligned
    assert prefix_keys([], 4) == []


def test_prefix_keys_collision_resistant_digest():
    """Regression: the keys were builtin ``hash()`` values, and builtin
    hashes collide — ``hash(-1) == hash(-2)`` in CPython, so the old
    tuple-hash keys for the prompts ``[-1]`` and ``[-2]`` were EQUAL and
    a later request would silently adopt the wrong live pages (wrong
    tokens, invisible to ``check()``).  The sha256 digests must tell
    such prompts apart."""
    assert hash((-1,)) == hash((-2,))    # the builtin trap the digest avoids
    assert prefix_keys([-1], 4) != prefix_keys([-2], 4)
    keys = prefix_keys(list(range(10)), 4)
    assert all(isinstance(k, bytes) for k in keys)
    # full-page and tail keys live in disjoint namespaces: the same
    # token run keyed as a full page never matches it keyed as a tail
    assert prefix_keys([1, 2, 3, 4], 4) != prefix_keys([1, 2, 3, 4], 5)


def test_allocator_prefix_index_register_match_drop():
    a = BlockAllocator(n_pages=8, page_size=2)
    toks = [7, 3, 9, 1, 4]           # 2 full pages + 1 tail
    keys = prefix_keys(toks, 2)
    a.allocate(0, 3)
    assert a.register_chain_prefix(0, keys) == 3
    assert a.match_prefix(keys) == a.chain(0)
    # a prefix of the prompt matches only its full pages
    assert a.match_prefix(prefix_keys(toks[:4], 2)) == a.chain(0)[:2]
    # first registration wins; re-registering is a no-op
    assert a.register_chain_prefix(0, keys) == 0
    a.check()
    # adopting via allocate(shared=) bumps refcounts
    shared = a.match_prefix(keys)
    a.allocate(1, 0, shared=shared)
    assert all(a.page_ref(p) == 2 for p in shared)
    a.check()
    # entries die with the page: release both holders -> no matches
    a.release(0)
    assert a.match_prefix(keys) == shared            # child keeps it live
    a.release(1)
    assert a.match_prefix(keys) == []
    a.check()
    with pytest.raises(ValueError):
        a.register_prefix(keys[0], 99)               # dead page


def test_allocator_null_page_never_issued():
    a = BlockAllocator(n_pages=5, page_size=1)
    pages = []
    for uid in range(4):             # drain the whole pool
        pages += a.allocate(uid, 1)
    assert NULL_PAGE not in pages
    assert sorted(pages) == [1, 2, 3, 4]
    assert not a.can_allocate(1)
    a.check()


# ---------------------------------------------------------------------------
# BlockAllocator: alloc/free interleavings (property + seeded fallback)
# ---------------------------------------------------------------------------

def _run_interleaving(n_pages, page_size, ops):
    """Drive an alloc/release script against the invariant checker and a
    shadow model of who owns what; ops = [(uid, n_tokens or None), ...]
    where None means release."""
    a = BlockAllocator(n_pages, page_size)
    owned = {}
    for uid, tok in ops:
        if tok is None:
            if uid in owned:
                freed = a.release(uid)
                assert sorted(freed) == sorted(owned.pop(uid))
        elif uid not in owned:
            n = a.pages_needed(tok)
            if a.can_allocate(n):
                owned[uid] = a.allocate(uid, n)
        a.check()                    # no double-assignment, conservation
        live = [p for c in owned.values() for p in c]
        assert len(set(live)) == len(live)
        assert a.used_pages == len(live)
    for uid in list(owned):
        a.release(uid)
        a.check()
    assert a.free_pages == a.capacity  # chains reclaim fully


@settings(max_examples=60, deadline=None)
@given(st.integers(2, 17), st.integers(1, 8),
       st.lists(st.tuples(st.integers(0, 5),
                          st.one_of(st.none(), st.integers(0, 40))),
                max_size=60))
def test_allocator_interleavings_property(n_pages, page_size, ops):
    _run_interleaving(n_pages, page_size, ops)


def test_allocator_interleavings_seeded():
    """Hypothesis-free twin of the property test, so the invariants are
    exercised even on environments without hypothesis installed."""
    rng = np.random.default_rng(0)
    for _ in range(40):
        n_pages = int(rng.integers(2, 18))
        page_size = int(rng.integers(1, 9))
        ops = [(int(rng.integers(0, 6)),
                None if rng.random() < 0.4 else int(rng.integers(0, 41)))
               for _ in range(int(rng.integers(0, 60)))]
        _run_interleaving(n_pages, page_size, ops)


# ---------------------------------------------------------------------------
# PagedKV geometry + host-side page tables
# ---------------------------------------------------------------------------

def test_pagedkv_build_geometry():
    geo = PagedKV.build(max_seq=40, n_slots=4, page_size=16)
    assert geo.blocks_per_slot == 3          # ceil(40 / 16)
    assert geo.view_len == 48                # >= max_seq, masked overhang
    assert geo.n_pages == 4 * 3 + 1          # full backing + null page
    small = PagedKV.build(40, 4, page_size=16, n_pages=7)
    assert small.n_pages == 7
    with pytest.raises(ValueError):
        PagedKV.build(40, 4, page_size=16, n_pages=3)  # < one request
    with pytest.raises(ValueError):
        PagedKV.build(40, 4, page_size=0)


def test_pagedkv_tables_and_chunk_spans():
    geo = PagedKV.build(max_seq=32, n_slots=2, page_size=8)
    t = geo.empty_tables(2)
    assert t.shape == (2, 4) and (t == NULL_PAGE).all()
    geo.set_chain(t, 1, [5, 2])
    assert list(t[1]) == [5, 2, NULL_PAGE, NULL_PAGE]
    assert (t[0] == NULL_PAGE).all()
    geo.clear_chain(t, 1)
    assert (t == NULL_PAGE).all()
    with pytest.raises(ValueError):
        geo.set_chain(t, 0, [1, 2, 3, 4, 5])  # wider than the table
    assert geo.chunk_spans(20, 8) == [(0, 8), (8, 8), (16, 4)]
    assert geo.chunk_spans(8, 8) == [(0, 8)]
    with pytest.raises(ValueError):
        geo.chunk_spans(20, 12)               # not a page multiple


# ---------------------------------------------------------------------------
# layout ops: gather/scatter against a contiguous shadow
# ---------------------------------------------------------------------------

def test_paged_write_rows_and_view_roundtrip():
    P, n_pages = 4, 7
    pool = jnp.zeros((n_pages, P, 3), jnp.float32)
    # two slots, chains [1,2] and [5], slot 2 inactive (all null)
    pages = jnp.asarray([[1, 2], [5, NULL_PAGE], [NULL_PAGE, NULL_PAGE]],
                        jnp.int32)
    rows = jnp.asarray([[1., 1, 1], [2., 2, 2], [9., 9, 9]])
    pool = paged_write_rows(pool, rows, pages, jnp.asarray([5, 0, 3]))
    v = np.asarray(paged_view(pool, pages))
    assert v.shape == (3, 2 * P, 3)
    assert (v[0, 5] == 1.0).all()            # slot 0, pos 5 -> page 2 row 1
    assert (v[1, 0] == 2.0).all()            # slot 1, pos 0 -> page 5 row 0
    # the inactive slot's write landed in the null page, not a real one
    assert not (np.asarray(pool)[1:] == 9.0).any()
    assert (np.asarray(pool)[NULL_PAGE, 3] == 9.0).all()


def test_paged_write_chunk_pads_to_null_page():
    P = 4
    pool = jnp.zeros((5, P, 2), jnp.float32)
    chain = jnp.asarray([3, NULL_PAGE, NULL_PAGE], jnp.int32)  # 1-page chain
    rows = jnp.stack([jnp.full((2,), float(i + 1)) for i in range(8)])
    # 3 true rows at positions [2, 5): rows 3..7 are bucket padding and
    # must sink into the null page, NOT clobber a clamped real page
    pool = paged_write_chunk(pool, rows, chain, jnp.int32(2), jnp.int32(3))
    got = np.asarray(pool)
    assert (got[3, 2] == 1.0).all() and (got[3, 3] == 2.0).all()
    real = got[1:].copy()
    real[2, 2:] = 0.0                         # the two true rows on page 3
    # position 4 (3rd true row) wraps to block 1 -> null page, by design:
    # the chain is 1 page, so rows past it go to the sink too
    assert (real == 0.0).all()
    assert got[NULL_PAGE].any()               # padding mass went to the sink


# ---------------------------------------------------------------------------
# page-gated admission (scheduler policy, no jax)
# ---------------------------------------------------------------------------

def _req(uid, p_len, max_new=4, **kw):
    return Request(uid=uid, prompt=list(range(p_len)),
                   max_new_tokens=max_new, **kw)


def test_admission_gated_by_free_pages_not_slots():
    alloc = BlockAllocator(n_pages=5, page_size=4)     # 4 usable pages
    s = Scheduler(4, allocator=alloc)
    # admission charges the prompt's pages only
    s.submit_many([_req(0, 12, max_new=4),  # 3 pages
                   _req(1, 3, max_new=8)])  # 1 page
    admitted = s.admit()
    assert [sl.request.uid for sl in admitted] == [0, 1]
    assert alloc.free_pages == 0
    s.submit(_req(2, 1, max_new=1))
    assert s.admit() == []                  # slots free, pages aren't
    for slot in s.slots:
        if slot.busy:
            for t in range(slot.request.max_new_tokens):
                s.record_token(slot, t)
    s.retire_done()
    assert alloc.free_pages == 4            # chains reclaimed on retire
    (slot,) = s.admit()
    assert slot.request.uid == 2
    alloc.check()


def test_admission_head_of_line_blocks_fifo():
    alloc = BlockAllocator(n_pages=4, page_size=2)     # 3 usable pages
    s = Scheduler(2, allocator=alloc)
    s.submit_many([_req(0, 8, max_new=2),   # 4 prompt pages: never fits
                   _req(1, 1, max_new=1)])  # 1 page: would fit
    assert s.admit() == []                  # strict FIFO: head blocks tail
    assert [r.uid for r in s.queue] == [0, 1]
    alloc.check()


def test_chunked_admit_sets_prefill_state():
    s = Scheduler(1, allocator=BlockAllocator(8, 2))
    s.submit(_req(0, 5))
    (slot,) = s.admit()
    assert slot.prefilling and slot.prefill_pos == 0
    assert s.decoding_slots() == []
    slot.prefill_pos = 5                    # engine finished the chunks
    assert not slot.prefilling
    assert s.decoding_slots() == [slot]


# ---------------------------------------------------------------------------
# grow-on-demand admission + preemption (scheduler policy, no jax)
# ---------------------------------------------------------------------------

def test_grow_admission_uses_prompt_footprint_only():
    alloc = BlockAllocator(n_pages=5, page_size=4)   # 4 usable pages
    s = Scheduler(4, allocator=alloc)
    # worst-case footprints are 3+3 pages (would NOT both fit under
    # reserve); prompt footprints are 2+1 and fit together under grow
    s.submit_many([_req(0, 8, max_new=4), _req(1, 1, max_new=8)])
    admitted = s.admit()
    assert [sl.request.uid for sl in admitted] == [0, 1]
    assert alloc.chain_len(0) == 2 and alloc.chain_len(1) == 1
    assert alloc.free_pages == 1
    alloc.check()


def test_preemption_victim_is_youngest_admitted():
    alloc = BlockAllocator(n_pages=9, page_size=4)
    s = Scheduler(3, allocator=alloc)
    s.submit_many([_req(0, 4), _req(1, 4), _req(2, 4)])
    s.admit()
    victim = s.preemption_victim()
    assert victim.request.uid == 2          # last admitted, least service
    assert s.preemption_victim(exclude=(victim.index,)).request.uid == 1


def test_preempt_requeues_at_head_with_generated_suffix():
    alloc = BlockAllocator(n_pages=9, page_size=4)
    s = Scheduler(2, allocator=alloc)
    s.submit_many([_req(0, 4, max_new=6), _req(1, 3, max_new=2),
                   _req(2, 2, max_new=2)])
    s.admit()
    slot = s.slots[0]
    slot.prefill_pos = 4                    # prefill done
    for t in (11, 12, 13):
        s.record_token(slot, t)
    rng_state = slot.rng.bit_generator.state
    s.preempt(slot)
    # pages released, request back at the HEAD (before still-queued uid 2)
    assert not slot.busy
    assert 0 not in alloc.live_uids()
    assert [r.uid for r in s.queue] == [0, 2]
    resumed = s.queue[0]
    assert list(resumed.prompt) == list(_req(0, 4).prompt) + [11, 12, 13]
    assert resumed.max_new_tokens == 6
    assert s.records[0].status == "queued"
    assert s.records[0].preemptions == 1
    alloc.check()
    # re-admission restores generated tokens and the sampling rng, so
    # decode continues exactly where it left off
    (slot2,) = s.admit()
    assert slot2.request.uid == 0
    assert slot2.generated == [11, 12, 13]
    assert slot2.rng.bit_generator.state == rng_state
    assert slot2.pos == 7                   # len(prompt + generated)
    # done-accounting still counts against the ORIGINAL budget
    for t in (14, 15, 16):
        s.record_token(slot2, t)
    assert slot2.done
    s.retire_done()
    assert s.finished[0] == [11, 12, 13, 14, 15, 16]
    alloc.check()


def test_preempt_twice_rebuilds_from_original_prompt():
    """Regression: preempting an already-resumed request must rebuild
    ``original_prompt + ALL generated`` — the resumed request's .prompt
    already embeds the first round of generated tokens, and appending
    ``slot.generated`` to it again duplicated that round (corrupt KV
    context, wrong positions, possible max_seq overflow)."""
    alloc = BlockAllocator(n_pages=17, page_size=4)
    s = Scheduler(1, allocator=alloc)
    orig = _req(0, 4, max_new=8)
    s.submit(orig)
    s.admit()
    slot = s.slots[0]
    slot.prefill_pos = 4
    for t in (11, 12):
        s.record_token(slot, t)
    s.preempt(slot)
    assert list(s.queue[0].prompt) == list(orig.prompt) + [11, 12]
    # resume, generate two more, preempt AGAIN: the rebuilt prompt must
    # hold each generated token exactly once
    (slot,) = s.admit()
    slot.prefill_pos = len(slot.request.prompt)
    for t in (13, 14):
        s.record_token(slot, t)
    s.preempt(slot)
    resumed = s.queue[0]
    assert list(resumed.prompt) == list(orig.prompt) + [11, 12, 13, 14]
    assert s.records[0].preemptions == 2
    alloc.check()
    # third leg runs to completion against the ORIGINAL budget
    (slot,) = s.admit()
    assert slot.generated == [11, 12, 13, 14]
    for t in (15, 16, 17, 18):
        s.record_token(slot, t)
    assert slot.done
    s.retire_done()
    assert s.finished[0] == [11, 12, 13, 14, 15, 16, 17, 18]
    alloc.check()


def test_grow_admission_adopts_registered_prefix_pages():
    alloc = BlockAllocator(n_pages=9, page_size=2)
    s = Scheduler(2, allocator=alloc)
    parent = _req(0, 6, max_new=2)
    s.submit(parent)
    s.admit()
    # engine finished the parent's prefill and published its pages
    alloc.register_chain_prefix(0, prefix_keys(parent.prompt, 2))
    dup = _req(1, 6, max_new=2)             # same prompt (same _req range)
    s.submit(dup)
    (slot,) = s.admit()
    assert slot.request.uid == 1
    assert alloc.chain(1) == alloc.chain(0)  # all 3 pages adopted
    assert s.prefix_hit_pages == 3
    # prefill restarts at the last prompt token, never a full skip: the
    # final logits row must come from a real chunk forward (and its
    # shared-page write is what triggers copy-on-write in the engine)
    assert slot.prefill_pos == 5
    alloc.check()


# ---------------------------------------------------------------------------
# the paged engine against the plain reference
# ---------------------------------------------------------------------------

def _cfg(**overrides):
    base = dict(head_pad=0, compute_dtype="float32", param_dtype="float32")
    base.update(overrides)
    return get_config("smollm-360m").reduced(**base)


def _mixed_requests(cfg, plens, gens):
    rng = np.random.default_rng(0)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size, n).tolist(),
                    max_new_tokens=g, sampling=SamplingParams(seed=i))
            for i, (n, g) in enumerate(zip(plens, gens))]


def _stepwise_greedy(params, cfg, prompt, n_new, max_seq):
    """Greedy tokens of one request decoded a position at a time through
    the model-level contiguous cache (``serve_step`` over
    ``init_cache``)."""
    step = jax.jit(lambda p, c, t, q: T.serve_step(p, c, {"tokens": t}, q,
                                                    cfg))
    cache, _ = T.init_cache(cfg, 1, max_seq)
    seq, out = list(prompt), []
    for pos in range(len(prompt) + n_new - 1):
        logits, cache = step(params, cache, jnp.asarray([[seq[pos]]]),
                             jnp.int32(pos))
        if pos >= len(prompt) - 1:
            out.append(int(np.argmax(logits[0])))
            seq.append(out[-1])
    return out


def test_paged_engine_matches_contiguous_mixed_lengths():
    """The acceptance-criteria workload: 8 requests over 4 slots, mixed
    prompt/gen lengths (several prompts span multiple prefill chunks),
    greedy sampling, with a pool SMALLER than full backing so admission
    really gates on pages and reclamation really recycles them: every
    served token is the reference's first choice."""
    m, cfg, params = R.smollm_small()
    mesh = make_mesh((1, 1), ("data", "model"))
    plens = [5, 19, 3, 26, 9, 14, 7, 22]
    gens = [6, 7, 8, 9, 10, 6, 7, 8]
    eng = Engine(cfg, mesh, max_seq=40, n_slots=4, page_size=8, n_pages=13,
                 prefill_chunk=8, params=params)
    reqs = _mixed_requests(cfg, plens, gens)
    out, stats = eng.serve(reqs)
    assert [len(out[i]) for i in range(8)] == gens
    R.assert_greedy("smollm-360m", params, m, reqs, out, 40)
    # prompts of 19/26/22 tokens took 3/4/3 chunks of 8 — prefill really
    # was chunked, not one monolithic call per prompt
    expected_chunks = sum(-(-n // 8) for n in plens)
    assert stats["prefill_chunks"] == expected_chunks
    assert stats["pages_capacity"] == 12


def test_paged_engine_int8_cache_variant():
    """The quantized-cache leaves (int8 rows + fp32 scales) go through
    the same generic gather/scatter.  The reference keeps no cache, so
    the tokens are held to stepwise greedy decode over the model-level
    int8 cache, which reads the same quantized rows."""
    _, cfg, params = R.smollm_small(kv_cache_dtype="int8")
    mesh = make_mesh((1, 1), ("data", "model"))
    plens, gens = [11, 4, 17, 6], [5, 6, 5, 6]
    eng = Engine(cfg, mesh, max_seq=32, n_slots=2, page_size=8,
                 prefill_chunk=8, params=params)
    reqs = _mixed_requests(cfg, plens, gens)
    out, _ = eng.serve(reqs)
    for r in reqs:
        assert out[r.uid] == _stepwise_greedy(
            params, cfg, r.prompt, r.max_new_tokens, 32)


def test_paged_engine_mla_cache_variant():
    """MLA latent caches (kv_lora + rope leaves instead of per-head K/V)
    page through the same generic gather/scatter: deepseek-v2-lite at
    small widths, its leading dense layer and held experts included,
    serves the reference's greedy tokens."""
    m, cfg, params = R.deepseek_small()
    mesh = make_mesh((1, 1), ("data", "model"))
    plens, gens = [11, 4, 17, 6], [5, 6, 5, 6]
    eng = Engine(cfg, mesh, max_seq=32, n_slots=2, page_size=8,
                 prefill_chunk=8, params=params)
    reqs = _mixed_requests(cfg, plens, gens)
    out, _ = eng.serve(reqs)
    R.assert_greedy("deepseek-v2-lite", params, m, reqs, out, 32)


def test_paged_serve_rejects_oversized_request():
    cfg = _cfg()
    mesh = make_mesh((1, 1), ("data", "model"))
    eng = Engine(cfg, mesh, max_seq=16, n_slots=2, page_size=8)
    with pytest.raises(ValueError, match="max_seq"):
        eng.serve([_req(0, 10, max_new=10)])  # 20 rows > max_seq 16


@pytest.mark.parametrize("option", [
    {"kv_layout": "ragged"}, {"kv_layout": "contiguous"},
    {"kv_policy": "reserve"}], ids=["ragged", "contiguous", "reserve"])
def test_engine_rejects_bad_layout(option):
    """One layout and one admission policy: any other value is refused,
    never quietly served paged."""
    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match=next(iter(option))):
        Engine(_cfg(), mesh, max_seq=16, **option)


def test_init_paged_cache_requires_attention_pattern():
    cfg = _cfg()
    cfg = dataclasses.replace(cfg, block_pattern=("mamba2",))
    with pytest.raises(NotImplementedError):
        T.init_paged_cache(cfg, n_pages=4, page_size=8)
