"""Continuous-batching inference engine over a paged KV cache.

The serving subsystem the paper's throughput claim lands on: weight
sparsity (CS-packed projections) and activation sparsity (k-WTA) both cut
per-token decode cost, and the batched-decode regime is where the two
multiply (cf. arXiv 2311.07625) — so the engine's job is to keep the
decode batch full.

Architecture:

  * ``Engine`` owns ``n_slots`` decode slots over a pool of fixed-size KV
    pages (:mod:`repro.runtime.kvcache`), and the compiled programs:
      - *prefill chunk* (:func:`repro.models.transformer.prefill_chunk`)
        — one page-aligned chunk of one prompt, its KV rows scattered
        into the slot's page chain; the true chunk length is a traced
        scalar, so every chunk shares one compile;
      - *decode step* (:func:`repro.models.transformer.serve_step` with
        ``pages``) — one token for ALL slots per call, with per-slot
        positions and page tables, so requests at different depths
        share every matmul;
      - *copy page* — the device half of a copy-on-write break.
  * ``repro.runtime.scheduler.Scheduler`` owns policy: FIFO admission on
    free slots and free pages, retirement on token budget / EOS, LRU
    preemption, and greedy or temperature/top-k sampling on host.

``Engine.serve(requests)`` runs the loop: admit -> one prefill chunk ->
retire -> grow chains -> decode-all-slots -> sample -> retire, until
queue and slots drain.  Admission charges a prompt's pages only, less
any prefix pages adopted from the allocator's hash index; decode pages
arrive one at a time as a slot crosses a page boundary, and when the
pool runs dry the youngest-admitted slot is preempted
(recompute-on-resume).  Requests sharing a prompt prefix share physical
pages, with copy-on-write on the first divergent write.

Telemetry: pass ``telemetry=repro.obs.Telemetry.on(...)`` and the engine
puts every host step of the loop in a named span (listed in
``src/repro/obs/README.md``), samples queue-depth and slot-occupancy
gauges each step, keeps per-request lifecycle records (scheduler-side),
attributes the staged execution paths
(``repro.core.api.observe_dispatch``), and — every
``telemetry.sparsity_every`` steps — decodes through a *probed* twin of
the step jit whose extra outputs are the per-layer k-WTA winner sets, so
realized activation sparsity and cross-step winner overlap are measured
from what actually ran.  ``Engine.metrics_snapshot()`` returns the whole
picture as a JSON-ready dict, live or at end of run.  With the default
``telemetry=None`` everything degrades to null objects and the staged
step program is bit-identical to the un-instrumented one.

Usage (published widths; add ``--reduced`` for the small CPU variant):
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
      --slots 4 --requests 8 --prompt-len 16 --gen 32
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import os
import time
from collections import deque
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.api import observe_dispatch
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.served_weights import served_params
from repro.models import transformer as T
from repro.models.common import dtype_of
from repro.obs import DispatchStats, SparsityStats, Telemetry
from repro.obs import sparsity as obs_sparsity
from repro.runtime.kvcache import (NULL_PAGE, BlockAllocator, PagedKV,
                                   prefix_keys)
from repro.runtime.scheduler import (Request, SamplingParams, Scheduler,
                                     sample_token)
from repro.sharding import make_rules, param_sharding, use_rules


class Engine:
    """Continuous-batching server for one model on one mesh.

    ``use_pallas`` overrides the kernel-executor flag on BOTH sparsity
    families (cfg.ffn_sparsity / cfg.proj_sparsity): 'auto' (Pallas on TPU
    only), 'force' (everywhere, interpret fallback off-TPU) or 'off' (pure
    jnp).  With the sparse-sparse config this is what routes the decode
    batch through the batched ``topk_gather`` kernel — one launch per
    sparse layer per decode step.

    The KV cache is a pool of ``n_pages`` pages of ``page_size`` rows
    (default: full backing, every slot able to hold ``max_seq`` rows);
    prompts prefill in chunks of ``prefill_chunk`` rows (a multiple of
    ``page_size``, default four pages).  ``kv_layout`` and ``kv_policy``
    accept only ``"paged"`` and ``"grow"``, the one layout and the one
    admission policy; any other value raises ``ValueError``.  Recurrent
    block patterns have no paged cache and are refused here.

    ``params`` stays the master tree; every compiled program is given
    ``served_params``, in which each weight the programs read only
    through a cast to the compute dtype has been cast once, here
    (:mod:`repro.launch.served_weights`)."""

    def __init__(self, cfg, mesh, max_seq: int, n_slots: int = 4,
                 params=None, use_pallas: Optional[str] = None,
                 telemetry: Optional[Telemetry] = None,
                 kv_layout: str = "paged", page_size: int = 16,
                 n_pages: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 kv_policy: str = "grow"):
        if kv_layout != "paged":
            raise ValueError(f"kv_layout must be 'paged', got {kv_layout!r}")
        if kv_policy != "grow":
            raise ValueError(f"kv_policy must be 'grow', got {kv_policy!r}")
        if use_pallas is not None:
            cfg = dataclasses.replace(
                cfg,
                ffn_sparsity=dataclasses.replace(
                    cfg.ffn_sparsity, use_pallas=use_pallas),
                proj_sparsity=dataclasses.replace(
                    cfg.proj_sparsity, use_pallas=use_pallas))
        self.cfg = cfg
        self.mesh = mesh
        self.max_seq = max_seq
        self.n_slots = n_slots
        self.kv_geo = geo = PagedKV.build(max_seq, n_slots,
                                          page_size=page_size,
                                          n_pages=n_pages)
        # the pool's shapes; a recurrent pattern is refused here, before
        # any weight is made
        cache = jax.eval_shape(lambda: T.init_paged_cache(
            cfg, geo.n_pages, geo.page_size)[0])
        # page-aligned chunk bucket: long prompts prefill in slabs of this
        # many rows, one slab per serve-loop iteration; the true chunk
        # length rides in as a traced scalar, so every chunk shares ONE
        # compile.
        self.prefill_chunk = (prefill_chunk if prefill_chunk is not None
                              else min(4 * geo.page_size, geo.view_len))
        geo.chunk_spans(1, self.prefill_chunk)  # validates
        self.rules = make_rules(mesh, "decode")
        with use_rules(self.rules):
            if params is None:
                params, specs = T.init_model(jax.random.PRNGKey(0), cfg)
                p_shard = param_sharding(specs, params, self.rules)
                params = jax.device_put(params, p_shard)
            #: the master tree, as given (float32 where the config says)
            self.params = params
        self.telemetry = telemetry if telemetry is not None \
            else Telemetry.off()
        self.prefill_calls = 0  # one per prefilled prompt (tests assert)
        # a config with experts also returns each row's routed pairs to
        # held experts (the ``moe.*`` counters)
        moe_counts = cfg.is_moe

        # Named functions, not lambdas: a device trace names each
        # program after its function (``jit_decode_step_paged``).
        def decode_step_paged(p, c, b, pos, pg):
            return T.serve_step(p, c, b, pos, cfg, pages=pg,
                                moe_counts=moe_counts)

        def prefill_chunk(p, c, toks, pg, start, ln):
            return T.prefill_chunk(p, c, {"tokens": toks}, start, ln,
                                   cfg, pg)

        # copy-on-write break: clone page src's rows onto dst in every
        # pool leaf (traced ids -> one compile, reused for every CoW)
        def copy_page(c, src, dst):
            return T.copy_cache_page(c, src, dst)

        def _probed_step_paged(p, c, b, pos, pg):
            # Twin of the decode step that also returns the per-layer
            # winner sets: the capture is active while serve_step TRACES,
            # so the supports (already computed by apply_kwta) leave the
            # scan as stacked extra outputs — no second top_k, no host
            # callback.
            with obs_sparsity.capture_supports() as cap:
                out = T.serve_step(p, c, b, pos, cfg, pages=pg,
                                   moe_counts=moe_counts)
            self._sparsity_meta.update(cap.meta)
            return (*out, cap.take_arrays())

        self._step_paged = jax.jit(decode_step_paged, donate_argnums=(1,))
        self._chunk_jit = jax.jit(prefill_chunk, donate_argnums=(1,))
        self._copy_page_jit = jax.jit(copy_page, donate_argnums=(0,))
        self._step_paged_probed = jax.jit(_probed_step_paged,
                                          donate_argnums=(1,))
        # -- telemetry ------------------------------------------------------
        self._sparsity = SparsityStats(self.telemetry.registry)
        self._dispatch = DispatchStats()
        self._last_sched: Optional[Scheduler] = None
        #: label -> {"d", "kind"} for the probed step's captured layers,
        #: filled at the probed jit's (one) trace.
        self._sparsity_meta: dict = {}
        # -- the served weights ---------------------------------------------
        # Each weight the programs read only through a cast to the compute
        # dtype is cast here, once, and every program is given this tree
        # (``served_weights`` says how the leaves are chosen).
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        n_pg = geo.blocks_per_slot
        staged = [(decode_step_paged, (cache, {"tokens": i32(n_slots, 1)},
                                       i32(n_slots), i32(n_slots, n_pg))),
                  (prefill_chunk, (cache, i32(1, self.prefill_chunk),
                                   i32(1, n_pg), i32(), i32()))]
        with use_rules(self.rules):
            self.served_params, cast, master = served_params(
                params, (jax.make_jaxpr(f)(params, *args)
                         for f, args in staged),
                dtype_of(cfg.compute_dtype))
        reg = self.telemetry.registry
        reg.gauge("engine.weights.cast_once_bytes", "bytes").set(cast)
        reg.gauge("engine.weights.kept_bytes", "bytes").set(master)

    # -- compiled pieces ----------------------------------------------------
    def new_paged_cache(self):
        """The page pools: leaves shaped (n_units, n_pages, page_size,
        ...), addressed through per-slot page tables."""
        geo = self.kv_geo
        with use_rules(self.rules):
            cache, specs = T.init_paged_cache(self.cfg, geo.n_pages,
                                              geo.page_size)
            shard = param_sharding(specs, cache, self.rules)
            return jax.device_put(cache, shard)

    def lower_decode_step(self):
        """Lower the decode step the serve loop runs at this engine's
        batch.  Its ``.compile().as_text()`` shows which kernels
        (``tpu_custom_call``) and collectives the served program holds."""
        with use_rules(self.rules):
            tokens = {"tokens": jnp.zeros((self.n_slots, 1), jnp.int32)}
            pos = jnp.zeros((self.n_slots,), jnp.int32)
            tables = jnp.asarray(self.kv_geo.empty_tables(self.n_slots))
            return self._step_paged.lower(self.served_params,
                                          self.new_paged_cache(), tokens,
                                          pos, tables)

    # -- continuous-batching loop -------------------------------------------
    def serve(self, requests: Sequence[Request]):
        """Run every request to completion with continuous batching.

        Returns (outputs, stats): outputs maps request uid -> generated
        token list; stats has tok/s, time-to-first-token per request,
        decode-step/prefill counts and the page pool's bookkeeping.

        * Admission is gated on FREE PAGES as well as free slots: the
          queue head takes only its PROMPT pages — minus any prefix
          pages adopted from the allocator's hash index — and decode
          pages arrive lazily: each iteration extends every decoding
          slot's chain (oldest-admitted first) to cover its next write,
          preempting the youngest-admitted slot when the pool is dry
          (recompute-on-resume; pre-validation of every request's
          worst case against the whole pool makes a sole survivor
          always able to finish, so eviction cannot livelock).
        * Writes into a page held by more than one chain break the
          sharing first: the allocator swaps in a private page and one
          compiled ``copy_page`` call clones the rows device-side
          (copy-on-write), so prefix sharing never changes any
          request's tokens.
        * A long prompt does not stall in-flight decode for its whole
          prefill: each iteration forwards at most ONE page-aligned
          chunk of the oldest prefilling slot, then decodes the slots
          whose prompts are fully cached — bounding the inter-token
          latency spike other requests see at admission.
        * The decode step receives the per-slot page tables; rows of
          slots that are free or still prefilling are nulled for the
          step, so their (ignored) writes sink into the null page
          instead of a live chain.

        ``REPRO_KV_CHECK=1`` runs ``alloc.check()`` every loop iteration
        (instead of only on drain) — the paranoid mode the fuzz harness
        serves under.
        """
        for r in requests:
            if r.max_new_tokens < 1:
                raise ValueError(f"request {r.uid}: max_new_tokens must "
                                 "be >= 1 (the first token comes from "
                                 "prefill)")
            if len(r.prompt) < 1:
                raise ValueError(f"request {r.uid}: prompt must hold at "
                                 "least one token (the first sampled "
                                 "token conditions on it)")
            if len(r.prompt) + r.max_new_tokens > self.max_seq:
                raise ValueError(
                    f"request {r.uid}: prompt {len(r.prompt)} + "
                    f"max_new {r.max_new_tokens} exceeds max_seq "
                    f"{self.max_seq}")
        tel = self.telemetry
        tracer = tel.tracer
        reg = tel.registry
        with tracer.span("serve.setup"):
            geo = self.kv_geo
            alloc = BlockAllocator(geo.n_pages, geo.page_size)
            for r in requests:
                need = alloc.pages_needed(len(r.prompt) + r.max_new_tokens)
                if need > alloc.capacity:
                    raise ValueError(
                        f"request {r.uid}: needs {need} KV pages, pool "
                        f"holds {alloc.capacity} — raise n_pages")
            paranoid = os.environ.get("REPRO_KV_CHECK") == "1"
            g_queue = reg.gauge("serve.queue_depth")
            g_active = reg.gauge("serve.slots_active")
            g_occ = reg.gauge("serve.slot_occupancy")
            h_step = reg.histogram("serve.decode_step_s")
            h_step_recent = reg.rolling_histogram(
                "serve.decode_step_recent_s")
            c_steps = reg.counter("serve.decode_steps")
            c_chunks = reg.counter("serve.prefill_chunks")
            c_cow = reg.counter("serve.cow_copies")
            c_grow = reg.counter("serve.kv_grow_pages")
            if self.cfg.is_moe:
                c_held = reg.counter("moe.held_assignments")
                c_moe_tokens = reg.counter("moe.tokens")
                moe_layers = self.cfg.n_units * sum(
                    k == "attn" for k in self.cfg.block_pattern)
            probe_every = tel.sparsity_every if tel.enabled else 0
            sched = Scheduler(self.n_slots, telemetry=tel, allocator=alloc)
            self._last_sched = sched
            sched.submit_many(requests, now=0.0)
            tables = geo.empty_tables(self.n_slots)
            chunk = self.prefill_chunk
            ps = geo.page_size
            n_chunks = 0
            n_cow = 0
            n_cow_inplace = 0
            n_grown = 0
            max_concurrent = 0
            prefillq: "deque" = deque()  # slots mid-prompt, FIFO
            cache = self.new_paged_cache()
            tokens = np.zeros((self.n_slots, 1), np.int32)
            pos = np.zeros((self.n_slots,), np.int32)

        def _evict(victim):
            """Preempt ``victim``: null its page table, drop it from the
            prefill queue, hand the request back to the scheduler
            (pages released, request re-queued at the head)."""
            geo.clear_chain(tables, victim.index)
            if victim in prefillq:
                prefillq.remove(victim)
            sched.preempt(victim, now=time.perf_counter() - t0)

        def _ensure_free(n, requester):
            """Free >= ``n`` pages by preempting youngest-admitted slots
            (least service lost, FIFO order preserved on requeue).
            Returns False when ``requester`` itself was the victim —
            the caller's slot is gone and its work this iteration is
            abandoned."""
            while alloc.free_pages < n:
                victim = sched.preemption_victim()
                if victim is None:
                    raise RuntimeError(
                        "KV pool exhausted with no slot to preempt")
                _evict(victim)
                if victim is requester:
                    return False
            return True

        def _cow(slot, blk):
            """Break sharing of chain page ``blk`` before ``slot``
            writes there.  Returns False when the slot lost its chain
            while freeing a page for the copy."""
            nonlocal cache, n_cow, n_cow_inplace
            uid = slot.request.uid
            if not alloc.page_shared(uid, blk):
                return True
            if alloc.free_pages < 1 and not _ensure_free(1, slot):
                return False
            cow = alloc.cow_page(uid, blk)
            if cow is None:
                # _ensure_free just preempted the page's only co-holder
                # (the youngest slot is typically the prefix-adopter):
                # the page is uniquely held now — write in place, no copy
                n_cow_inplace += 1
                return True
            old, new = cow
            with tracer.span("kv.cow", uid=uid, block=blk):
                cache = self._copy_page_jit(cache, jnp.int32(old),
                                            jnp.int32(new))
            geo.set_chain(tables, slot.index, alloc.chain(uid))
            n_cow += 1
            c_cow.inc()
            return True

        def _grow():
            """Grow every decoding slot's chain to cover its next write,
            oldest-admitted first (the youngest is the preemption victim,
            so growing oldest-first means a victim's freed pages go to
            the slots that keep running).  A slot evicted by an earlier
            _ensure_free in this very loop shows up as not busy — skip
            it."""
            nonlocal n_grown
            for slot in sorted(sched.decoding_slots(),
                               key=lambda s: s.admit_seq):
                if not slot.busy:
                    continue
                uid = slot.request.uid
                evicted = False
                while alloc.chain_len(uid) <= slot.pos // ps:
                    if alloc.free_pages < 1 and not _ensure_free(1, slot):
                        evicted = True
                        break
                    alloc.extend(uid, 1)
                    n_grown += 1
                    c_grow.inc()
                if evicted or not slot.busy:
                    continue
                # the write row may sit in a page adopted from a
                # prompt-prefix match: break the sharing first
                if not _cow(slot, slot.pos // ps):
                    continue
                geo.set_chain(tables, slot.index, alloc.chain(uid))

        def _prefill_next_chunk():
            """Forward one chunk of the oldest prefilling slot; after its
            last chunk, sample the request's first token."""
            nonlocal cache, n_chunks
            slot = prefillq[0]
            req = slot.request
            start = slot.prefill_pos
            ln = min(chunk, len(req.prompt) - start)
            with tracer.span("prefill.chunk", uid=req.uid, start=start,
                             chunk_len=ln):
                with tracer.span("prefill.inputs"):
                    # chunk rows may land in adopted prefix pages (an
                    # exact-duplicate prompt re-prefills its final token
                    # into the sharer's last page): break the sharing
                    # first.  _cow can preempt, including this very slot —
                    # then skip the chunk, the request is back in the
                    # queue.
                    if not all(_cow(slot, blk) for blk in range(
                            start // ps, (start + ln - 1) // ps + 1)):
                        return
                    buf = np.zeros((1, chunk), np.int32)
                    buf[0, :ln] = np.asarray(req.prompt[start:start + ln],
                                             np.int32)
                    chunk_in = (jnp.asarray(buf),
                                jnp.asarray(tables[slot.index:slot.index + 1]),
                                jnp.int32(start), jnp.int32(ln))
                # the jit call sits directly in prefill.chunk, with no
                # child span open (see the obs README on device labels)
                logits, cache = self._chunk_jit(self.served_params, cache,
                                                *chunk_in)
            c_chunks.inc()
            n_chunks += 1
            slot.prefill_pos += ln
            if slot.prefilling:
                return
            with tracer.span("prefill.fetch"):
                row = np.asarray(logits[0, ln - 1])
            prefillq.popleft()
            self.prefill_calls += 1
            reg.counter("serve.prefill_calls").inc()
            # rows are on device now — publish the prompt's pages for
            # later prefix matches
            with tracer.span("kv.prefix"):
                alloc.register_chain_prefix(req.uid,
                                            prefix_keys(req.prompt, ps))
            with tracer.span("sample"):
                first = sample_token(row, req.sampling, slot.rng)
                sched.record_token(slot, first, now=time.perf_counter() - t0)
                tokens[slot.index, 0] = first
                pos[slot.index] = slot.pos  # == len(prompt)

        with use_rules(self.rules):
            n_steps = 0
            t0 = time.perf_counter()
            while sched.has_work:
                with tracer.span("serve.iteration"):
                    if paranoid:
                        alloc.check()
                    with tracer.span("schedule.admit"):
                        admitted = sched.admit(now=time.perf_counter() - t0)
                        for slot in admitted:
                            self._sparsity.reset_row(slot.index)
                            geo.set_chain(tables, slot.index,
                                          alloc.chain(slot.request.uid))
                            prefillq.append(slot)
                        max_concurrent = max(max_concurrent,
                                             len(sched.active_slots()))
                    # ONE chunk per iteration: prefill progress is
                    # interleaved with decode so in-flight slots keep
                    # emitting tokens.
                    if prefillq:
                        _prefill_next_chunk()
                    # budget-1 requests finish at prefill
                    with tracer.span("retire"):
                        for slot in sched.retire_done(
                                now=time.perf_counter() - t0):
                            geo.clear_chain(tables, slot.index)
                    with tracer.span("kv.grow"):
                        _grow()
                    active = sched.decoding_slots()
                    g_queue.set(len(sched.queue))
                    g_active.set(len(active))
                    g_occ.set(len(active) / self.n_slots)
                    if not active:
                        continue
                    with tracer.span("kv.tables"):
                        # Null the page-table rows of slots sitting this step
                        # out (free, or mid-prefill): their stale token/pos
                        # rows still ride the batch, but their writes sink to
                        # the null page.
                        step_tables = tables.copy()
                        decoding = {s.index for s in active}
                        for i in range(self.n_slots):
                            if i not in decoding:
                                step_tables[i, :] = NULL_PAGE
                    obs_ctx = (observe_dispatch(self._dispatch.on_event)
                               if tel.enabled and not self._dispatch.sealed
                               else contextlib.nullcontext())
                    probed = probe_every > 0 and n_steps % probe_every == 0
                    t_step = time.perf_counter()
                    with tracer.span("decode.step", probed=probed), obs_ctx:
                        with tracer.span("decode.inputs"):
                            step_in = ({"tokens": jnp.asarray(tokens)},
                                       jnp.asarray(pos),
                                       jnp.asarray(step_tables))
                        # the jit call sits directly in decode.step, with
                        # no child span open
                        if probed:
                            logits, cache, *held, sp_aux = \
                                self._step_paged_probed(self.served_params,
                                                        cache, *step_in)
                        else:
                            logits, cache, *held = self._step_paged(
                                self.served_params, cache, *step_in)
                        with tracer.span("decode.fetch"):
                            logits = np.asarray(logits)
                            if held:
                                rows = [s.index for s in active]
                                c_held.inc(int(np.asarray(held[0])[rows]
                                               .sum()))
                                c_moe_tokens.inc(len(rows) * moe_layers)
                    self._dispatch.seal()
                    dt_step = time.perf_counter() - t_step
                    h_step.observe(dt_step)
                    h_step_recent.observe(dt_step)
                    c_steps.inc()
                    n_steps += 1
                    if probed:
                        self._sparsity.update(
                            sp_aux, self._sparsity_meta,
                            active_rows=[s.index for s in active])
                    now = time.perf_counter() - t0
                    with tracer.span("sample"):
                        for slot in active:
                            nxt = sample_token(logits[slot.index],
                                               slot.request.sampling, slot.rng)
                            sched.record_token(slot, nxt, now=now)
                            tokens[slot.index, 0] = nxt
                            slot.pos += 1
                            pos[slot.index] = slot.pos
                    with tracer.span("retire"):
                        for slot in sched.retire_done(
                                now=time.perf_counter() - t0):
                            geo.clear_chain(tables, slot.index)
            dt = time.perf_counter() - t0
        with tracer.span("serve.drain"):
            alloc.check()
            if alloc.used_pages:
                raise RuntimeError(f"{alloc.used_pages} KV pages still "
                                   "held after the queue drained")
            total = sum(len(v) for v in sched.finished.values())
            stats = {
                "wall_s": dt,
                "tok_s": total / dt if dt else float("inf"),
                "decode_steps": n_steps,
                "prefill_calls": self.prefill_calls,
                "prefill_chunks": n_chunks,
                "pages_capacity": alloc.capacity,
                "page_size": geo.page_size,
                "kv_policy": "grow",
                "max_concurrent": max_concurrent,
                "preemptions": sched.preemption_count,
                "prefix_hit_pages": sched.prefix_hit_pages,
                "cow_copies": n_cow,
                "cow_in_place": n_cow_inplace,
                "grown_pages": n_grown,
                "ttft_s": dict(sched.ttft),
            }
            if tel.enabled:
                tel.emit({"kind": "snapshot",
                          "metrics": self.metrics_snapshot()})
        return sched.finished, stats

    # -- telemetry read side -------------------------------------------------
    def metrics_snapshot(self) -> dict:
        """JSON-ready snapshot of everything the telemetry layer measured.

        Callable live (mid-``serve``) or at end of run:

        * ``metrics`` — registry counters/gauges/histograms (per-request
          TTFT and inter-token latency histograms, queue-depth and
          slot-occupancy gauges, stage latency histograms);
        * ``stages`` — prefill / decode.step / schedule.admit / sample
          wall-clock totals and span counts from the tracer;
        * ``requests`` — per-request lifecycle records
          (enqueue/admit/first-token/finish times, token counts, ITL
          aggregates) keyed by uid.  The table covers EVERY submitted
          request: ones still queued or decoding at snapshot time appear
          with ``status`` "queued"/"in_flight" and partial timings, not
          silently dropped;
        * ``sparsity`` — per-layer realized k/N and cross-step winner
          overlap from the probed decode steps, plus the staged
          execution paths (topk/hadamard/dense × backend, sites each).
        """
        stages = self.telemetry.tracer.totals()
        requests = {}
        if self._last_sched is not None:
            requests = {uid: rec.to_event()
                        for uid, rec in self._last_sched.records.items()}
        return {
            "enabled": self.telemetry.enabled,
            "metrics": self.telemetry.registry.snapshot(),
            "stages": stages,
            "requests": requests,
            "sparsity": {
                "layers": self._sparsity.summary(),
                "paths": self._dispatch.summary(),
                "probe_steps": self._sparsity.probes,
            },
        }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--reduced", action="store_true",
                    help="serve the config's small same-family variant "
                    "(CPU smoke runs) instead of its published widths")
    ap.add_argument("--use-pallas", choices=("auto", "force", "off"),
                    default=None,
                    help="kernel executor override for the sparse paths "
                    "(default: the config's own setting)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="token rows per KV page")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="KV pool size in pages (default: full backing, "
                    "slots*ceil(max_seq/page_size)+1)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prefill chunk rows, multiple of page-size "
                    "(default: 4 pages)")
    ap.add_argument("--telemetry", action="store_true",
                    help="enable runtime telemetry (repro.obs) and print "
                    "a metrics snapshot at end of run")
    ap.add_argument("--telemetry-jsonl", default=None, metavar="PATH",
                    help="stream telemetry events to PATH as JSON lines "
                    "(implies --telemetry)")
    args = ap.parse_args()

    setup_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    dims = tuple(int(x) for x in args.mesh.split("x"))
    mesh = make_mesh(dims, ("data", "model"))
    telemetry = None
    if args.telemetry or args.telemetry_jsonl:
        telemetry = Telemetry.on(jsonl_path=args.telemetry_jsonl)
    engine = Engine(cfg, mesh, max_seq=args.prompt_len + args.gen + 1,
                    n_slots=args.slots, use_pallas=args.use_pallas,
                    telemetry=telemetry, page_size=args.page_size,
                    n_pages=args.n_pages, prefill_chunk=args.prefill_chunk)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        args.prompt_len).tolist(),
                    max_new_tokens=args.gen,
                    sampling=SamplingParams(temperature=args.temperature,
                                            top_k=args.top_k, seed=i))
            for i in range(args.requests)]
    out, stats = engine.serve(reqs)
    print(f"served {len(out)} requests, {stats['decode_steps']} decode "
          f"steps, {stats['prefill_calls']} prefill calls, "
          f"{stats['tok_s']:.1f} tok/s; sample: {out[0][:16]}")
    if telemetry is not None:
        import json as _json
        print(_json.dumps(engine.metrics_snapshot(), indent=2,
                          sort_keys=True))
        telemetry.close()


if __name__ == "__main__":
    main()
