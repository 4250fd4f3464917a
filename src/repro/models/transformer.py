"""Decoder LM assembled from config-driven blocks.

The layer stack is a ``lax.scan`` over *superblocks* (one repetition of
``cfg.block_pattern``) with stacked params — compile time and HLO size stay
O(pattern), not O(n_layers).  Heterogeneous stacks (xLSTM's mLSTM+sLSTM,
zamba2's mamba2+shared-attention) are expressed inside the pattern;
zamba2's weight-shared attention block lives *outside* the scanned params
(a closure constant — the same weights at every invocation, which is
exactly the Zamba trick).

Block kinds:
  attn        — (MLA when cfg.use_mla) attention + FFN or MoE, pre-norm.
  mamba2      — Mamba-2 mixer (chunked SSD).
  mlstm/slstm — xLSTM mixers.
  shared_attn — weight-shared attention + FFN block (zamba2).

Entry points:
  :func:`loss_fn` / :func:`forward` — training (full sequence).
  :func:`serve_step` + :func:`init_paged_cache`, :func:`prefill_chunk` —
  what the serving engine runs: paged decode and chunked prefill
  (attention-only block patterns).
  :func:`serve_step` + :func:`init_cache` — one-token decode over a
  contiguous per-batch-row cache (KV for attention; O(1) state for SSM
  blocks, which have no paged cache yet).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.obs import sparsity as obs_sparsity
from repro.sharding.context import constrain, is_spec as _is_spec
from . import attention as A
from . import ssm as S
from .common import (cross_entropy, dtype_of, embedding_init, rmsnorm_apply,
                     rmsnorm_init)
from .ffn import ffn_apply, ffn_init
from .moe import moe_apply, moe_init


# ---------------------------------------------------------------------------
# Block init/apply/decode dispatch
# ---------------------------------------------------------------------------

def _block_init(kind: str, key, cfg, dense_d_ff: int = 0):
    """One block's params; ``dense_d_ff`` gives an attention block a dense
    FFN of that width in place of the config's FFN or expert layer (the
    leading dense layers)."""
    ks = jax.random.split(key, 4)
    if kind in ("attn", "shared_attn"):
        p, s = {}, {}
        p["norm1"], s["norm1"] = rmsnorm_init(cfg.d_model)
        if cfg.use_mla:
            p["mixer"], s["mixer"] = A.mla_init(ks[0], cfg)
        else:
            p["mixer"], s["mixer"] = A.gqa_init(ks[0], cfg)
        p["norm2"], s["norm2"] = rmsnorm_init(cfg.d_model)
        if dense_d_ff:
            p["ffn"], s["ffn"] = ffn_init(ks[1], cfg.d_model, dense_d_ff,
                                          cfg.ffn_sparsity, cfg.act)
        elif cfg.is_moe and kind == "attn":
            p["moe"], s["moe"] = moe_init(ks[1], cfg.d_model, cfg.d_ff,
                                          cfg.n_experts, cfg.n_shared_experts,
                                          cfg.act, cfg.ffn_sparsity,
                                          n_held=cfg.n_held_experts)
        elif cfg.d_ff > 0:
            p["ffn"], s["ffn"] = ffn_init(ks[1], cfg.d_model, cfg.d_ff,
                                          cfg.ffn_sparsity, cfg.act)
        return p, s
    if kind == "mamba2":
        p, s = {}, {}
        p["norm"], s["norm"] = rmsnorm_init(cfg.d_model)
        p["mixer"], s["mixer"] = S.mamba2_init(ks[0], cfg)
        return p, s
    if kind == "mlstm":
        p, s = {}, {}
        p["norm"], s["norm"] = rmsnorm_init(cfg.d_model)
        p["mixer"], s["mixer"] = S.mlstm_init(ks[0], cfg)
        return p, s
    if kind == "slstm":
        p, s = {}, {}
        p["norm"], s["norm"] = rmsnorm_init(cfg.d_model)
        p["mixer"], s["mixer"] = S.slstm_init(ks[0], cfg)
        return p, s
    raise ValueError(f"unknown block kind {kind}")


def _block_apply(kind: str, params, x, cfg, positions):
    """Full-sequence forward. Returns (x, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "shared_attn"):
        h = rmsnorm_apply(params["norm1"], x, cfg.norm_eps)
        if cfg.use_mla:
            h = A.mla_apply(params["mixer"], h, cfg, positions)
        else:
            h = A.gqa_apply(params["mixer"], h, cfg, positions)
        x = x + h
        h = rmsnorm_apply(params["norm2"], x, cfg.norm_eps)
        if "moe" in params:
            h, aux, _ = moe_apply(params["moe"], h, cfg, cfg.ffn_sparsity)
            x = x + h
        elif "ffn" in params:
            x = x + ffn_apply(params["ffn"], h, cfg.ffn_sparsity, cfg.act)
        return x, aux
    h = rmsnorm_apply(params["norm"], x, cfg.norm_eps)
    mixer = {"mamba2": S.mamba2_apply, "mlstm": S.mlstm_apply,
             "slstm": S.slstm_apply}[kind]
    return x + mixer(params["mixer"], h, cfg), aux


def _block_cache_init(kind: str, cfg, batch: int, max_seq: int, dtype):
    if kind in ("attn", "shared_attn"):
        if cfg.use_mla:
            return A.mla_cache_init(cfg, batch, max_seq, dtype), \
                A.mla_cache_specs()
        return A.gqa_cache_init(cfg, batch, max_seq, dtype), \
            A.gqa_cache_specs(cfg)
    init = {"mamba2": S.mamba2_cache_init, "mlstm": S.mlstm_cache_init,
            "slstm": S.slstm_cache_init}[kind]
    specs = {"mamba2": S.mamba2_cache_specs, "mlstm": S.mlstm_cache_specs,
             "slstm": S.slstm_cache_specs}[kind]
    return init(cfg, batch, dtype), specs()


def _block_decode(kind: str, params, x, cfg, cache, pos, pages=None):
    """One-token step. Returns (x, new_cache, held): ``held`` is an
    expert block's (B, 1) count of each row's routed pairs that went to
    an expert held here, and ``()`` for every other block (no leaf).
    ``pages`` (the paged KV layout's per-slot page table) is
    attention-only: SSM blocks keep O(1) recurrence state and have no
    per-position rows to page."""
    if kind in ("attn", "shared_attn"):
        h = rmsnorm_apply(params["norm1"], x, cfg.norm_eps)
        dec = A.mla_decode if cfg.use_mla else A.gqa_decode
        h, new_cache = dec(params["mixer"], h, cfg, cache, pos,
                           pages=pages)
        x = x + h
        h = rmsnorm_apply(params["norm2"], x, cfg.norm_eps)
        held = ()
        if "moe" in params:
            h, _, held = moe_apply(params["moe"], h, cfg, cfg.ffn_sparsity)
            x = x + h
        elif "ffn" in params:
            x = x + ffn_apply(params["ffn"], h, cfg.ffn_sparsity, cfg.act)
        return x, new_cache, held
    if pages is not None:
        raise NotImplementedError(
            f"paged KV layout not implemented for block kind {kind!r} "
            "(SSM decode state has no sequence axis to page)")
    h = rmsnorm_apply(params["norm"], x, cfg.norm_eps)
    dec = {"mamba2": S.mamba2_decode, "mlstm": S.mlstm_decode,
           "slstm": S.slstm_decode}[kind]
    h, new_cache = dec(params["mixer"], h, cfg, cache, pos)
    return x + h, new_cache, ()


# ---------------------------------------------------------------------------
# Model init
# ---------------------------------------------------------------------------

#: Salt of the leading dense layers' key (the other params' keys are
#: unchanged by their presence).
LEAD_KEY = 0x1EAD


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _repeat(tree, n: int):
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (n, *x.shape)), tree)


def _stack_specs(spec):
    return jax.tree.map(lambda sp: (None,) + tuple(sp), spec,
                        is_leaf=_is_spec)


def _lead_init(key, cfg):
    """The leading dense layers (``cfg.n_dense_layers`` attention blocks
    with a dense FFN of width ``cfg.dense_d_ff``), stacked on a leading
    layer axis like the scanned units but applied outside the scan."""
    ps = [_block_init("attn", k, cfg, dense_d_ff=cfg.dense_d_ff)
          for k in jax.random.split(key, cfg.n_dense_layers)]
    return _stack([p for p, _ in ps]), _stack_specs(ps[0][1])


def _lead_layers(tree):
    """The per-layer slices of a stacked ``lead`` tree (params or
    cache); empty when the model has no leading dense layer."""
    if tree is None:
        return []
    n = jax.tree.leaves(tree)[0].shape[0]
    return [jax.tree.map(lambda a, i=i: a[i], tree) for i in range(n)]


def _split_cache(cache):
    """(the leading layers' cache or None, the scanned units' cache)."""
    units = {k: v for k, v in cache.items() if k != "lead"}
    return cache.get("lead"), units


def init_model(key, cfg) -> Tuple[Dict, Dict]:
    """Returns (params, specs).  params["units"] leaves have leading dim
    n_units (scanned); params["lead"] (if any) holds the leading dense
    layers, stacked, applied before the scan; params["shared"] (if any)
    is the zamba2 shared block."""
    keys = jax.random.split(key, cfg.n_units + 3)
    params: Dict[str, Any] = {}
    specs: Dict[str, Any] = {}
    params["embed"], specs["embed"] = embedding_init(
        keys[0], cfg.padded_vocab, cfg.d_model)

    has_shared = "shared_attn" in cfg.block_pattern
    if has_shared:
        params["shared"], specs["shared"] = _block_init("shared_attn",
                                                        keys[1], cfg)

    def unit_init(key):
        ks = jax.random.split(key, len(cfg.block_pattern))
        p, s = {}, {}
        for i, kind in enumerate(cfg.block_pattern):
            if kind == "shared_attn":
                continue  # weights live in params["shared"]
            p[f"b{i}"], s[f"b{i}"] = _block_init(kind, ks[i], cfg)
        return p, s

    if cfg.n_dense_layers:
        params["lead"], specs["lead"] = _lead_init(
            jax.random.fold_in(key, LEAD_KEY), cfg)

    unit_ps = [unit_init(keys[2 + u]) for u in range(cfg.n_units)]
    params["units"] = _stack([p for p, _ in unit_ps])
    # specs: identical across units; prepend the (unsharded) layer axis
    specs["units"] = _stack_specs(unit_ps[0][1])

    params["final_norm"], specs["final_norm"] = rmsnorm_init(cfg.d_model)
    if not cfg.tie_embeddings:
        from .common import normal_init
        params["head"] = {"table": normal_init(keys[-1],
                                               (cfg.padded_vocab, cfg.d_model),
                                               0.02)}
        specs["head"] = {"table": ("vocab", "embed")}
    return params, specs


def param_count(params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------

def _embed_inputs(params, batch, cfg, ct):
    """Token/frontend embedding. Returns (x, loss_mask)."""
    if cfg.frontend == "embed":
        x = batch["embeds"].astype(ct)  # (B, S, D) precomputed (stub)
        mask = None
    elif cfg.frontend == "vision_prefix":
        tok = jnp.take(params["embed"]["table"].astype(ct),
                       batch["tokens"], axis=0)
        x = jnp.concatenate([batch["patch_embeds"].astype(ct), tok], axis=1)
        mask = jnp.concatenate(
            [jnp.zeros(batch["patch_embeds"].shape[:2], bool),
             jnp.ones(batch["tokens"].shape, bool)], axis=1)
    else:
        x = jnp.take(params["embed"]["table"].astype(ct),
                     batch["tokens"], axis=0)
        mask = None
    return constrain(x, "batch", "seq", None), mask


def forward(params, batch, cfg) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward. Returns (logits, aux_loss)."""
    ct = dtype_of(cfg.compute_dtype)
    x, _ = _embed_inputs(params, batch, cfg, ct)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    shared = params.get("shared")
    for i, p in enumerate(_lead_layers(params.get("lead"))):
        with jax.named_scope(f"lead{i}"):
            x, _ = _block_apply("attn", p, x, cfg, positions)

    def unit_fn(carry, unit_params):
        x, aux = carry
        for i, kind in enumerate(cfg.block_pattern):
            p = shared if kind == "shared_attn" else unit_params[f"b{i}"]
            apply = lambda p, x, k=kind: _block_apply(k, p, x, cfg, positions)
            if cfg.remat:
                # block-granular remat: backward holds at most one block's
                # intermediates (the scan carry is the remat stack)
                apply = jax.checkpoint(apply)
            with jax.named_scope(f"b{i}_{kind}"):
                x, a = apply(p, x)
            aux = aux + a
        x = constrain(x, "batch", "seq", None)
        return (x, aux), None

    (x, aux), _ = lax.scan(unit_fn, (x, jnp.zeros((), jnp.float32)),
                           params["units"])
    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    table = (params["embed"] if cfg.tie_embeddings else params["head"])["table"]
    logits = x @ table.astype(ct).T
    return constrain(logits, "batch", "seq", "vocab"), aux


def loss_fn(params, batch, cfg):
    """Next-token LM loss. batch: tokens/embeds (+ labels)."""
    logits, aux = forward(params, batch, cfg)
    labels = batch["labels"]
    if cfg.frontend == "vision_prefix":
        # logits cover [prefix + text]; predict text tokens only
        n_pre = batch["patch_embeds"].shape[1]
        logits = logits[:, n_pre:]
    lm = cross_entropy(logits[:, :-1], labels[:, 1:])
    loss = lm + cfg.router_aux_weight * aux
    return loss, {"loss": loss, "lm_loss": lm, "aux_loss": aux}


# ---------------------------------------------------------------------------
# Serving (one-token decode with caches)
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_seq: int):
    """Stacked per-unit caches: each leaf has leading dim n_units."""
    ct = dtype_of(cfg.compute_dtype)
    unit_cache, unit_specs = {}, {}
    for i, kind in enumerate(cfg.block_pattern):
        c, sp = _block_cache_init(kind, cfg, batch, max_seq, ct)
        unit_cache[f"b{i}"], unit_specs[f"b{i}"] = c, sp
    cache, specs = _repeat(unit_cache, cfg.n_units), _stack_specs(unit_specs)
    if cfg.n_dense_layers:
        c, sp = _block_cache_init("attn", cfg, batch, max_seq, ct)
        cache["lead"] = _repeat(c, cfg.n_dense_layers)
        specs["lead"] = _stack_specs(sp)
    return cache, specs


def init_paged_cache(cfg, n_pages: int, page_size: int):
    """Stacked per-unit PAGED caches: every attention leaf is a page
    pool ``(n_units, n_pages, page_size, ...)`` addressed through the
    per-slot page tables that :func:`serve_step` / :func:`prefill_chunk`
    take as ``pages`` (see :mod:`repro.runtime.kvcache`).  Pool geometry
    replaces the contiguous ``(batch, kvseq)`` axes, so the same
    per-block inits produce the leaves; the sharding spec replicates the
    pool axes (pages are not sharded — page ids must stay global).

    Attention-only block patterns (paged layout pages per-position KV
    rows; SSM decode state is O(1) and has nothing to page)."""
    if not all(k in ("attn", "shared_attn") for k in cfg.block_pattern):
        raise NotImplementedError(
            "paged KV layout requires an attention-only block pattern, "
            f"got {cfg.block_pattern}")
    ct = dtype_of(cfg.compute_dtype)
    unit_cache, unit_specs = {}, {}
    for i, kind in enumerate(cfg.block_pattern):
        c, sp = _block_cache_init(kind, cfg, n_pages, page_size, ct)
        unit_cache[f"b{i}"] = c
        unit_specs[f"b{i}"] = jax.tree.map(
            lambda s: (None, None) + tuple(s)[2:], sp, is_leaf=_is_spec)
    cache, specs = _repeat(unit_cache, cfg.n_units), _stack_specs(unit_specs)
    if cfg.n_dense_layers:
        # the leading layers' own pool leaves, stacked over those layers:
        # the same page ids address them, so copy-on-write and prefix
        # sharing cover them with the scanned leaves
        c, sp = _block_cache_init("attn", cfg, n_pages, page_size, ct)
        cache["lead"] = _repeat(c, cfg.n_dense_layers)
        specs["lead"] = _stack_specs(jax.tree.map(
            lambda s: (None, None) + tuple(s)[2:], sp, is_leaf=_is_spec))
    return cache, specs


def copy_cache_page(cache, src, dst):
    """Copy physical page ``src``'s rows over page ``dst`` in every pool
    leaf of an :func:`init_paged_cache` cache — the device half of a
    copy-on-write break (the allocator already swapped ``dst`` into the
    writer's chain; this materialises the shared rows there before the
    writer's next scatter lands).  src/dst: scalar int32 page ids; leaf
    layout ``(n_units, n_pages, page_size, ...)``, or ``(n_dense_layers,
    ...)`` for the leading layers' leaves."""
    return jax.tree.map(lambda leaf: leaf.at[:, dst].set(leaf[:, src]),
                        cache)


def serve_step(params, cache, batch, pos, cfg, pages=None,
               moe_counts: bool = False):
    """Decode one token given caches of past state.

    batch: {"tokens": (B, 1)} (or {"embeds": (B, 1, D)}).
    pos: scalar position (static batch) or (B,) per-slot positions
    (continuous batching).
    pages: optional (B, n_blocks) int32 per-slot page tables — the cache
    leaves are then the :func:`init_paged_cache` pools and every
    attention read/write goes through the page indirection (same math,
    same mask; token-exact vs the contiguous layout).
    Returns (logits (B, vocab), new_cache); with ``moe_counts`` (a
    config with experts) also each row's routed pairs, over every expert
    layer, that went to an expert held here, (B,) int32.

    Sparse-sparse decode runs the fused pipeline per layer: the FFN's
    k-WTA Select hands its (vals, idx) support straight to the down
    projection (one top_k per sparse layer), which contracts the whole
    decode batch in one ``topk_gather`` launch when the executor
    (``cfg.ffn_sparsity.use_pallas``) engages the Pallas path.
    """
    ct = dtype_of(cfg.compute_dtype)
    if cfg.frontend == "embed":
        x = batch["embeds"].astype(ct)
    else:
        x = jnp.take(params["embed"]["table"].astype(ct), batch["tokens"],
                     axis=0)
    shared = params.get("shared")
    lead_cache, cache = _split_cache(cache)
    lead_new = []
    for i, (p, c) in enumerate(zip(_lead_layers(params.get("lead")),
                                   _lead_layers(lead_cache))):
        with jax.named_scope(f"lead{i}"):
            x, c, _ = _block_decode("attn", p, x, cfg, c, pos, pages)
        lead_new.append(c)

    def unit_fn(x, scanned):
        unit_params, unit_cache = scanned
        new_cache, held = {}, []
        for i, kind in enumerate(cfg.block_pattern):
            p = shared if kind == "shared_attn" else unit_params[f"b{i}"]
            with jax.named_scope(f"b{i}_{kind}"), \
                    obs_sparsity.observe_site(f"b{i}"):
                x, new_cache[f"b{i}"], h = _block_decode(
                    kind, p, x, cfg, unit_cache[f"b{i}"], pos, pages)
            if moe_counts and not isinstance(h, tuple):
                held.append(h[:, 0])
        # Realized-sparsity capture handoff: when the serving engine's
        # probed step is tracing, the winner sets observed inside this
        # body leave the scan as stacked (n_units, ...) outputs.  With no
        # active capture this is the empty tuple — zero extra leaves, the
        # staged jaxpr is unchanged (asserted by tests/test_obs.py).  The
        # held counts are an output only when asked for.
        held = (sum(held),) if held else ()
        return x, (new_cache, obs_sparsity.drain_pending(), held)

    x, (new_cache, sparsity_aux, held) = lax.scan(
        unit_fn, x, (params["units"], cache))
    obs_sparsity.emit_stacked(sparsity_aux)
    if lead_new:
        new_cache["lead"] = _stack(lead_new)
    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    table = (params["embed"] if cfg.tie_embeddings else params["head"])["table"]
    logits = constrain((x @ table.astype(ct).T)[:, 0], "batch", "vocab")
    if moe_counts:
        rows = jnp.sum(held[0], axis=0) if held else jnp.zeros(
            x.shape[:1], jnp.int32)
        return logits, new_cache, rows
    return logits, new_cache


def _block_chunk_prefill(kind: str, params, x, cfg, cache, pages,
                         pos_start, chunk_len):
    """Chunked-prefill step of one block over the paged cache.
    Returns (x, new_cache)."""
    if kind not in ("attn", "shared_attn"):
        raise NotImplementedError(
            f"chunked prefill not implemented for block kind {kind!r}")
    h = rmsnorm_apply(params["norm1"], x, cfg.norm_eps)
    pre = A.mla_chunk_prefill if cfg.use_mla else A.gqa_chunk_prefill
    h, new_cache = pre(params["mixer"], h, cfg, cache, pages, pos_start,
                       chunk_len)
    x = x + h
    h = rmsnorm_apply(params["norm2"], x, cfg.norm_eps)
    if "moe" in params:
        h, _, _ = moe_apply(params["moe"], h, cfg, cfg.ffn_sparsity)
        x = x + h
    elif "ffn" in params:
        x = x + ffn_apply(params["ffn"], h, cfg.ffn_sparsity, cfg.act)
    return x, new_cache


def prefill_chunk(params, cache, batch, pos_start, chunk_len, cfg, pages):
    """Forward ONE page-aligned prompt chunk of ONE slot through every
    block, scattering its KV rows into the slot's page chains (the paged
    layout's incremental prefill — long prompts run as a sequence of
    these interleaved with decode steps).

    batch: {"tokens": (1, C)}; pages: (1, n_blocks) int32 — the
    prefilling slot's page table; pos_start / chunk_len: traced scalars,
    so chunks of any true length share one compile per C bucket (rows
    past ``chunk_len`` are bucket padding: their KV sinks to the null
    page and their logits are garbage the engine ignores).
    Returns (logits (1, C, vocab), new_cache) with pool-shaped leaves.
    """
    ct = dtype_of(cfg.compute_dtype)
    if cfg.frontend == "embed":
        x = batch["embeds"].astype(ct)
    else:
        x = jnp.take(params["embed"]["table"].astype(ct), batch["tokens"],
                     axis=0)
    shared = params.get("shared")
    lead_cache, cache = _split_cache(cache)
    lead_new = []
    for i, (p, c) in enumerate(zip(_lead_layers(params.get("lead")),
                                   _lead_layers(lead_cache))):
        with jax.named_scope(f"lead{i}"):
            x, c = _block_chunk_prefill("attn", p, x, cfg, c, pages,
                                        pos_start, chunk_len)
        lead_new.append(c)

    def unit_fn(x, scanned):
        unit_params, unit_cache = scanned
        new_cache = {}
        for i, kind in enumerate(cfg.block_pattern):
            p = shared if kind == "shared_attn" else unit_params[f"b{i}"]
            with jax.named_scope(f"b{i}_{kind}"), \
                    obs_sparsity.observe_site(f"b{i}"):
                x, new_cache[f"b{i}"] = _block_chunk_prefill(
                    kind, p, x, cfg, unit_cache[f"b{i}"], pages,
                    pos_start, chunk_len)
        # Same capture handoff as serve_step (empty tuple when inactive).
        return x, (new_cache, obs_sparsity.drain_pending())

    x, (new_cache, sparsity_aux) = lax.scan(unit_fn, x,
                                            (params["units"], cache))
    obs_sparsity.emit_stacked(sparsity_aux)
    if lead_new:
        new_cache["lead"] = _stack(lead_new)
    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    table = (params["embed"] if cfg.tie_embeddings else params["head"])["table"]
    logits = x @ table.astype(ct).T
    return constrain(logits, "batch", "seq", "vocab"), new_cache


def unit_step_fn(cfg):
    """A single-superblock forward for per-layer cost accounting (the
    roofline reads FLOPs from this, times n_units — lax.scan bodies are
    counted once by XLA's cost analysis; see launch/roofline.py)."""

    def fn(unit_params, shared, x, positions):
        aux = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(cfg.block_pattern):
            p = shared if kind == "shared_attn" else unit_params[f"b{i}"]
            with jax.named_scope(f"b{i}_{kind}"):
                x, a = _block_apply(kind, p, x, cfg, positions)
            aux += a
        return x, aux

    return fn
