"""Attention blocks: GQA with RoPE (+ blockwise 'flash' softmax for long
prefill), MLA (DeepSeek-V2 latent compression), and KV-cache decode steps.

Conventions:
  x          (B, S, D)
  kv cache   {"k": (B, Smax, Hkv, Dh), "v": ..., } + position carried by the
             caller; cache seq axis uses logical axis "kvseq" (SP, §6).
  Projections may be complementary-sparse (cfg.proj_sparsity) — the paper's
  §6.4 'apply Complementary Sparsity to Transformers'.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.api import SparsityConfig
from repro.core.layers import (apply_kwta, linear_apply, linear_init,
                               packed_linear_apply, packed_linear_init)
from repro.obs.sparsity import observe_site
from repro.runtime.kvcache import paged_view, paged_write_chunk, \
    paged_write_rows
from repro.sharding.context import constrain
from .common import (apply_rope, normal_init, rmsnorm_apply, rmsnorm_init,
                     yarn_freqs, yarn_mscale)


def _proj_init(key, d_in, d_out, sp: SparsityConfig, out_axis, name_seed):
    """Dense or CS-packed projection depending on cfg.proj_sparsity."""
    if sp.weight_sparse and d_in % sp.n == 0 and d_out % sp.n == 0:
        return packed_linear_init(key, d_in, d_out, sp, bias=False,
                                  seed=name_seed, out_axis=out_axis)
    p, s = linear_init(key, d_in, d_out, bias=False, out_axis=out_axis)
    return p, s


def _proj_apply(params, x, sp: SparsityConfig, x_is_sparse=False,
                support=None):
    if "packed" in params:
        return packed_linear_apply(params, x, sp, x_is_sparse=x_is_sparse,
                                   support=support)
    return linear_apply(params, x)


def _o_proj(params, out_flat, sp: SparsityConfig):
    """Output projection with the sparse-activation handoff: when the
    projection family is activation-sparse (cfg.proj_sparsity.k_frac), the
    attention output goes through k-WTA and its winner support is handed to
    the CS-packed o-projection — the same one-Select-per-layer pipeline as
    the FFN down projection (paper Fig. 8a applied to §6.4's Transformer
    projections)."""
    with jax.named_scope("o_proj"), observe_site("o_proj"):
        if sp.activation_sparse:
            out_flat, support = apply_kwta(out_flat, sp, return_support=True)
            return _proj_apply(params, out_flat, sp, x_is_sparse=True,
                               support=support)
        return _proj_apply(params, out_flat, sp)


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------

def gqa_init(key, cfg):
    h, hkv, dh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    hp = cfg.padded_heads
    ks = jax.random.split(key, 4)
    sp = cfg.proj_sparsity
    q, qs = _proj_init(ks[0], d, h * dh, sp, "heads", 11)
    k, ks_ = _proj_init(ks[1], d, hkv * dh, sp, "kv", 12)
    v, vs = _proj_init(ks[2], d, hkv * dh, sp, "kv", 13)
    # o-proj rows for padded dummy heads exist but only ever see zeros
    o, os_ = _proj_init(ks[3], hp * dh, d, sp, "embed", 14)
    return ({"q": q, "k": k, "v": v, "o": o},
            {"q": qs, "k": ks_, "v": vs, "o": os_})


def _split_heads(x, n, dh):
    return x.reshape(*x.shape[:-1], n, dh)


def _repeat_kv(k, n_rep):
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=-2)


def _pad_heads(x, h_pad):
    """Pad the head axis (-2) with zero heads up to h_pad (TP
    divisibility; DESIGN.md §6). GQA grouping is preserved because padding
    happens *after* the kv repeat."""
    h = x.shape[-2]
    if h_pad <= h:
        return x
    pad = [(0, 0)] * x.ndim
    pad[-2] = (0, h_pad - h)
    return jnp.pad(x, pad)


def _mask_dummy_heads(out, cfg):
    """Zero the padded heads' outputs so the o-projection sees the exact
    n_heads function (dummy heads attend uniformly — must not leak)."""
    h, hp = cfg.n_heads, cfg.padded_heads
    if hp == h:
        return out
    mask = (jnp.arange(hp) < h).astype(out.dtype)
    return out * mask[..., :, None]


def _causal_attn(q, k, v, scale):
    """Materialized causal attention (short seq). q/k/v: (B, S, H, Dh)."""
    s_q, s_k = q.shape[1], k.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    mask = np.tril(np.ones((s_q, s_k), bool), k=s_k - s_q)
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _flash_attn(q, k, v, scale, block: int, unroll: bool = False):
    """Blockwise (online-softmax) causal attention: O(S·block) memory.

    Scans over KV chunks carrying (acc, row_max, row_sum). Used whenever
    S_kv exceeds `block` (32k prefill would otherwise materialize an
    S², per-head score tensor).
    """
    b, s_q, h, dh = q.shape
    dv = v.shape[-1]  # may differ from dh (MLA: rope-extended queries)
    s_k = k.shape[1]
    nblk = s_k // block
    q32 = q.astype(jnp.float32) * scale
    q_pos = jnp.arange(s_q)

    @jax.checkpoint  # flash-style backward: recompute scores per block
    def body(carry, blk):
        acc, m, l = carry
        kb, vb, kb_start = blk
        scores = jnp.einsum("bqhd,bkhd->bhqk", q32, kb.astype(jnp.float32))
        k_pos = kb_start + jnp.arange(block)
        mask = q_pos[:, None] + (s_k - s_q) >= k_pos[None, :]
        scores = jnp.where(mask[None, None], scores, -1e30)
        m_new = jnp.maximum(m, scores.max(-1))
        p = jnp.exp(scores - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32))
        return (acc_new, m_new, l_new), None

    kb = k.reshape(b, nblk, block, h, dh).swapaxes(0, 1)
    vb = v.reshape(b, nblk, block, h, dv).swapaxes(0, 1)
    starts = jnp.arange(nblk) * block
    init = (jnp.zeros((b, h, s_q, dv), jnp.float32),
            jnp.full((b, h, s_q), -jnp.inf),
            jnp.zeros((b, h, s_q), jnp.float32))
    (acc, m, l), _ = lax.scan(body, init, (kb, vb, starts),
                           unroll=nblk if unroll else 1)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.swapaxes(1, 2).astype(q.dtype)  # (B, S, H, Dh)


def _gqa_forward(params, x, cfg, positions, quantize_kv: bool = False):
    """Full causal self-attention. Returns (y, k_rows, v_rows) where
    k_rows/v_rows are the roped true-head K/V — exactly what the decode
    cache stores per position (the fused-prefill bulk write).

    ``quantize_kv`` (int8 cache prefill): attention reads the
    quantize→dequantize roundtrip of K/V instead of the exact rows —
    the cache *representation* — so fused prefill sees exactly what
    chunked prefill and every later decode step will read back, keeping
    the contiguous engine a token-exact oracle for the paged one.
    ``k_rows``/``v_rows`` stay exact: storage quantizes the originals.
    """
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    hp = cfg.padded_heads
    sp = cfg.proj_sparsity
    q = _split_heads(_proj_apply(params["q"], x, sp), h, dh)
    k = _split_heads(_proj_apply(params["k"], x, sp), hkv, dh)
    v = _split_heads(_proj_apply(params["v"], x, sp), hkv, dh)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    k_rows, v_rows = k, v
    if quantize_kv:
        kq, ks = _quant_rows(k)
        vq, vs = _quant_rows(v)
        k = kq.astype(x.dtype) * ks[..., None].astype(x.dtype)
        v = vq.astype(x.dtype) * vs[..., None].astype(x.dtype)
    k = _repeat_kv(k, h // hkv)
    v = _repeat_kv(v, h // hkv)
    q, k, v = (_pad_heads(t, hp) for t in (q, k, v))
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "heads", None)
    v = constrain(v, "batch", "seq", "heads", None)
    scale = 1.0 / np.sqrt(dh)
    if x.shape[1] > cfg.flash_block:
        out = _flash_attn(q, k, v, scale, cfg.flash_block,
                          unroll=cfg.unroll_inner)
    else:
        out = _causal_attn(q, k, v, scale)
    out = constrain(out, "batch", "seq", "heads", None)
    out = _mask_dummy_heads(out, cfg)
    y = _o_proj(params["o"], out.reshape(*x.shape[:-1], hp * dh), sp)
    return y, k_rows, v_rows


def gqa_apply(params, x, cfg, positions):
    """Training/prefill forward (full causal self-attention)."""
    return _gqa_forward(params, x, cfg, positions)[0]


def _pad_seq(x, max_seq: int):
    """Zero-pad the sequence axis (1) out to ``max_seq``."""
    s = x.shape[1]
    if s >= max_seq:
        return x[:, :max_seq]
    pad = [(0, 0)] * x.ndim
    pad[1] = (0, max_seq - s)
    return jnp.pad(x, pad)


def gqa_prefill(params, x, cfg, positions, max_seq: int):
    """Fused full-sequence prefill: one forward over the whole prompt that
    also emits the decode cache in bulk (rows [0, S) written at once).
    Rows >= S are scratch — zeros here, but pad-token K/V when the caller
    bucket-pads the prompt — and are only safe because decode overwrites
    row ``pos`` before its validity mask ever reads it; no consumer may
    assume they are meaningful (or zero).
    With an int8 cache, attention reads the quantized representation
    (see ``_gqa_forward(quantize_kv=...)``) so the fused path stays a
    token-exact oracle for chunked paged prefill.
    Returns (y, cache) with the same cache pytree as gqa_cache_init."""
    int8 = getattr(cfg, "kv_cache_dtype", "") == "int8"
    y, k, v = _gqa_forward(params, x, cfg, positions, quantize_kv=int8)
    if int8:
        kq, ks = _quant_rows(k)
        vq, vs = _quant_rows(v)
        cache = {"k": _pad_seq(kq, max_seq), "v": _pad_seq(vq, max_seq),
                 "k_scale": _pad_seq(ks, max_seq),
                 "v_scale": _pad_seq(vs, max_seq)}
    else:
        cache = {"k": _pad_seq(k, max_seq), "v": _pad_seq(v, max_seq)}
    return y, cache


def gqa_cache_init(cfg, batch: int, max_seq: int, dtype):
    """KV cache holding the *true* kv heads (head padding happens at use).

    With ``cfg.kv_cache_dtype == 'int8'`` (beyond-paper, §Perf): values are
    stored quantized with one scale per (batch, position, head) row —
    halving the decode-dominating cache bytes; dequantization is fused into
    the attention reads."""
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    if getattr(cfg, "kv_cache_dtype", "") == "int8":
        z8 = jnp.zeros((batch, max_seq, hkv, dh), jnp.int8)
        zs = jnp.zeros((batch, max_seq, hkv), jnp.float32)
        return {"k": z8, "v": z8, "k_scale": zs, "v_scale": zs}
    return {"k": jnp.zeros((batch, max_seq, hkv, dh), dtype),
            "v": jnp.zeros((batch, max_seq, hkv, dh), dtype)}


def _quant_rows(x):
    """Per-(..., head)-row symmetric int8 quantization over head_dim."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _cache_write(cache, new, pos, mode: str = None):
    """Write one position into a (B, S, ...) cache.

    ``pos`` may be a scalar (all rows at the same position — the static
    batch) or a (B,) vector of per-row positions (continuous batching:
    every slot decodes at its own depth).

    ``dynamic_update_slice`` at a traced index on the sequence axis defeats
    GSPMD when the cache is sequence-sharded (SP): it all-gathers the whole
    cache (measured: 34 GB/step collectives on yi-6b decode_32k).

    modes (cfg.cache_write):
      masked — one-hot elementwise write: partitions on every axis, costs
               one full cache read+write per step (the safe default).
      owner  — shard_map row-owner write (§Perf hillclimb A rung 3): only
               the shard owning position ``pos`` runs a local
               dynamic_update_slice; other shards pass through untouched.
               Scalar ``pos`` only; vector positions fall back to masked.
    """
    mode = mode or "masked"
    pos = jnp.asarray(pos, jnp.int32)
    s = cache.shape[1]
    if pos.ndim == 1:  # per-slot positions: (B, S) one-hot masked write
        hot = jnp.arange(s)[None, :] == pos[:, None]
        hot = hot.reshape(hot.shape + (1,) * (cache.ndim - 2))
        return jnp.where(hot, new.astype(cache.dtype), cache)
    if mode == "owner":
        owner = _owner_write(cache, new, pos)
        if owner is not None:
            return owner
    hot = (jnp.arange(s) == pos)
    shape = [1, s] + [1] * (cache.ndim - 2)
    hot = hot.reshape(shape)
    return jnp.where(hot, new.astype(cache.dtype), cache)



def _owner_write(cache, new, pos):
    """shard_map write into the sequence-sharded cache; returns None when
    no rules/sharding apply (caller falls back to the masked write)."""
    from jax.sharding import PartitionSpec as P
    from repro.sharding.context import get_rules
    rules = get_rules()
    if rules is None:
        return None
    axes = rules.resolve("kvseq", cache.shape[1])
    if axes is None:
        return None
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    b_axes = rules.resolve("batch", cache.shape[0])
    mesh = rules.mesh
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    shard_len = cache.shape[1] // n_shards
    cache_spec = P(b_axes, axes, *([None] * (cache.ndim - 2)))
    new_spec = P(b_axes, None, *([None] * (cache.ndim - 2)))

    def local(c, n, p):
        # linearized shard index over the (possibly multi-axis) seq axes
        idx = jnp.zeros((), jnp.int32)
        for a in axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        start = idx * shard_len
        lp = p - start
        in_range = jnp.logical_and(lp >= 0, lp < shard_len)

        def write(c):
            lpc = jnp.clip(lp, 0, shard_len - 1)
            starts = (0, lpc) + (0,) * (c.ndim - 2)
            return lax.dynamic_update_slice(c, n.astype(c.dtype), starts)

        return lax.cond(in_range, write, lambda c: c, c)

    return jax.shard_map(
        local, mesh=mesh, in_specs=(cache_spec, new_spec, P()),
        out_specs=cache_spec, check_vma=False,
    )(cache, new, pos if hasattr(pos, "dtype") else jnp.int32(pos))


def gqa_cache_specs(cfg=None):
    specs = {"k": ("batch", "kvseq", "kv", None),
             "v": ("batch", "kvseq", "kv", None)}
    if cfg is not None and getattr(cfg, "kv_cache_dtype", "") == "int8":
        specs["k_scale"] = ("batch", "kvseq", "kv")
        specs["v_scale"] = ("batch", "kvseq", "kv")
    return specs


def _kv_update(cache, k, v, cfg, pos, pos_b, pages):
    """Write the new K/V row(s) and return ``(new_cache, k_view, v_view)``
    where the views are the readable (dequantized) full-length caches.

    ``pages=None`` — contiguous layout: masked/owner write into the
    (B, max_seq, ...) cache, the view IS the cache.
    ``pages`` given — paged layout: scatter each slot's row into its page
    chain (:func:`repro.runtime.kvcache.paged_write_rows`) and gather the
    (B, view_len, ...) slot-logical read view.  Inactive slots' page
    tables are all null, so their stale writes land in the null page.
    """
    if pages is None:
        write = lambda c, n: _cache_write(c, n, pos, cfg.cache_write)
        view = lambda c: c
    else:
        write = lambda c, n: paged_write_rows(c, n[:, 0], pages, pos_b)
        view = lambda c: paged_view(c, pages)
    new_cache = {}
    if "k_scale" in cache:  # int8-quantized cache (beyond-paper)
        kq, ks = _quant_rows(k)
        vq, vs = _quant_rows(v)
        new_cache["k"] = write(cache["k"], kq)
        new_cache["v"] = write(cache["v"], vq)
        new_cache["k_scale"] = write(cache["k_scale"], ks)
        new_cache["v_scale"] = write(cache["v_scale"], vs)
        k_view = (view(new_cache["k"]).astype(k.dtype)
                  * view(new_cache["k_scale"])[..., None].astype(k.dtype))
        v_view = (view(new_cache["v"]).astype(k.dtype)
                  * view(new_cache["v_scale"])[..., None].astype(k.dtype))
    else:
        new_cache["k"] = write(cache["k"], k)
        new_cache["v"] = write(cache["v"], v)
        k_view = view(new_cache["k"])
        v_view = view(new_cache["v"])
    return new_cache, k_view, v_view


def _gqa_cache_attn(params, x, q, k_view, v_view, valid, cfg):
    """Attention of (B, S_q, H, Dh) queries over a full-length cache view
    with a broadcastable validity mask ``valid`` (B|1, S_q|1, V) — the
    shared tail of the decode step and the chunked-prefill step."""
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    hp = cfg.padded_heads
    q = _pad_heads(q, hp)
    kf = _pad_heads(_repeat_kv(k_view, h // hkv), hp)
    vf = _pad_heads(_repeat_kv(v_view, h // hkv), hp)
    scale = 1.0 / np.sqrt(dh)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kf).astype(jnp.float32) * scale
    scores = jnp.where(valid[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vf)
    out = _mask_dummy_heads(out, cfg)
    return _o_proj(params["o"], out.reshape(*x.shape[:-1], hp * dh),
                   cfg.proj_sparsity)


def gqa_decode(params, x, cfg, cache, pos, pages=None):
    """One-token decode step. x: (B, 1, D); pos: scalar current position,
    or a (B,) vector of per-row positions (continuous batching — each slot
    sits at its own depth in the cache).

    The new K/V row is scattered into the cache at ``pos``; attention reads
    the full cache with a validity mask (positions > pos are masked).  With
    the cache sequence axis sharded ("kvseq" -> model/SP), GSPMD turns the
    softmax reductions into cross-shard collectives.

    With ``pages`` (a (B, n_blocks) int32 page table) the cache leaves are
    the PAGED pool ``(n_pages, page_size, ...)``: the row write scatters
    into each slot's own page chain and attention runs over the gathered
    per-slot view — same math, same mask, decoupled memory.
    """
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    sp = cfg.proj_sparsity
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (x.shape[0],))
    positions = pos_b[:, None]
    q = _split_heads(_proj_apply(params["q"], x, sp), h, dh)
    k = _split_heads(_proj_apply(params["k"], x, sp), hkv, dh)
    v = _split_heads(_proj_apply(params["v"], x, sp), hkv, dh)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    new_cache, k_view, v_view = _kv_update(cache, k, v, cfg, pos, pos_b,
                                           pages)
    valid = (jnp.arange(k_view.shape[1])[None, None, :]
             <= pos_b[:, None, None])
    y = _gqa_cache_attn(params, x, q, k_view, v_view, valid, cfg)
    return y, new_cache


def gqa_chunk_prefill(params, x, cfg, cache, pages, pos_start, chunk_len):
    """Chunked prefill over the PAGED cache: forward C prompt tokens of
    ONE slot at absolute positions [pos_start, pos_start + C), scattering
    their K/V rows into the slot's page chain and attending causally to
    the gathered history (earlier chunks are already in the pool).  Rows
    past ``chunk_len`` are bucket padding: their K/V is redirected to the
    null page and their outputs are garbage the caller ignores.

    x: (1, C, D); pages: (1, n_blocks) int32; pos_start/chunk_len:
    scalars (traced — one compile per chunk bucket, not per length).
    Returns (y (1, C, D), new_cache with pool-shaped leaves)."""
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    sp = cfg.proj_sparsity
    b, c, _ = x.shape
    pos0 = jnp.asarray(pos_start, jnp.int32)
    offs = jnp.arange(c, dtype=jnp.int32)
    positions = jnp.broadcast_to(pos0 + offs, (b, c))
    q = _split_heads(_proj_apply(params["q"], x, sp), h, dh)
    k = _split_heads(_proj_apply(params["k"], x, sp), hkv, dh)
    v = _split_heads(_proj_apply(params["v"], x, sp), hkv, dh)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    write = lambda pool, rows: paged_write_chunk(pool, rows, pages[0],
                                                 pos0, chunk_len)
    new_cache = {}
    if "k_scale" in cache:  # int8-quantized cache (beyond-paper)
        kq, ks = _quant_rows(k)
        vq, vs = _quant_rows(v)
        new_cache["k"] = write(cache["k"], kq[0])
        new_cache["v"] = write(cache["v"], vq[0])
        new_cache["k_scale"] = write(cache["k_scale"], ks[0])
        new_cache["v_scale"] = write(cache["v_scale"], vs[0])
        k_view = (paged_view(new_cache["k"], pages).astype(x.dtype)
                  * paged_view(new_cache["k_scale"],
                               pages)[..., None].astype(x.dtype))
        v_view = (paged_view(new_cache["v"], pages).astype(x.dtype)
                  * paged_view(new_cache["v_scale"],
                               pages)[..., None].astype(x.dtype))
    else:
        new_cache["k"] = write(cache["k"], k[0])
        new_cache["v"] = write(cache["v"], v[0])
        k_view = paged_view(new_cache["k"], pages)
        v_view = paged_view(new_cache["v"], pages)
    # causal in slot-logical coordinates: chunk row j sees cols <= pos0+j
    valid = (jnp.arange(k_view.shape[1])[None, None, :]
             <= (pos0 + offs)[None, :, None])
    y = _gqa_cache_attn(params, x, q, k_view, v_view, valid, cfg)
    return y, new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2): latent KV compression
# ---------------------------------------------------------------------------
#
# Rope convention: DeepSeek-V2 stores the rope columns of q and k_pe
# interleaved and de-interleaves them before rotating halves; here they
# are stored as halves.  The two differ by a fixed permutation of the
# rope columns of the ``q`` and ``kpe`` weights, so under random weights
# they are the same model; the benchmark's reference uses halves too.

def mla_init(key, cfg):
    d, h, dh = cfg.d_model, cfg.n_heads, cfg.head_dim
    r, dr = cfg.kv_lora_rank, cfg.rope_head_dim
    ks = jax.random.split(key, 6)
    params = {
        "q": normal_init(ks[0], (d, h * (dh + dr)), 0.02),
        "dkv": normal_init(ks[1], (d, r), 0.02),
        "kpe": normal_init(ks[2], (d, dr), 0.02),
        "uk": normal_init(ks[3], (r, h * dh), 0.02),
        "uv": normal_init(ks[4], (r, h * dh), 0.02),
        "o": normal_init(ks[5], (h * dh, d), 0.02),
    }
    specs = {"q": (None, "heads"), "dkv": (None, None), "kpe": (None, None),
             "uk": (None, "heads"), "uv": (None, "heads"),
             "o": ("heads", None)}
    if cfg.mla_latent_norm:
        params["kv_norm"], specs["kv_norm"] = rmsnorm_init(r)
    return params, specs


def _mla_rope(x, positions, cfg):
    """Rope on the ``rope_head_dim`` columns, with YaRN's frequencies and
    cos/sin scale when ``cfg.yarn_factor`` is set."""
    if not cfg.yarn_factor:
        return apply_rope(x, positions, cfg.rope_theta)
    freqs = yarn_freqs(cfg.rope_head_dim, cfg.rope_theta, cfg.yarn_factor,
                       cfg.yarn_original_max_pos, cfg.yarn_beta_fast,
                       cfg.yarn_beta_slow)
    scale = (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
             / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))
    return apply_rope(x, positions, cfg.rope_theta, freqs=jnp.asarray(freqs),
                      scale=scale)


def mla_softmax_scale(cfg) -> float:
    """(nope + rope head size)^-1/2, times YaRN's mscale squared where
    ``yarn_mscale_all_dim`` is set (as DeepSeek-V2 scales its softmax)."""
    scale = 1.0 / np.sqrt(cfg.head_dim + cfg.rope_head_dim)
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


def _mla_qkv(params, x, cfg, positions):
    """Queries split into their nope and rope parts, the latent rows (after
    the latent norm, as cached) and the roped shared key rows."""
    h, dh, dr = cfg.n_heads, cfg.head_dim, cfg.rope_head_dim
    ct = x.dtype
    q = (x @ params["q"].astype(ct)).reshape(*x.shape[:-1], h, dh + dr)
    q_nope, q_pe = q[..., :dh], _mla_rope(q[..., dh:], positions, cfg)
    c_kv = x @ params["dkv"].astype(ct)                      # (B, S, r)
    if "kv_norm" in params:
        c_kv = rmsnorm_apply(params["kv_norm"], c_kv, cfg.norm_eps)
    k_pe = _mla_rope(x @ params["kpe"].astype(ct), positions,
                     cfg)                                    # (B, S, dr)
    return q_nope, q_pe, c_kv, k_pe


def _mla_expand(params, c_kv, cfg, ct):
    h, dh = cfg.n_heads, cfg.head_dim
    k_nope = (c_kv @ params["uk"].astype(ct)).reshape(*c_kv.shape[:-1], h, dh)
    v = (c_kv @ params["uv"].astype(ct)).reshape(*c_kv.shape[:-1], h, dh)
    return k_nope, v


def _mla_forward(params, x, cfg, positions):
    """Full causal MLA forward in the expanded form (K and V up-projected
    from the latent).  Returns (y, c_kv, k_pe) — the latent rows the
    decode cache stores (fused-prefill bulk write)."""
    h, dh, dr = cfg.n_heads, cfg.head_dim, cfg.rope_head_dim
    q_nope, q_pe, c_kv, k_pe = _mla_qkv(params, x, cfg, positions)
    k_nope, v = _mla_expand(params, c_kv, cfg, x.dtype)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope,
                         jnp.broadcast_to(k_pe[..., None, :],
                                          (*k_pe.shape[:-1], h, dr))], axis=-1)
    scale = mla_softmax_scale(cfg)
    if x.shape[1] > cfg.flash_block:
        out = _flash_attn(q, k, v, scale, cfg.flash_block,
                          unroll=cfg.unroll_inner)
    else:
        out = _causal_attn(q, k, v, scale)
    y = out.reshape(*x.shape[:-1], h * dh) @ params["o"].astype(x.dtype)
    return y, c_kv, k_pe


def mla_apply(params, x, cfg, positions):
    return _mla_forward(params, x, cfg, positions)[0]


def mla_cache_init(cfg, batch: int, max_seq: int, dtype):
    """MLA caches the compressed latent + rope key only: (r + dr) per token
    — the paper-adjacent memory win that makes MLA decode cheap."""
    return {"ckv": jnp.zeros((batch, max_seq, cfg.kv_lora_rank), dtype),
            "kpe": jnp.zeros((batch, max_seq, cfg.rope_head_dim), dtype)}


def mla_cache_specs():
    return {"ckv": ("batch", "kvseq", None), "kpe": ("batch", "kvseq", None)}


def mla_prefill(params, x, cfg, positions, max_seq: int):
    """Fused full-sequence MLA prefill: forward + bulk latent-cache write
    (same contract as :func:`gqa_prefill`)."""
    y, c_kv, k_pe = _mla_forward(params, x, cfg, positions)
    return y, {"ckv": _pad_seq(c_kv, max_seq), "kpe": _pad_seq(k_pe, max_seq)}


def _mla_cache_attn(params, x, q_nope, q_pe, ckv_view, kpe_view, valid, cfg):
    """MLA attention over latent-cache views in the absorbed form, with a
    broadcastable validity mask ``valid`` (B|1, S_q|1, V) — the shared
    tail of the decode step and the chunked-prefill step.

    ``uk`` is folded into the query (one latent query per head) and
    ``uv`` into the output, so the cached latent rows are read as they
    are and never up-projected: per query row and head, the scores are
    taken against the ``r + dr`` wide rows and the probabilities weight
    the latent rows, whose mix is up-projected once."""
    h, dh, r = cfg.n_heads, cfg.head_dim, cfg.kv_lora_rank
    ct = x.dtype
    with jax.named_scope("mla.attn"):
        uk = params["uk"].astype(ct).reshape(r, h, dh)
        uv = params["uv"].astype(ct).reshape(r, h, dh)
        q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, uk)
        scores = (jnp.einsum("bqhr,bkr->bhqk", q_lat, ckv_view,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhd,bkd->bhqk", q_pe, kpe_view,
                               preferred_element_type=jnp.float32))
        scores = jnp.where(valid[:, None], scores * mla_softmax_scale(cfg),
                           -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(ct)
        ctx = jnp.einsum("bhqk,bkr->bqhr", probs, ckv_view)
        out = jnp.einsum("bqhr,rhd->bqhd", ctx, uv)
    return out.reshape(*x.shape[:-1], h * dh) @ params["o"].astype(ct)


def mla_decode(params, x, cfg, cache, pos, pages=None):
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (x.shape[0],))
    positions = pos_b[:, None]
    q_nope, q_pe, c_kv, k_pe = _mla_qkv(params, x, cfg, positions)
    if pages is None:
        new_cache = {
            "ckv": _cache_write(cache["ckv"], c_kv, pos, cfg.cache_write),
            "kpe": _cache_write(cache["kpe"], k_pe, pos, cfg.cache_write),
        }
        ckv_view, kpe_view = new_cache["ckv"], new_cache["kpe"]
    else:
        new_cache = {
            "ckv": paged_write_rows(cache["ckv"], c_kv[:, 0], pages, pos_b),
            "kpe": paged_write_rows(cache["kpe"], k_pe[:, 0], pages, pos_b),
        }
        ckv_view = paged_view(new_cache["ckv"], pages)
        kpe_view = paged_view(new_cache["kpe"], pages)
    valid = (jnp.arange(ckv_view.shape[1])[None, None, :]
             <= pos_b[:, None, None])
    y = _mla_cache_attn(params, x, q_nope, q_pe, ckv_view, kpe_view, valid,
                        cfg)
    return y, new_cache


def mla_chunk_prefill(params, x, cfg, cache, pages, pos_start, chunk_len):
    """Chunked MLA prefill over the PAGED latent cache — the MLA
    counterpart of :func:`gqa_chunk_prefill` (same contract: x (1, C, D),
    pages (1, n_blocks), traced pos_start/chunk_len, padded rows sink to
    the null page)."""
    b, c, _ = x.shape
    pos0 = jnp.asarray(pos_start, jnp.int32)
    offs = jnp.arange(c, dtype=jnp.int32)
    positions = jnp.broadcast_to(pos0 + offs, (b, c))
    q_nope, q_pe, c_kv, k_pe = _mla_qkv(params, x, cfg, positions)
    new_cache = {
        "ckv": paged_write_chunk(cache["ckv"], c_kv[0], pages[0], pos0,
                                 chunk_len),
        "kpe": paged_write_chunk(cache["kpe"], k_pe[0], pages[0], pos0,
                                 chunk_len),
    }
    ckv_view = paged_view(new_cache["ckv"], pages)
    kpe_view = paged_view(new_cache["kpe"], pages)
    valid = (jnp.arange(ckv_view.shape[1])[None, None, :]
             <= (pos0 + offs)[None, :, None])
    y = _mla_cache_attn(params, x, q_nope, q_pe, ckv_view, kpe_view, valid,
                        cfg)
    return y, new_cache
