"""Pallas TPU kernel: sparse-sparse CS contraction (paper §3.2 / Fig. 8).

Implements the five-step sparse-sparse pipeline's hot loop: for each of the
K non-zero activations, fetch the corresponding packed weight row (the
paper's 'K-ported weight memory' becomes K sequential VMEM row loads — on
TPU, parallelism comes from the G·N lanes of each fetched row instead of
from memory ports), mask by Kernel-ID match (route == offset), scale by the
activation value, and accumulate.

FLOPs: 2·B·K·D_out — the multiplicative sparse-sparse saving
(D_in/K from activations × N from weights on the memory side).

Layouts:
  vals   (B, K)       activation values (f32)          SMEM, whole array
  p_idx  (B, K) int32 partition index of each non-zero  SMEM, whole array
  s_off  (B, K) int32 offset-within-partition           SMEM, whole array
  packed (P, G, N)    partition-major packed weights    VMEM as (P, G·N) f32
  route  (P, G, N)    routes                            VMEM as (P, G·N) i32
  out    (B, G*N)     f32

The support is scalar data (one row index, one offset and one scale per
non-zero), so it lives in SMEM and the K loop reads it with scalar loads.
The weights are lane-dense: (P, G·N) puts the G·N output columns on the
128-wide lane axis, so one dynamic row load fetches a partition's whole
output row with no in-kernel reshape.  Mosaic loads a single row at a
dynamic sublane offset only from 32-bit refs, so the wrapper widens the
weights to f32 and the routes to int32 before the call.

Grid: (nG, B) — batch innermost.  The weight tile (P, block_g·N) stays
VMEM-resident across the K loop AND across the whole decode batch: with B
as the fastest grid dimension the packed/route index maps are constant
while b sweeps, so Pallas' revisit caching skips the re-fetch and one
launch serves every decode slot (the batched-decode regime of arXiv
2311.07625 — weight reads amortize over B, which is where weight ×
activation sparsity multiply).  A group tile must span a multiple of 128
lanes (block_g·N % 128 == 0) unless it covers all G groups.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .block_validation import LANES, validate_block


def _topk_gather_kernel(vals_ref, pidx_ref, soff_ref, packed_ref, route_ref,
                        o_ref, *, k_nnz: int):
    b = pl.program_id(1)

    def body(j, acc):
        p = pidx_ref[b, j]
        w = packed_ref[pl.ds(p, 1), :]            # (1, block_g*N) f32
        r = route_ref[pl.ds(p, 1), :]             # (1, block_g*N) int32
        hit = r == soff_ref[b, j]
        return acc + jnp.where(hit, w, 0.0) * vals_ref[b, j]

    o_ref[...] = lax.fori_loop(0, k_nnz, body,
                               jnp.zeros(o_ref.shape, jnp.float32))


@functools.partial(jax.jit, static_argnames=("block_g", "interpret"))
def topk_gather_matmul(vals: jax.Array, p_idx: jax.Array, s_off: jax.Array,
                       packed_p: jax.Array, route_p: jax.Array,
                       block_g: int = 0, interpret: bool = False) -> jax.Array:
    """Sparse-sparse contraction of K non-zeros against packed weights.

    Returns (B, G*N) float32. See module docstring for layouts.
    """
    b, k_nnz = vals.shape
    p, g, n = packed_p.shape
    block_g = block_g or g
    if k_nnz < 1:
        raise ValueError(f"k_nnz={k_nnz} must be >= 1 (at least one "
                         "non-zero per row)")
    # Explicit-block convention: an oversized block_g is the caller's error,
    # not something to clamp away (shared validator, clamp=False).
    block_g = validate_block("block_g", block_g, g, "G", clamp=False)
    if block_g < g and (block_g * n) % LANES:
        raise ValueError(f"block_g={block_g}: block_g*N={block_g * n} must "
                         f"be a multiple of {LANES} lanes (or block_g=G)")
    width = block_g * n
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_topk_gather_kernel, k_nnz=k_nnz),
        grid=(g // block_g, b),
        in_specs=[
            smem, smem, smem,
            pl.BlockSpec((p, width), lambda ig, ib: (0, ig)),
            pl.BlockSpec((p, width), lambda ig, ib: (0, ig)),
        ],
        out_specs=pl.BlockSpec((None, 1, width), lambda ig, ib: (ib, 0, ig)),
        out_shape=jax.ShapeDtypeStruct((b, 1, g * n), jnp.float32),
        interpret=interpret,
    )(vals.astype(jnp.float32), p_idx.astype(jnp.int32),
      s_off.astype(jnp.int32),
      packed_p.reshape(p, g * n).astype(jnp.float32),
      route_p.reshape(p, g * n).astype(jnp.int32))
    return out.reshape(b, g * n)


def topk_support(x: jax.Array, k: int, n: int):
    """Select step (paper's k-WTA + index extraction): the K largest-|x|
    positions as (vals, p_idx, s_off). Exact for any k-sparse x."""
    from repro.core.instrument import counted_top_k
    _, sel = counted_top_k(jnp.abs(x), k)
    vals = jnp.take_along_axis(x, sel, axis=-1)
    return (vals.astype(jnp.float32), (sel // n).astype(jnp.int32),
            (sel % n).astype(jnp.int32))
