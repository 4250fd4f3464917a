"""Pallas TPU kernel: exact k-WTA without a sort or a scatter.

``lax.top_k`` lowers to a full sort on the TPU, and writing its winners
back takes a scatter; both run far below the memory roofline of what a
k-WTA has to do, which is to read each row once and write it once.  This
kernel holds a block of rows in VMEM, finds each row's winners there with
the radix select of :func:`repro.core.kwta.topk_keep` (32 compare-and-count
rounds over an order-preserving int32 key, then a few more over position
for ties, which go to the lower index as with ``lax.top_k``), and writes
``where(keep, x, 0)``: one HBM read and one HBM write.

The operand is (G, D, N) with the competition along D: each of the N rows
of a group lies along lanes and its D values along sublanes, so a round's
per-row count is a sum of whole vregs and one sublane reduction, and its
threshold one sublane broadcast back.  For a conv activation (B, H, W, C)
at a batch of 64 or more that is (H*W, C, B), the layout XLA keeps such
activations in, so the transposes around the kernel cost no copy; at a
smaller batch every row goes along lanes (see
:func:`repro.kernels.ops.kwta_exact_lastaxis`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.kwta import order_key, topk_keep

from .block_validation import LANES

#: Target bytes of one input block.
_BLOCK_BYTES = 1024 * 1024


def _kwta_exact_kernel(x_ref, *out_refs, k: int):
    x = x_ref[...]                                    # (gb, D, bn)
    d = x.shape[1]
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)

    def count(m):
        return jnp.sum(jnp.where(m, 1, 0), axis=1, keepdims=True)

    keep = topk_keep(order_key(x), lane, k, d, count)
    out_refs[0][...] = jnp.where(keep, x, jnp.zeros_like(x))
    if len(out_refs) > 1:
        out_refs[1][...] = jnp.where(keep, 1.0, 0.0).astype(x.dtype)


def _grow(n: int, size: int, cap: int) -> int:
    """Largest ``size * 2**j`` that divides ``n`` and stays within ``cap``."""
    while size * 2 <= cap and n % (size * 2) == 0:
        size *= 2
    return size


@functools.partial(jax.jit, static_argnames=("k", "with_mask", "interpret"))
def kwta_exact_pallas(x: jax.Array, k: int, with_mask: bool = False,
                      interpret: bool = False):
    """Exact k-WTA along axis 1 of ``x`` (G, D, N), 0 < k < D.

    Returns ``y``, or ``(y, keep)`` with ``with_mask`` (``keep`` 1.0 on the
    K winners of each row and 0.0 elsewhere, in ``x.dtype``; the backward
    pass needs it, since a winner may hold the value 0)."""
    g, d, n = x.shape
    if not 0 < k < d:
        raise ValueError(f"need 0 < k < d, got k={k}, d={d}")
    n_pad = -(-n // LANES) * LANES
    xp = x if n_pad == n else jnp.pad(x, ((0, 0), (0, 0), (0, n_pad - n)))
    row_bytes = d * x.dtype.itemsize
    bn = _grow(n_pad, LANES, _BLOCK_BYTES // row_bytes)
    gb = _grow(g, 1, _BLOCK_BYTES // (row_bytes * bn))
    spec = pl.BlockSpec((gb, d, bn), lambda i, j: (i, 0, j))
    n_out = 2 if with_mask else 1
    outs = pl.pallas_call(
        functools.partial(_kwta_exact_kernel, k=k),
        grid=(g // gb, n_pad // bn),
        in_specs=[spec],
        out_specs=[spec] * n_out,
        out_shape=[jax.ShapeDtypeStruct(xp.shape, x.dtype)] * n_out,
        interpret=interpret,
    )(xp)
    outs = [o[:, :, :n] for o in outs]
    return tuple(outs) if with_mask else outs[0]
