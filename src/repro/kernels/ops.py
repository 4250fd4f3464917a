"""Jit'd public wrappers around the Pallas kernels, with custom VJPs so the
kernels are usable inside training graphs.

Forward = Pallas kernel; backward = the sparse-cost jnp formulas from
repro.core.functional (static gathers/scatters — same N-fold savings as the
forward, see DESIGN.md §3).  ``interpret`` is passed through unchanged:
whether a kernel runs compiled or in interpret mode is decided once, by
:func:`repro.core.api.choose_executor`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import functional as F
from .block_validation import LANES
from .grouped_cs_matmul import grouped_cs_matmul
from .kwta_exact import kwta_exact_pallas
from .kwta_hist import kwta_hist_pallas
from .packed_matmul import packed_matmul, to_partition_major
from .ref import ref_kwta_hist
from .topk_gather import topk_gather_matmul, topk_support


# ---------------------------------------------------------------------------
# packed matmul op (decompress-in-VMEM MXU path)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def packed_matmul_op(x, packed, route, interpret: bool = False):
    """y = x @ decompress(packed, route); forward via the Pallas kernel."""
    pr, rr = to_partition_major(packed, route)
    y = packed_matmul(x, pr, rr, interpret=interpret)
    return y.astype(x.dtype)


def _pm_fwd(x, packed, route, interpret):
    return packed_matmul_op(x, packed, route, interpret), (x, packed, route)


def _pm_bwd(interpret, res, dy):
    """Sparse-cost backward: gradients only on the packed support, routed
    through the same static gather/scatter as the forward (DESIGN.md §3)."""
    x, packed, route = res
    g, p, n = packed.shape
    r = g // route.shape[0]
    idx = F.route_to_gather_idx(route, n)               # (Gr, P, N)
    dyr = dy.reshape(*dy.shape[:-1], g // r, r, n)
    xg = x[..., idx]
    dpacked = jnp.einsum("...ups,...urs->urps", xg, dyr)
    dpacked = dpacked.reshape(g, p, n).astype(packed.dtype)
    contrib = jnp.einsum("urps,...urs->...ups",
                         packed.reshape(g // r, r, p, n).astype(dy.dtype), dyr)
    dx = jnp.zeros_like(x).at[..., idx].add(contrib.astype(x.dtype))
    return dx, dpacked, None


packed_matmul_op.defvjp(_pm_fwd, _pm_bwd)


# ---------------------------------------------------------------------------
# grouped (shared-route) CS matmul op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def grouped_cs_matmul_op(xg, packed_s, interpret: bool = False):
    """out[s] = xg[s] @ packed_s[s]; (N, B, P) x (N, P, G) -> (N, B, G)."""
    y = grouped_cs_matmul(xg, packed_s, interpret=interpret)
    return y.astype(xg.dtype)


def _gm_fwd(xg, packed_s, interpret):
    return grouped_cs_matmul_op(xg, packed_s, interpret), (xg, packed_s)


def _gm_bwd(interpret, res, dy):
    xg, packed_s = res
    dxg = jnp.einsum("nbg,npg->nbp", dy, packed_s.astype(dy.dtype))
    dw = jnp.einsum("nbp,nbg->npg", xg.astype(dy.dtype), dy)
    return dxg.astype(xg.dtype), dw.astype(packed_s.dtype)


grouped_cs_matmul_op.defvjp(_gm_fwd, _gm_bwd)


# ---------------------------------------------------------------------------
# sparse-sparse topk-gather op (serving path; straight-through custom_vjp:
# gradients flow only on the selected support, mirroring _pm_bwd)
# ---------------------------------------------------------------------------

def _float0(a):
    """Zero cotangent for integer primals (JAX's float0 convention)."""
    return np.zeros(a.shape, dtype=jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def topk_gather_support_op(vals, p_idx, s_off, packed, route,
                           interpret: bool = False):
    """Batched sparse-sparse contraction consuming an explicit support.

    The executor target of the sparse-activation handoff: the upstream
    k-WTA already ran the ONE Select of the layer, so this takes the
    support directly and issues a single Pallas launch for the whole
    (flattened) decode batch.

    vals/p_idx/s_off: (..., K) support (see ``F.topk_support_flat``);
    packed: (G, P, N); route: (G/R, P, N).  Returns (..., G*N) in
    ``vals.dtype``.
    """
    g, p, n = packed.shape
    lead, k = vals.shape[:-1], vals.shape[-1]
    pr, rr = to_partition_major(packed, route)
    y = topk_gather_matmul(vals.astype(jnp.float32).reshape(-1, k),
                           p_idx.reshape(-1, k), s_off.reshape(-1, k),
                           pr, rr, interpret=interpret)
    return y.reshape(*lead, g * n).astype(vals.dtype)


def _tgs_fwd(vals, p_idx, s_off, packed, route, interpret):
    y = topk_gather_support_op(vals, p_idx, s_off, packed, route, interpret)
    return y, (vals, p_idx, s_off, packed, route)


def _tgs_bwd(interpret, res, dy):
    """Sparse-cost backward on the selected support only: d_vals re-reads
    the same K packed rows as the forward; d_packed scatter-adds each
    non-zero's contribution into its partition row (same N-fold savings)."""
    vals, p_idx, s_off, packed, route = res
    g, p, n = packed.shape
    r = g // route.shape[0]
    k = vals.shape[-1]
    wrow = jnp.moveaxis(jnp.take(packed, p_idx, axis=1), 0, -2)  # (...,K,G,N)
    rrow = jnp.moveaxis(jnp.take(route, p_idx, axis=1), 0, -2)   # (...,K,Gr,N)
    hit = (rrow == s_off[..., None, None].astype(rrow.dtype))
    hit = (jnp.repeat(hit, r, axis=-2) if r > 1 else hit).astype(jnp.float32)
    dyr = dy.reshape(*dy.shape[:-1], g, n).astype(jnp.float32)
    wsel = wrow.astype(jnp.float32) * hit
    dvals = jnp.einsum("...gs,...kgs->...k", dyr, wsel).astype(vals.dtype)
    contrib = (vals.astype(jnp.float32)[..., None, None]
               * dyr[..., None, :, :] * hit)                     # (...,K,G,N)
    dpacked = jnp.zeros((g, p, n), jnp.float32).at[
        :, p_idx.reshape(-1, k), :].add(
        jnp.moveaxis(contrib.reshape(-1, k, g, n), 2, 0))
    return (dvals, _float0(p_idx), _float0(s_off),
            dpacked.astype(packed.dtype), _float0(route))


topk_gather_support_op.defvjp(_tgs_fwd, _tgs_bwd)


def topk_gather_op(x, packed, route, k: int, interpret: bool = False):
    """Sparse-sparse contraction via the Pallas kernel, Select included.

    x: (..., D_in) k-sparse; packed (G, P, N); route (G/R, P, N).
    Differentiable: d_x flows straight-through onto the selected support
    (via the take_along_axis in the Select), d_packed via the custom VJP of
    :func:`topk_gather_support_op`.
    """
    n = packed.shape[2]
    vals, p_idx, s_off = topk_support(x, k, n)
    return topk_gather_support_op(vals, p_idx, s_off, packed, route,
                                  interpret).astype(x.dtype)


# ---------------------------------------------------------------------------
# histogram k-WTA op (straight-through gradient on the kept support)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def kwta_hist_op(x, k: int, interpret: bool = False):
    return kwta_hist_pallas(x, k, interpret=interpret)


def _kh_fwd(x, k, interpret):
    y = kwta_hist_op(x, k, interpret)
    return y, (y != 0)


def _kh_bwd(k, interpret, mask, dy):
    return (dy * mask.astype(dy.dtype),)


kwta_hist_op.defvjp(_kh_fwd, _kh_bwd)


# ---------------------------------------------------------------------------
# exact k-WTA op (straight-through gradient on the K winners)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def kwta_exact_op(x, k: int, interpret: bool = False):
    """Exact k-WTA along axis 1 of (G, D, N) via the Pallas kernel."""
    return kwta_exact_pallas(x, k, interpret=interpret)


def _ke_fwd(x, k, interpret):
    # The residual is the winner mask itself, not ``y != 0``: after a ReLU
    # a winner may hold 0 and still takes its gradient.
    return kwta_exact_pallas(x, k, with_mask=True, interpret=interpret)


def _ke_bwd(k, interpret, keep, dy):
    return (dy * keep.astype(dy.dtype),)


kwta_exact_op.defvjp(_ke_fwd, _ke_bwd)


def kwta_exact_lastaxis(x, k: int, interpret: bool = False):
    """Exact k-WTA over the last axis of ``x`` through :func:`kwta_exact_op`.

    With a leading axis N of at least ``LANES // 2`` (the batch of a conv
    activation (B, H, W, C)) the kernel's view is (H*W, C, B): XLA keeps
    such an activation batch-minor, so both transposes are bitcasts, and
    padding N to whole lanes at most doubles the kernel's work.  A shorter
    leading axis would leave most lanes padding (127 of 128 at batch 1), so
    there every row goes along lanes, (1, D, R), for one transposing copy
    each way."""
    d = x.shape[-1]
    n = x.shape[0] if x.ndim > 1 else 1
    if 2 * n >= LANES:
        x3 = x.reshape(n, -1, d).transpose(1, 2, 0)
        y3 = kwta_exact_op(x3, k, interpret)
        return y3.transpose(2, 0, 1).reshape(x.shape)
    y3 = kwta_exact_op(x.reshape(-1, d).T[None], k, interpret)
    return y3[0].T.reshape(x.shape)
