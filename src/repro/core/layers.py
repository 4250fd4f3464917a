"""Parameterized layers: dense and complementary-sparse linear / conv2d.

Functional style (no framework): each layer is an ``init(key, ...) ->
(params, specs)`` + ``apply(params, x, ...)`` pair.  ``specs`` mirrors the
params pytree with logical-axis tuples consumed by repro.sharding.

Packed layers hold:
  packed  (G, P, N)  float   — pre-routed packed weights (trainable)
  route   (G/R, P, N) int8   — static complementary routing (not trainable)
  bias    (D_out,)    float  — optional

The packed weight's group dim G is the sharding analog of D_out: tensor
parallelism shards G exactly like a dense layer shards its output features,
and each shard carries its own slice of the route table.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.obs import sparsity as obs_sparsity
from repro.sharding.context import get_rules

from . import functional as F
from .api import (SparsityConfig, choose_executor, choose_path,
                  dispatch_observed, notify_dispatch)
from .kwta import kwta, kwta_bisect, kwta_hist, kwta_local, kwta_support
from .masks import CSLayout, make_routes
from .packing import pack_dense


# ---------------------------------------------------------------------------
# Dense linear (baseline)
# ---------------------------------------------------------------------------

def linear_init(key, d_in: int, d_out: int, bias: bool = True,
                out_axis: str = "mlp", in_axis: Optional[str] = None,
                dtype=jnp.float32):
    k_w, _ = jax.random.split(key)
    scale = 1.0 / np.sqrt(d_in)
    params = {"w": jax.random.uniform(k_w, (d_in, d_out), dtype, -scale, scale)}
    specs = {"w": (in_axis, out_axis)}
    if bias:
        params["b"] = jnp.zeros((d_out,), dtype)
        specs["b"] = (out_axis,)
    return params, specs


def linear_apply(params, x):
    y = x @ params["w"].astype(x.dtype)
    if "b" in params:
        y = y + params["b"].astype(x.dtype)
    return y


# ---------------------------------------------------------------------------
# Complementary-sparse packed linear
# ---------------------------------------------------------------------------

def packed_linear_init(key, d_in: int, d_out: int, cfg: SparsityConfig,
                       bias: bool = True, seed: int = 0,
                       out_axis: str = "mlp", dtype=jnp.float32):
    """Initialize a packed CS linear layer.

    Initialization matches a dense layer restricted to the CS support: each
    output has fan-in D_in/N, so we scale by sqrt(N/D_in) (sparse-aware init,
    crucial for trainability at high sparsity).

    Dims that don't divide the pack factor are transparently padded (the
    paper's sets need not all be full, §3: 'the restriction applies only to
    each set being combined'); ``packed_linear_apply`` pads inputs / slices
    outputs back. The bias (when present) carries the logical d_out.
    """
    from .masks import pad_to_multiple
    d_in_p = pad_to_multiple(d_in, cfg.n)
    d_out_p = pad_to_multiple(d_out, cfg.n)
    layout = CSLayout(d_in_p, d_out_p, cfg.n, cfg.perm_kind)
    d_in, d_out_logical, d_out = d_in_p, d_out, d_out_p
    g, p, n = layout.groups, layout.partitions, layout.n
    r = g if cfg.route_share == 0 else min(cfg.route_share, g)
    while g % r:  # fall back to the nearest divisor
        r -= 1
    route_np = make_routes(CSLayout(d_in, n * (g // r), n, cfg.perm_kind), seed)
    scale = np.sqrt(cfg.n / d_in)
    packed = jax.random.uniform(key, (g, p, n), dtype, -scale, scale)
    params = {"packed": packed, "route": jnp.asarray(route_np)}
    specs = {"packed": (out_axis, None, None), "route": (out_axis, None, None)}
    if bias:
        params["b"] = jnp.zeros((d_out_logical,), dtype)
        specs["b"] = (out_axis,)
    return params, specs


def packed_linear_from_dense(w: np.ndarray, cfg: SparsityConfig, seed: int = 0,
                             bias: Optional[np.ndarray] = None):
    """Pack an existing (masked) dense weight (the paper's offline Combine)."""
    d_in, d_out = w.shape
    layout = CSLayout(d_in, d_out, cfg.n, cfg.perm_kind)
    g = layout.groups
    r = g if cfg.route_share == 0 else min(cfg.route_share, g)
    while g % r:
        r -= 1
    route = make_routes(CSLayout(d_in, layout.n * (g // r), layout.n,
                                 cfg.perm_kind), seed)
    route_full = np.broadcast_to(route[:, None], (g // r, r, *route.shape[1:]))
    route_full = route_full.reshape(g, *route.shape[1:])
    packed = pack_dense(layout, w, route_full)
    params = {"packed": jnp.asarray(packed), "route": jnp.asarray(route)}
    if bias is not None:
        params["b"] = jnp.asarray(bias)
    return params


def _topk_execute(vals, idx, packed, route, cfg: SparsityConfig):
    """Sparse-sparse Multiply-Route-Sum on an explicit support, dispatched
    to the batched Pallas kernel or the jnp formula per the executor."""
    n = packed.shape[2]
    p_idx, s_off = idx // n, idx % n
    ex = choose_executor(cfg)
    if ex.use_pallas:
        # deferred import: kernels.ops imports repro.core at module scope
        from repro.kernels.ops import topk_gather_support_op

        def call(*args):
            return topk_gather_support_op(*args, ex.interpret)

        rules = get_rules()
        if rules is not None and rules.mesh.size > 1:
            # The compiler cannot partition a Mosaic kernel: every device
            # runs it on replicated (all-gathered) operands.
            call = jax.shard_map(call, mesh=rules.mesh, in_specs=P(),
                                 out_specs=P(), check_vma=False)
        return call(vals, p_idx, s_off, packed, route)
    return F.cs_topk_from_support(vals, p_idx, s_off, packed, route)


def packed_linear_apply(params, x, cfg: SparsityConfig,
                        x_is_sparse: bool = False, support=None):
    """Apply packed CS linear with regime dispatch (DESIGN.md §2.1).

    Handles padded layouts: inputs are zero-padded up to P*N, outputs are
    sliced back to the bias length (when a bias is present).

    ``support`` is the optional sparse-activation handoff from the
    upstream k-WTA (``apply_kwta(..., return_support=True)``): a
    ``(vals, idx)`` pair over the *unpadded* last axis.  On the topk path
    it replaces the re-derivation of the support (one Select per layer,
    paper Fig. 8a); other paths ignore it.  Which backend runs the topk
    contraction — batched Pallas kernel vs jnp — is the executor's call
    (``cfg.use_pallas``, see :func:`repro.core.api.choose_executor`)."""
    packed = params["packed"].astype(x.dtype)
    route = params["route"]
    d_in = packed.shape[1] * packed.shape[2]
    if x.shape[-1] < d_in:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, d_in - x.shape[-1])]
        x = jnp.pad(x, pad)
    batch = int(np.prod(x.shape[:-1])) if x.ndim > 1 else 1
    path = choose_path(cfg, batch, d_in, x_is_sparse)
    if dispatch_observed():
        # Trace-time dispatch telemetry (repro.obs): which path/backend
        # this layer application staged.  Pure Python — nothing lands in
        # the jaxpr.
        ex = choose_executor(cfg) if path == "topk" else None
        notify_dispatch({"path": path, "batch": batch, "d_in": d_in,
                         "d_out": packed.shape[0] * packed.shape[2],
                         "n": cfg.n, "k": cfg.k_for(d_in),
                         "pallas": bool(ex and ex.use_pallas),
                         "interpret": bool(ex and ex.interpret)})
    # The cs_<path> scope lets the static analyzer attribute every staged
    # primitive to the execution path that produced it (repro.analysis).
    with jax.named_scope(f"cs_{path}"):
        if path == "topk":
            if support is None:
                # No handoff: run this layer's own Select on the k-sparse x.
                vals, idx = F.topk_support_flat(x, cfg.k_for(d_in))
            else:
                # Handoff indices address the unpadded axis; zero-padding
                # only appends positions, so they stay valid in the padded
                # layout.
                vals, idx = support
            y = _topk_execute(vals, idx, packed, route, cfg)
        elif path == "dense":
            y = F.cs_matmul_dense(x, packed, route)
        else:
            y = F.cs_matmul(x, packed, route)
    if "b" in params:
        b = params["b"]
        y = y[..., :b.shape[0]] + b.astype(x.dtype)
    return y


def apply_kwta(x, cfg: SparsityConfig, return_support: bool = False):
    """Apply the configured k-WTA activation along the last axis.

    With ``return_support=True`` returns ``(y, support)`` where ``support``
    is the ``(vals, idx)`` winner set when the exact global top-k impl ran,
    else ``None`` (hist/bisect keep >= K values with no index form; local
    k-WTA selects per-partition).  Passing the support to the next
    ``packed_linear_apply`` makes the Select run once per layer."""
    if not cfg.activation_sparse:
        return (x, None) if return_support else x
    k = cfg.k_for(x.shape[-1])
    support = None
    if cfg.kwta_impl == "hist":
        y = kwta_hist(x, k)
    elif cfg.kwta_impl == "bisect":
        y = kwta_bisect(x, k)
    elif cfg.kwta_partitions > 1:
        y = kwta_local(x, k, cfg.kwta_partitions)
    else:
        y, support = kwta_support(x, k)
    # Realized-sparsity capture (repro.obs): when the serving engine's
    # probed decode step is tracing, report this layer's winner set (exact
    # top-k) or a staged nnz reduction (>=-K threshold impls).  With no
    # active capture — every other trace, including everything the static
    # linter checks — both calls return immediately and stage nothing.
    if support is not None:
        obs_sparsity.observe_support(support[0], support[1], x.shape[-1])
    elif obs_sparsity.capture_active():
        obs_sparsity.observe_activation(y)
    return (y, support) if return_support else y


# ---------------------------------------------------------------------------
# Conv2D (dense + packed) — NHWC, via im2col so conv reuses the CS algebra
# ---------------------------------------------------------------------------

def _same_pad(x, kh, kw):
    ph, pw = kh // 2, kw // 2
    return jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))


def im2col(x: jax.Array, kh: int, kw: int, stride: int = 1,
           padding: str = "VALID") -> jax.Array:
    """Extract patches: (B, H, W, C) -> (B, OH, OW, kh*kw*C)."""
    if padding == "SAME":
        x = _same_pad(x, kh, kw)
    b, h, w, c = x.shape
    oh = (h - kh) // stride + 1
    ow = (w - kw) // stride + 1
    patches = jnp.stack(
        [x[:, i:i + oh * stride:stride, j:j + ow * stride:stride, :]
         for i in range(kh) for j in range(kw)], axis=-2)
    return patches.reshape(b, oh, ow, kh * kw * c)


def conv2d_init(key, kh: int, kw: int, c_in: int, c_out: int,
                bias: bool = True, dtype=jnp.float32):
    scale = 1.0 / np.sqrt(kh * kw * c_in)
    params = {"w": jax.random.uniform(key, (kh, kw, c_in, c_out), dtype,
                                      -scale, scale)}
    specs = {"w": (None, None, None, "mlp")}
    if bias:
        params["b"] = jnp.zeros((c_out,), dtype)
        specs["b"] = ("mlp",)
    return params, specs


def conv2d_apply(params, x, stride: int = 1, padding: str = "VALID"):
    w = params["w"].astype(x.dtype)
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if "b" in params:
        y = y + params["b"].astype(x.dtype)
    return y


def packed_conv2d_init(key, kh: int, kw: int, c_in: int, c_out: int,
                       cfg: SparsityConfig, bias: bool = True, seed: int = 0,
                       dtype=jnp.float32):
    """CS conv packed along the filter dimension (paper Fig. 7)."""
    params, specs = packed_linear_init(
        key, kh * kw * c_in, c_out, cfg, bias=bias, seed=seed, dtype=dtype)
    return params, specs


def packed_conv2d_mxu(params, x, kh: int, kw: int, stride: int = 1,
                      padding: str = "VALID"):
    """The packed conv as one f32 convolution over its decompressed kernel.

    The dense masked weight (1/N nonzero) is reshaped to HWIO: im2col's
    column order (i, j, c) is exactly that reshape.  Rows past
    ``kh*kw*C`` met zero padding, and outputs past the bias are sliced
    off, so the kernel drops both before the convolution.  Every product
    is the faithful path's, at ``Precision.HIGHEST``; only the order of
    the sums differs.  Autodiff transposes the decompression into a
    gather onto the packed support, so gradients stay masked."""
    c_in = x.shape[-1]
    w = F.decompress(params["packed"].astype(x.dtype), params["route"])
    w = w[:kh * kw * c_in]
    if "b" in params:
        w = w[:, :params["b"].shape[0]]
    w = w.reshape(kh, kw, c_in, w.shape[1])
    ph, pw = (kh // 2, kw // 2) if padding == "SAME" else (0, 0)
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((ph, ph), (pw, pw)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32).astype(x.dtype)
    if "b" in params:
        y = y + params["b"].astype(x.dtype)
    return y


def packed_conv2d_apply(params, x, cfg: SparsityConfig, kh: int, kw: int,
                        stride: int = 1, padding: str = "VALID",
                        x_is_sparse: bool = False):
    """CS conv: im2col, then the packed linear map over each receptive
    field (paper Fig. 7).

    On a TPU the layer runs :func:`packed_conv2d_mxu` instead (path
    ``conv_mxu``): the faithful path's routed activation, (G, P, N)
    columns for every output pixel, would be written to HBM and read
    back, where the decompressed kernel is a few hundred KB and one
    convolution runs on the MXU."""
    if jax.default_backend() == "tpu":
        with jax.named_scope("cs_conv_mxu"):
            y = packed_conv2d_mxu(params, x, kh, kw, stride, padding)
        if dispatch_observed():
            packed = params["packed"]
            d_in = packed.shape[1] * packed.shape[2]
            notify_dispatch({"path": "conv_mxu",
                             "batch": int(np.prod(y.shape[:-1])),
                             "d_in": d_in,
                             "d_out": packed.shape[0] * packed.shape[2],
                             "n": cfg.n, "k": cfg.k_for(d_in),
                             "pallas": False, "interpret": False})
        return y
    cols = im2col(x, kh, kw, stride, padding)  # (B, OH, OW, kh*kw*C)
    return packed_linear_apply(params, cols, cfg, x_is_sparse=x_is_sparse)


def maxpool2d(x, size: int = 2, stride: int = 2):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, size, size, 1), (1, stride, stride, 1),
        "VALID")
