"""k-Winner-Take-All activation functions (paper §2.2.2, §3.3.3).

k-WTA replaces ReLU: exactly the K largest pre-activations propagate, the
rest are zeroed (winners keep their values).  Gradients flow only through
winners (straight-through on the support, zero elsewhere, matching [Ahmad &
Scheinkman 2019]).

Implementations, and where each runs:

* :func:`kwta` — exact k-WTA, sort-free and scatter-free: a radix select of
  the K-th largest value over an order-preserving int32 key, then the
  lowest-index ties up to K (``lax.top_k``'s tie rule), then one masked
  write (:func:`topk_keep`).  On a TPU it runs as the ``kwta_exact``
  Pallas kernel (one HBM read and one write per row block); elsewhere the
  same formula runs in ``jnp``.  The reference semantics and the training
  default; :func:`kwta_channel` and :func:`kwta_local` reach it.
* :func:`kwta_support` — exact top-k via ``lax.top_k`` + scatter that also
  returns the ``(vals, idx)`` winner support, for the sparse-activation
  handoff to the next layer's ``topk_gather``.
* :func:`kwta_hist` — the paper's **histogram-threshold global k-WTA**
  (Fig. 10): build a value histogram, walk it from the top bin to find the
  smallest threshold retaining >= K values, keep everything above it.  Exact
  for quantized inputs with distinct bins; for continuous inputs may retain
  slightly more than K on bin ties (the paper's hardware has the same
  behavior — threshold compare, not an exact sort).
* :func:`kwta_bisect` — threshold k-WTA by bisection on the value axis
  (>= K kept on ties), the SPMD-native form the serving FFN uses.
* :func:`kwta_local` — the paper's **local/partitioned k-WTA** (used after
  conv layers; competition within partitions).  On TPU we align partitions
  with the tensor-parallel shard so winner selection never crosses chips
  (DESIGN.md §2).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .instrument import counted_top_k, staged_select

_INT32_MIN = -2 ** 31


def order_key(x: jax.Array) -> jax.Array:
    """Order-preserving int32 key of ``x``'s float32 values.

    Compared as signed ints, keys order as the floats do; -0.0 and 0.0 share
    the key 0, since they compare equal."""
    x32 = x.astype(jnp.float32)
    bits = lax.bitcast_convert_type(x32, jnp.int32)
    key = bits ^ ((bits >> 31) & 0x7FFFFFFF)
    return jnp.where(x32 == 0, 0, key)


def topk_keep(key: jax.Array, lane: jax.Array, k: int, d: int, count):
    """Winner mask of exact top-k over rows of ``d`` keys, without a sort.

    ``lane`` is each key's position in its row; ``count(mask)`` gives each
    row's number of True entries, broadcastable against ``key``.  Radix
    select builds the K-th largest key ``t`` bit by bit (32 rounds of
    compare-and-count); every key above ``t`` wins, and among the keys
    equal to ``t`` the lowest positions fill the remaining places, found by
    a second radix select over the reversed position (``lax.top_k``'s tie
    rule).  Exactly K winners per row."""
    one = jnp.int32(1)

    def key_round(i, t):
        c = t | (one << (30 - i))
        return jnp.where(count(key >= c) >= k, c, t)

    # Bit 31 of the unsigned key is the sign: candidate 0 in signed terms.
    t = jnp.where(count(key >= 0) >= k, 0, jnp.int32(_INT32_MIN))
    t = lax.fori_loop(0, 31, key_round, t)
    gt, eq = key > t, key == t
    need = k - count(gt)                   # >= 1 ties still to take
    rank = (d - 1) - lane                  # higher for lower positions
    bits = max(1, (d - 1).bit_length())

    def tie_round(i, u):
        c = u | (one << (bits - 1 - i))
        return jnp.where(count(eq & (rank >= c)) >= need, c, u)

    u = lax.fori_loop(0, bits, tie_round, jnp.zeros_like(t))
    return gt | (eq & (rank >= u))


def kwta_exact_jnp(x: jax.Array, k: int) -> jax.Array:
    """:func:`topk_keep` over the last axis in plain ``jnp`` (off-TPU)."""
    d = x.shape[-1]
    lane = lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    keep = topk_keep(order_key(x), lane, k, d,
                     lambda m: jnp.sum(m, axis=-1, keepdims=True,
                                       dtype=jnp.int32))
    return jnp.where(keep, x, jnp.zeros_like(x))


def kwta(x: jax.Array, k: int, axis: int = -1) -> jax.Array:
    """Exact k-WTA: keep the K largest values along ``axis``, zero the rest.

    Ties go to the lower index, as with ``lax.top_k``.  Sort-free: the
    ``kwta_exact`` Pallas kernel on a TPU, :func:`kwta_exact_jnp` elsewhere.
    """
    if k <= 0:
        raise ValueError(f"k must be positive, got {k}")
    x_m = jnp.moveaxis(x, axis, -1)
    d = x_m.shape[-1]
    if k >= d:
        return x
    with staged_select("threshold"):
        if jax.default_backend() == "tpu":
            # deferred import: kernels.ops imports repro.core at module scope
            from repro.kernels.ops import kwta_exact_lastaxis

            y = kwta_exact_lastaxis(x_m, k)
        else:
            y = kwta_exact_jnp(x_m, k)
    return jnp.moveaxis(y, -1, axis)


def kwta_support(x: jax.Array, k: int):
    """Exact k-WTA over the last axis that ALSO returns the winner support.

    This is the sparse-activation handoff (paper Fig. 8a: one Select per
    layer): the consumer of the k-sparse output — typically the next
    CS-packed projection's sparse-sparse path — takes ``(vals, idx)``
    directly instead of re-running ``lax.top_k`` on the scattered result.

    Returns ``(y, (vals, idx))`` where ``y`` is the k-sparse activation
    (same as :func:`kwta`), ``vals`` is (..., K) winner values and ``idx``
    is (..., K) int32 flat positions along the last axis.  When ``k >= d``
    the input is already dense and the support is ``None``.
    """
    d = x.shape[-1]
    if k >= d:
        return x, None
    vals, idx = counted_top_k(x, k)
    y = jnp.put_along_axis(jnp.zeros_like(x), idx, vals, axis=-1,
                           inplace=False)
    return y, (vals, idx.astype(jnp.int32))


def kwta_mask(x: jax.Array, k: int, axis: int = -1) -> jax.Array:
    """Boolean winner mask of exact k-WTA (ties broken by top_k order)."""
    x_m = jnp.moveaxis(x, axis, -1)
    _, idx = counted_top_k(x_m, min(k, x_m.shape[-1]))
    m = jnp.zeros(x_m.shape, jnp.bool_)
    m = jnp.put_along_axis(m, idx, True, axis=-1, inplace=False)
    return jnp.moveaxis(m, -1, axis)


def kwta_hist(x: jax.Array, k: int, bins: int = 256) -> jax.Array:
    """Histogram-threshold global k-WTA over the last axis (paper Fig. 10).

    Mirrors the FPGA datapath: quantize values to ``bins`` levels, histogram,
    cumulative-sum from the largest bin down until the running count reaches
    K, threshold-compare the inputs against the resulting cutoff.

    Retains *at least* K values (>= semantics at the threshold bin, like the
    hardware); exact when bin occupancy at the threshold is 1.
    """
    d = x.shape[-1]
    if k >= d:
        return x
    lo = jnp.min(x, axis=-1, keepdims=True)
    hi = jnp.max(x, axis=-1, keepdims=True)
    scale = jnp.where(hi > lo, (bins - 1) / (hi - lo), jnp.zeros_like(hi))
    b = jnp.clip(((x - lo) * scale), 0, bins - 1).astype(jnp.int32)  # (..., D)
    hist = jax.nn.one_hot(b, bins, dtype=jnp.int32).sum(axis=-2)  # (..., bins)
    # count of elements with bin >= t  (reverse cumulative sum)
    ccount = jnp.cumsum(hist[..., ::-1], axis=-1)[..., ::-1]
    # threshold bin: the largest t whose tail-count is still >= k
    ok = (ccount >= k)  # non-increasing in t -> last True
    tbin = jnp.sum(ok.astype(jnp.int32), axis=-1) - 1          # (...,)
    tbin = jnp.clip(tbin, 0, bins - 1)
    keep = b >= tbin[..., None]
    return x * keep.astype(x.dtype)


def kwta_bisect(x: jax.Array, k: int, iters: int = 16) -> jax.Array:
    """Threshold k-WTA via bisection on the value axis (SPMD-native).

    The sort/scatter lowering of exact top-k forces GSPMD to *replicate* the
    batch across the mesh (measured: a 10.7 GB all-gather per FFN at
    train_4k scale — see EXPERIMENTS.md §Perf).  This variant binary-searches
    the threshold instead: ``iters`` rounds of (compare + count) — pure
    elementwise + reduction ops that partition along every batch dim.

    Equivalent to walking the paper's histogram CDF (Fig. 10) to the K-th
    count with radix-2 refinement; like the hardware it keeps *at least* K
    values (>= threshold semantics, ties inclusive).
    """
    d = x.shape[-1]
    if k >= d:
        return x
    x32 = x.astype(jnp.float32)
    lo = jnp.min(x32, axis=-1, keepdims=True)
    hi = jnp.max(x32, axis=-1, keepdims=True)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        cnt = jnp.sum((x32 >= mid).astype(jnp.int32), axis=-1, keepdims=True)
        keep_going_down = cnt >= k      # threshold can move up
        lo = jnp.where(keep_going_down, mid, lo)
        hi = jnp.where(keep_going_down, hi, mid)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    # lo is the largest probed threshold with count >= k
    return x * (x32 >= lo).astype(x.dtype)


def kwta_local(x: jax.Array, k: int, partitions: int, axis: int = -1) -> jax.Array:
    """Partitioned k-WTA: split ``axis`` into ``partitions`` equal groups and
    select k/partitions winners within each (paper's local k-WTA after convs;
    our per-TP-shard winner selection)."""
    x_m = jnp.moveaxis(x, axis, -1)
    d = x_m.shape[-1]
    if d % partitions:
        raise ValueError(f"dim {d} not divisible by partitions {partitions}")
    if k % partitions:
        raise ValueError(f"k {k} not divisible by partitions {partitions}")
    xp = x_m.reshape(*x_m.shape[:-1], partitions, d // partitions)
    yp = kwta(xp, k // partitions, axis=-1)
    return jnp.moveaxis(yp.reshape(x_m.shape), -1, axis)


def kwta_channel(x: jax.Array, k: int) -> jax.Array:
    """Convolutional k-WTA along the channel (last) dimension per spatial
    location — the paper's conv usage ('competition happens along the channel
    dimension')."""
    return kwta(x, k, axis=-1)


def activation_sparsity(x: jax.Array) -> jax.Array:
    """Fraction of zero entries (diagnostic; paper reports 88-90%)."""
    return jnp.mean((x == 0).astype(jnp.float32))
