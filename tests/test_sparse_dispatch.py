"""Sparse-sparse dispatch tests: the one-Select-per-layer handoff, the
batched topk_gather kernel vs the jnp formulas across layouts, the
backend-aware executor, and the kernel's argument validation."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (CSLayout, SparsityConfig, choose_executor,
                        count_selects, cs_topk_from_support, cs_topk_matmul,
                        kwta, kwta_support, make_routes, pack_dense,
                        routes_to_mask, topk_support_flat)
from repro.core.layers import (apply_kwta, packed_linear_apply,
                               packed_linear_init)
from repro.kernels import (to_partition_major, topk_gather_matmul,
                           topk_gather_op, topk_gather_support_op,
                           topk_support)


def make_case(d_in, d_out, n, seed=0, route_share=1):
    lay = CSLayout(d_in, d_out, n)
    g = lay.groups
    r = g if route_share == 0 else min(route_share, g)
    while g % r:
        r -= 1
    route = make_routes(CSLayout(d_in, n * (g // r), n), seed)
    route_full = np.broadcast_to(
        route[:, None], (g // r, r, lay.partitions, n)).reshape(
        g, lay.partitions, n)
    rng = np.random.default_rng(seed + 1)
    w = rng.normal(size=(d_in, d_out)).astype(np.float32)
    w = w * routes_to_mask(lay, route_full)
    packed = pack_dense(lay, w, route_full)
    return jnp.asarray(w), jnp.asarray(packed), jnp.asarray(route)


# ---------------------------------------------------------------------------
# batched kernel vs jnp formula: route sharing, batch regimes, block tiling
# ---------------------------------------------------------------------------

# route_share 0 = one table for all groups, 1 = faithful per-group,
# 99 >= G = per-group after the divisor fallback.
@pytest.mark.parametrize("route_share", [0, 1, 99])
@pytest.mark.parametrize("b", [1, 3, 8, 16])
def test_batched_kernel_matches_jnp_paths(route_share, b):
    """Interpret-mode batched topk_gather vs F.cs_topk_matmul vs the masked
    dense matmul, across route sharing and batch sizes straddling the
    B*K < D_in crossover (D_in=64, K=8: topk wins below B=8)."""
    d_in, d_out, n, k = 64, 32, 4, 8
    w, packed, route = make_case(d_in, d_out, n, seed=route_share + 1,
                                 route_share=route_share)
    x = jax.random.normal(jax.random.PRNGKey(b), (b, d_in))
    xs = kwta(x, k)
    y_jnp = cs_topk_matmul(xs, packed, route, k)
    vals, idx = topk_support_flat(xs, k)
    y_pl = topk_gather_support_op(vals, idx // n, idx % n, packed, route,
                                  True)
    np.testing.assert_allclose(np.asarray(y_jnp), np.asarray(xs @ w),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(xs @ w),
                               atol=1e-4)


def test_batched_kernel_block_g_tiling():
    """block_g < G sweeps the group grid dimension; results must not move.
    A group tile spans block_g*N lanes, so block_g*N is a multiple of 128."""
    d_in, d_out, n, k = 64, 512, 4, 8
    w, packed, route = make_case(d_in, d_out, n, seed=3)
    x = kwta(jax.random.normal(jax.random.PRNGKey(0), (4, d_in)), k)
    vals, p_idx, s_off = topk_support(x, k, n)
    pr, rr = to_partition_major(packed, route)
    full = topk_gather_matmul(vals, p_idx, s_off, pr, rr, interpret=True)
    for block_g in (32, 64, 128):
        tiled = topk_gather_matmul(vals, p_idx, s_off, pr, rr,
                                   block_g=block_g, interpret=True)
        np.testing.assert_allclose(np.asarray(tiled), np.asarray(full),
                                   atol=1e-5)
    np.testing.assert_allclose(np.asarray(full), np.asarray(x @ w),
                               atol=1e-4)


def test_packed_linear_padded_bias_sliced_layout():
    """d_in/d_out not divisible by N: inputs zero-pad, outputs slice back to
    the bias length — identical on the jnp and forced-Pallas executors, and
    with/without the k-WTA support handoff."""
    d_in, d_out, n, k = 62, 30, 4, 8
    cfg = SparsityConfig(n=n, k_frac=k / d_in, path="topk")
    params, _ = packed_linear_init(jax.random.PRNGKey(0), d_in, d_out, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, d_in))
    h, support = apply_kwta(x, cfg, return_support=True)
    y_ref = packed_linear_apply(params, h,
                                dataclasses.replace(cfg, path="hadamard"))
    for use_pallas in ("off", "force"):
        cfg_x = dataclasses.replace(cfg, use_pallas=use_pallas)
        y_hand = packed_linear_apply(params, h, cfg_x, x_is_sparse=True,
                                     support=support)
        y_self = packed_linear_apply(params, h, cfg_x, x_is_sparse=True)
        assert y_hand.shape == (3, d_out)
        np.testing.assert_allclose(np.asarray(y_hand), np.asarray(y_ref),
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(y_self), np.asarray(y_ref),
                                   atol=1e-4)


def test_kernel_on_multi_device_mesh_matches_jnp():
    """On a mesh of more than one device the kernel runs inside a
    replicated shard_map (no compiler partitions a Mosaic kernel); the
    result must still equal the masked dense matmul."""
    from repro.launch.mesh import make_mesh
    from repro.sharding import make_rules, use_rules
    d_in, d_out, n, k = 64, 32, 4, 8
    cfg = SparsityConfig(n=n, k_frac=k / d_in, path="topk",
                         use_pallas="force")
    w, packed, route = make_case(d_in, d_out, n, seed=21)
    params = {"packed": packed, "route": route}
    x = kwta(jax.random.normal(jax.random.PRNGKey(3), (4, d_in)), k)
    mesh = make_mesh((1, 4), ("data", "model"), devices=jax.devices()[:4])

    def f(p, x):
        return packed_linear_apply(p, x, cfg, x_is_sparse=True)

    with use_rules(make_rules(mesh, "decode")):
        assert "shard_map" in str(jax.make_jaxpr(f)(params, x))
        y = jax.jit(f)(params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), atol=1e-4)


def test_auto_path_crossover_consistency():
    """path='auto' flips topk -> hadamard when B*K >= D_in; both sides of
    the crossover must agree with the masked dense matmul."""
    d_in, d_out, n, k = 64, 32, 4, 8
    cfg = SparsityConfig(n=n, k_frac=k / d_in)
    w, packed, route = make_case(d_in, d_out, n, seed=9)
    params = {"packed": packed, "route": route}
    for b in (2, 4, 8, 32):   # crossover at B*8 < 64 -> B < 8
        x = kwta(jax.random.normal(jax.random.PRNGKey(b), (b, d_in)), k)
        y = packed_linear_apply(params, x, cfg, x_is_sparse=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                                   atol=1e-4)


def test_support_op_handles_leading_batch_dims():
    """The serving shape (B, S=1, D) flattens to one kernel launch."""
    d_in, d_out, n, k = 64, 32, 4, 8
    w, packed, route = make_case(d_in, d_out, n, seed=5)
    x = kwta(jax.random.normal(jax.random.PRNGKey(2), (4, 1, d_in)), k)
    vals, idx = topk_support_flat(x, k)
    y = topk_gather_support_op(vals, idx // n, idx % n, packed, route, True)
    assert y.shape == (4, 1, d_out)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), atol=1e-4)


# ---------------------------------------------------------------------------
# one Select per sparse layer (the Fig. 8a pipeline contract)
# ---------------------------------------------------------------------------

def test_ffn_issues_exactly_one_topk_per_layer():
    from repro.models.ffn import ffn_apply, ffn_init
    cfg_sp = SparsityConfig(n=4, k_frac=0.125)
    params, _ = ffn_init(jax.random.PRNGKey(0), 64, 256, cfg_sp)
    x = jnp.zeros((2, 1, 64))
    with count_selects() as c:
        jax.make_jaxpr(lambda x: ffn_apply(params, x, cfg_sp))(x)
    assert c.top_k == 1, (
        "sparse-sparse FFN must run ONE Select: the k-WTA support is handed "
        "to the down projection instead of re-running top_k")


def test_serve_step_issues_one_topk_per_sparse_layer(lint_clean):
    """Decode through the whole transformer: exactly one top_k staged per
    sparse FFN in the scanned superblock (and none anywhere else)."""
    from repro.analysis import expected_selects
    from repro.configs import get_config
    from repro.models import transformer as T
    cfg = get_config("smollm-360m").reduced(
        d_model=64, d_ff=256, vocab_size=256, n_heads=4, n_kv_heads=2,
        head_pad=0, compute_dtype="float32", param_dtype="float32",
        ffn_sparsity=SparsityConfig(n=4, k_frac=0.125))
    params, _ = T.init_model(jax.random.PRNGKey(0), cfg)
    cache, _ = T.init_cache(cfg, 2, 8)
    batch = {"tokens": jnp.zeros((2, 1), jnp.int32)}
    pos = jnp.zeros((2,), jnp.int32)
    n_sparse_per_unit = sum(k == "attn" for k in cfg.block_pattern)
    with count_selects() as c:
        jax.make_jaxpr(lambda p, c, b, pos: T.serve_step(p, c, b, pos, cfg))(
            params, cache, batch, pos)
    assert c.top_k == n_sparse_per_unit
    # and the static analyzer agrees, layer by layer
    lint_clean(lambda p, c, b, q: T.serve_step(p, c, b, q, cfg),
               params, cache, batch, pos,
               expected=expected_selects(cfg, n_tokens=2))


def test_cs_topk_matmul_without_handoff_still_one_topk():
    """The standalone sparse-sparse matmul runs its own single Select."""
    _, packed, route = make_case(64, 32, 4)
    with count_selects() as c:
        jax.make_jaxpr(lambda x: cs_topk_matmul(x, packed, route, 8))(
            jnp.zeros((2, 64)))
    assert c.top_k == 1


def test_kwta_support_matches_kwta():
    x = jax.random.normal(jax.random.PRNGKey(7), (5, 96))
    y, (vals, idx) = kwta_support(x, 12)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(kwta(x, 12)))
    np.testing.assert_allclose(
        np.asarray(jnp.take_along_axis(y, idx, axis=-1)), np.asarray(vals))
    # support consumed downstream reproduces the sparse-sparse product
    w, packed, route = make_case(96, 32, 4, seed=8)
    y_sup = cs_topk_from_support(vals, idx // 4, idx % 4, packed, route)
    np.testing.assert_allclose(np.asarray(y_sup), np.asarray(y @ w),
                               atol=1e-4)


# ---------------------------------------------------------------------------
# executor selection (backend-aware; CPU test environment -> no real Pallas)
# ---------------------------------------------------------------------------

def test_choose_executor_modes():
    on_tpu = jax.default_backend() == "tpu"
    ex = choose_executor(SparsityConfig(use_pallas="off"))
    assert not ex.use_pallas
    ex = choose_executor(SparsityConfig(use_pallas="force"))
    assert ex.use_pallas and ex.interpret == (not on_tpu)
    ex = choose_executor(SparsityConfig(use_pallas="auto"))
    assert ex.use_pallas == on_tpu and not ex.interpret


# ---------------------------------------------------------------------------
# kernel argument validation (regression: the reversed divisibility error)
# ---------------------------------------------------------------------------

def _kernel_args(p=16, g=8, n=4, b=1, k=2):
    v = jnp.zeros((b, k))
    i = jnp.zeros((b, k), jnp.int32)
    return v, i, i, jnp.zeros((p, g, n)), jnp.zeros((p, g, n), jnp.int8)


def test_topk_gather_rejects_non_divisor_block_g():
    v, pi, so, pr, rr = _kernel_args()
    with pytest.raises(ValueError, match=r"block_g=3 must divide G=8"):
        topk_gather_matmul(v, pi, so, pr, rr, block_g=3)


def test_topk_gather_rejects_oversized_block_g():
    v, pi, so, pr, rr = _kernel_args()
    with pytest.raises(ValueError, match=r"block_g=16 exceeds G=8"):
        topk_gather_matmul(v, pi, so, pr, rr, block_g=16)


def test_topk_gather_rejects_lane_misaligned_block_g():
    v, pi, so, pr, rr = _kernel_args()
    with pytest.raises(ValueError, match=r"block_g\*N=16 must be a multiple "
                       r"of 128 lanes"):
        topk_gather_matmul(v, pi, so, pr, rr, block_g=4)


def test_topk_gather_rejects_empty_support():
    v, pi, so, pr, rr = _kernel_args(k=1)
    with pytest.raises(ValueError, match=r"k_nnz=0"):
        topk_gather_matmul(v[:, :0], pi[:, :0], so[:, :0], pr, rr)


# ---------------------------------------------------------------------------
# gradients: straight-through on the support, parity with the jnp path
# ---------------------------------------------------------------------------

def test_topk_gather_op_grad_parity_with_jnp():
    """Differentiating through the Pallas call (custom VJP) must equal the
    autodiff of cs_topk_matmul — gradients live only on the selected
    support, for both the packed weights and the input."""
    d_in, d_out, n, k = 128, 64, 4, 16
    _, packed, route = make_case(d_in, d_out, n, seed=11)
    x = kwta(jax.random.normal(jax.random.PRNGKey(4), (4, d_in)), k)

    def loss_pl(p, x):
        return jnp.sum(topk_gather_op(x, p, route, k, True) ** 2)

    def loss_jnp(p, x):
        return jnp.sum(cs_topk_matmul(x, p, route, k) ** 2)

    gp_pl, gx_pl = jax.grad(loss_pl, argnums=(0, 1))(packed, x)
    gp_j, gx_j = jax.grad(loss_jnp, argnums=(0, 1))(packed, x)
    np.testing.assert_allclose(np.asarray(gp_pl), np.asarray(gp_j),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(gx_pl), np.asarray(gx_j),
                               rtol=1e-3, atol=1e-3)
    # input gradient is zero off the support
    off = np.asarray(x) == 0
    assert np.all(np.asarray(gx_pl)[off] == 0)


def test_topk_gather_op_grad_route_share():
    d_in, d_out, n, k = 64, 64, 4, 8
    _, packed, route = make_case(d_in, d_out, n, seed=13, route_share=0)
    x = kwta(jax.random.normal(jax.random.PRNGKey(5), (2, d_in)), k)
    gp_pl = jax.grad(lambda p: jnp.sum(
        topk_gather_op(x, p, route, k, True) ** 2))(packed)
    gp_j = jax.grad(lambda p: jnp.sum(
        cs_topk_matmul(x, p, route, k) ** 2))(packed)
    np.testing.assert_allclose(np.asarray(gp_pl), np.asarray(gp_j),
                               rtol=1e-3, atol=1e-3)
