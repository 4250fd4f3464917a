"""k-WTA activation tests: exact top-k semantics (the sort-free form and
its Pallas kernel against the ``lax.top_k`` formula), histogram-threshold
approximation bounds, locality, gradients (straight-through on winners)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from jax import lax

from repro.core import (activation_sparsity, kwta, kwta_hist, kwta_local,
                        kwta_mask)
from repro.core.instrument import count_selects
from repro.core.kwta import order_key, topk_keep
from repro.kernels.kwta_exact import kwta_exact_pallas
from repro.kernels.ops import kwta_exact_lastaxis, kwta_exact_op


@given(st.integers(1, 64), st.integers(2, 6), st.integers(0, 999))
@settings(max_examples=40, deadline=None)
def test_kwta_exact_count_and_values(k, rows, seed):
    d = 128
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(rows, d)).astype(np.float32))
    y = kwta(x, k)
    nz = (y != 0).sum(axis=-1)
    assert (np.asarray(nz) == k).all()
    # winners keep their values; they are the k largest
    srt = jnp.sort(x, axis=-1)[:, ::-1]
    thresh = srt[:, k - 1:k]
    assert bool(jnp.all(jnp.where(y != 0, y >= thresh, True)))
    assert bool(jnp.all(jnp.where(y != 0, y == x, True)))


@given(st.integers(4, 40), st.integers(0, 99))
@settings(max_examples=30, deadline=None)
def test_kwta_hist_superset_of_topk(k, seed):
    """Histogram k-WTA keeps >= k values and always includes the true
    winners above the threshold bin (paper's >= semantics)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(3, 200)).astype(np.float32))
    yh = kwta_hist(x, k)
    nz = np.asarray((yh != 0).sum(axis=-1))
    assert (nz >= k).all()
    # histogram cannot keep more than k + (bin occupancy - 1) extras; with
    # 256 bins over 200 gaussian values the overshoot is small
    assert (nz <= k + 40).all()
    yk = kwta(x, k)
    # every exact winner strictly above the threshold survives in hist
    assert bool(jnp.all(jnp.where(yk != 0, (yh == yk) | (yh == 0), True)))


def test_kwta_hist_exact_for_quantized():
    """For 8-bit-style inputs with distinct bins, histogram k-WTA is exact
    (the paper's FPGA operates on 8-bit activations)."""
    rng = np.random.default_rng(0)
    vals = rng.choice(256, size=100, replace=False).astype(np.float32)
    x = jnp.asarray(vals)[None, :] / 255.0
    for k in [1, 5, 25, 99]:
        yh = kwta_hist(x, k)
        yk = kwta(x, k)
        np.testing.assert_array_equal(np.asarray(yh), np.asarray(yk))


def test_kwta_local_partition_counts():
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 64))
    y = kwta_local(x, 8, partitions=4)
    yp = np.asarray(y).reshape(5, 4, 16)
    assert ((yp != 0).sum(axis=-1) == 2).all()  # 2 winners per partition


def test_kwta_gradient_straight_through():
    x = jnp.asarray([[3.0, 1.0, 2.0, 0.5]])
    g = jax.grad(lambda x: jnp.sum(kwta(x, 2) * jnp.arange(1.0, 5.0)))(x)
    np.testing.assert_allclose(np.asarray(g)[0], [1.0, 0.0, 3.0, 0.0])


def test_kwta_k_geq_d_identity():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8))
    np.testing.assert_array_equal(np.asarray(kwta(x, 8)), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(kwta_hist(x, 9)), np.asarray(x))


def test_activation_sparsity_metric():
    x = jnp.asarray([[1.0, 0.0, 0.0, 0.0]])
    assert float(activation_sparsity(x)) == 0.75


def test_kwta_mask_matches():
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 32))
    m = kwta_mask(x, 4)
    y = kwta(x, 4)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(y != 0))


# ---------------------------------------------------------------------------
# Sort-free exact k-WTA against the lax.top_k + scatter formula it replaced
# ---------------------------------------------------------------------------

def _oracle(x, k):
    """The former kwta: lax.top_k, then a scatter of the winners."""
    vals, idx = lax.top_k(x, k)
    return jnp.put_along_axis(jnp.zeros_like(x), idx, vals, axis=-1,
                              inplace=False)


def _oracle_keep(x, k):
    _, idx = lax.top_k(x, k)
    return jnp.put_along_axis(jnp.zeros(x.shape, bool), idx, True, axis=-1,
                              inplace=False)


def _rows(kind, r, d, seed=0):
    x = np.random.default_rng(seed).normal(size=(r, d)).astype(np.float32)
    if kind == "relu":                 # many zero ties
        x = np.maximum(x, 0)
    elif kind == "quantised":          # positive ties
        x = np.round(np.maximum(x, 0) * 2) / 2
    elif kind == "zero":
        x = np.zeros_like(x)
    elif kind == "negative":
        x = -np.abs(x) - 1
    return jnp.asarray(x)


_SHAPES = [(64, 1), (64, 8), (64, 63), (1504, 180)]
_KINDS = ["normal", "relu", "quantised", "zero", "negative"]


def _jnp_keep(x, k):
    d = x.shape[-1]
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return topk_keep(order_key(x), lane, k, d,
                     lambda m: jnp.sum(m, -1, keepdims=True, dtype=jnp.int32))


@pytest.mark.parametrize("kind", _KINDS)
@pytest.mark.parametrize("d,k", _SHAPES)
def test_kwta_equals_topk_oracle(d, k, kind):
    x = _rows(kind, 12, d)
    np.testing.assert_array_equal(np.asarray(kwta(x, k)),
                                  np.asarray(_oracle(x, k)))
    # exactly K winners per row, zero-valued winners counted by the mask
    keep = np.asarray(_jnp_keep(x, k))
    assert (keep.sum(-1) == k).all()
    np.testing.assert_array_equal(keep, np.asarray(_oracle_keep(x, k)))


@pytest.mark.parametrize("kind", ["normal", "relu", "quantised", "zero"])
@pytest.mark.parametrize("d,k", [(64, 8), (1504, 180)])
def test_kwta_gradient_matches_oracle(d, k, kind):
    """Winners take the gradient even where their value is 0."""
    x = _rows(kind, 6, d, seed=1)
    w = _rows("normal", 6, d, seed=2)
    g = jax.grad(lambda x: jnp.sum(kwta(x, k) * w))(x)
    g_ref = jax.grad(lambda x: jnp.sum(_oracle(x, k) * w))(x)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g_ref))


def test_kwta_other_axis_and_negative_zero():
    x = _rows("relu", 6, 64).at[:, ::3].set(-0.0)
    np.testing.assert_array_equal(
        np.asarray(kwta(x.T, 8, axis=0)), np.asarray(_oracle(x, 8).T))


def _grouped(fn, x, groups=4):
    """Run ``fn`` on rows (R, D) laid out as the kernel's (G, D, N)."""
    r, d = x.shape
    x3 = x.reshape(r // groups, groups, d).transpose(1, 2, 0)
    return jax.tree.map(
        lambda y: y.transpose(2, 0, 1).reshape(r, d), fn(x3))


@pytest.mark.parametrize("kind", _KINDS)
@pytest.mark.parametrize("d,k", _SHAPES + [(100, 7)])
def test_kwta_exact_kernel_interpret_matches_oracle(d, k, kind):
    x = _rows(kind, 20, d, seed=3)
    y, keep = _grouped(
        lambda x3: kwta_exact_pallas(x3, k, with_mask=True, interpret=True), x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(_oracle(x, k)))
    np.testing.assert_array_equal(np.asarray(keep) != 0,
                                  np.asarray(_oracle_keep(x, k)))
    np.testing.assert_array_equal(
        np.asarray(_grouped(
            lambda x3: kwta_exact_pallas(x3, k, interpret=True), x)),
        np.asarray(y))


def test_kwta_exact_op_gradient_through_zero_winners():
    x = _rows("relu", 16, 64, seed=4)
    w = _rows("normal", 16, 64, seed=5)
    g = jax.grad(lambda x: jnp.sum(
        _grouped(lambda x3: kwta_exact_op(x3, 8, True), x) * w))(x)
    g_ref = jax.grad(lambda x: jnp.sum(_oracle(x, 8) * w))(x)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g_ref))


@pytest.mark.parametrize("shape,k", [
    ((2, 5, 5, 64), 8),      # leading axis < 64: every row on lanes
    ((64, 3, 64), 8),        # leading axis >= 64: (G, D, N) batch view
    ((3, 1504), 180), ((130, 100), 7)])
def test_kwta_exact_lastaxis_views_match_oracle(shape, k):
    """Both of the kernel's views give the former kwta's values and
    gradient, zero-valued winners included."""
    x = jnp.maximum(jax.random.normal(jax.random.PRNGKey(6), shape), 0)
    w = jax.random.normal(jax.random.PRNGKey(7), shape)
    np.testing.assert_array_equal(
        np.asarray(kwta_exact_lastaxis(x, k, True)), np.asarray(_oracle(x, k)))
    g = jax.grad(lambda x: jnp.sum(kwta_exact_lastaxis(x, k, True) * w))(x)
    g_ref = jax.grad(lambda x: jnp.sum(_oracle(x, k) * w))(x)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g_ref))


def test_kwta_ticks_threshold_select_not_top_k():
    x = jnp.zeros((4, 64))
    with count_selects() as c:
        jax.make_jaxpr(lambda x: kwta(kwta(x, 8), 4))(x)
    assert c.counts["threshold"] == 2 and c.top_k == 0


def test_gsc_forward_stages_three_sort_free_selects():
    from repro.models import gsc_cnn as G
    cfg = G.GSCConfig(variant="sparse_sparse")
    params, _ = G.init_model(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((2, 32, 32, 1))
    with count_selects() as c:
        jaxpr = jax.make_jaxpr(lambda p, x: G.forward(p, x, cfg))(params, x)
    assert c.counts["threshold"] == 3 and c.top_k == 0
    assert not re.search(r"\b(top_k|sort)\[", str(jaxpr))
