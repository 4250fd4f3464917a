"""Parity on padded / non-divisible shapes — the inputs the
``unmasked-pad`` rule guards.

The kernel wrappers refuse genuinely partial blocks at runtime (the
shared ``block_validation`` divisibility contract), so the sanctioned
way to run a non-divisible logical shape is pad-to-multiple → kernel →
slice — exactly the laundering the verifier models (a padded lane never
reaches the output unmasked, because the pad is zeros and the logical
region is sliced back out).  These tests pin both halves of that
contract for all four Pallas kernels: (a) the padded round-trip matches
the ``ref.py`` oracle on the *original* shape, and (b) the wrappers
reject the partial shape itself with the uniform divisibility error."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CSLayout, kwta, make_routes, pack_dense, routes_to_mask
from repro.kernels import (grouped_cs_matmul, kwta_hist_pallas,
                           packed_matmul, permute_activations,
                           to_partition_major, topk_gather_matmul,
                           topk_support)
from repro.kernels import ref as R


def make_case(d_in, d_out, n, seed=0, dtype=np.float32):
    lay = CSLayout(d_in, d_out, n)
    route = make_routes(lay, seed)
    rng = np.random.default_rng(seed + 1)
    w = rng.normal(size=(d_in, d_out)).astype(dtype)
    w = w * routes_to_mask(lay, route).astype(dtype)
    packed = pack_dense(lay, w, route)
    return jnp.asarray(w), jnp.asarray(packed), jnp.asarray(route)


def _pad_axis(x, axis, to):
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, to - x.shape[axis])
    return jnp.pad(x, pad)


# ---------------------------------------------------------------------------
# packed_matmul: batch 6 over block_b=4 — trailing batch block is partial
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,block_b", [(6, 4), (3, 2), (10, 8)])
def test_packed_matmul_padded_batch(b, block_b):
    d_in, d_out, n = 64, 64, 4
    w, packed, route = make_case(d_in, d_out, n, seed=3)
    pr, rr = to_partition_major(packed, route)
    x = jax.random.normal(jax.random.PRNGKey(0), (b, d_in))
    with pytest.raises(ValueError, match="must divide"):
        packed_matmul(x, pr, rr, block_b=block_b, block_p=8, block_g=8,
                      interpret=True)
    b_pad = -(-b // block_b) * block_b
    y = packed_matmul(_pad_axis(x, 0, b_pad), pr, rr, block_b=block_b,
                      block_p=8, block_g=8, interpret=True)[:b]
    y_ref = R.ref_packed_matmul(x, packed, route)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4)


# ---------------------------------------------------------------------------
# grouped_cs_matmul: batch axis of the (N, B, P) slot-major layout
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,block_b", [(6, 4), (5, 4)])
def test_grouped_padded_batch(b, block_b):
    d_in, d_out, n = 64, 32, 4
    route_s = make_routes(CSLayout(d_in, n, n), seed=4)     # shared route
    x = jax.random.normal(jax.random.PRNGKey(1), (b, d_in))
    xg = permute_activations(x, route_s)
    pk = jax.random.normal(jax.random.PRNGKey(2), (n, d_in // n, d_out // n))
    with pytest.raises(ValueError, match="must divide"):
        grouped_cs_matmul(xg, pk, block_b=block_b, block_p=8, block_g=8,
                          interpret=True)
    b_pad = -(-b // block_b) * block_b
    y = grouped_cs_matmul(_pad_axis(xg, 1, b_pad), pk, block_b=block_b,
                          block_p=8, block_g=8, interpret=True)[:, :b]
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(R.ref_grouped_cs_matmul(xg, pk)),
                               atol=1e-4)


# ---------------------------------------------------------------------------
# topk_gather_matmul: group axis — pad packed/route G with zero groups
# ---------------------------------------------------------------------------

# (a group tile spans block_g*N lanes: a multiple of 128)
@pytest.mark.parametrize("d_out,g_extra,block_g",
                         [(160, 24, 32), (200, 14, 32)])
def test_topk_gather_padded_groups(d_out, g_extra, block_g):
    d_in, n, b, k = 64, 4, 4, 8
    w, packed, route = make_case(d_in, d_out, n, seed=7)
    pr, rr = to_partition_major(packed, route)      # (P, G, N), G = 40, 50
    g = pr.shape[1]
    assert g % block_g, "case must exercise a non-divisible G"
    xs = kwta(jax.random.normal(jax.random.PRNGKey(3), (b, d_in)), k)
    vals, pidx, soff = topk_support(xs, k, n)
    with pytest.raises(ValueError, match="must divide"):
        topk_gather_matmul(vals, pidx, soff, pr, rr, block_g=block_g,
                           interpret=True)
    # Pad G to a block multiple with zero weight groups: padded routes are
    # 0, but their packed values are 0, so any spurious "hit" adds 0.
    g_pad = g + g_extra
    assert g_pad % block_g == 0
    y = topk_gather_matmul(vals, pidx, soff, _pad_axis(pr, 1, g_pad),
                           _pad_axis(rr, 1, g_pad), block_g=block_g,
                           interpret=True)
    # kernel output interleaves groups as (B, nG tiles of block_g*N):
    # slicing the logical region back out means dropping the zero groups
    y = y.reshape(b, g_pad, n)[:, :g].reshape(b, g * n)
    y_ref = R.ref_topk_gather(vals, pidx, soff, pr, rr)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(y), np.asarray(xs @ w), atol=1e-4)


# ---------------------------------------------------------------------------
# kwta_hist: batch rows over block_b — padded rows are all-zero rows
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,block_b", [(6, 4), (7, 4)])
def test_kwta_hist_padded_batch(b, block_b):
    d, k = 128, 16
    x = jax.random.normal(jax.random.PRNGKey(4), (b, d))
    with pytest.raises(ValueError, match="must divide"):
        kwta_hist_pallas(x, k, block_b=block_b, interpret=True)
    b_pad = -(-b // block_b) * block_b
    y = kwta_hist_pallas(_pad_axis(x, 0, b_pad), k, block_b=block_b,
                         interpret=True)[:b]
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(R.ref_kwta_hist(x, k)))
