"""The packed convolution's two forms at the GSC network's layouts.

Off the TPU, ``packed_conv2d_apply`` runs the paper's im2col +
Multiply-Route-Sum.  On a TPU it runs ``packed_conv2d_mxu``: one f32
convolution over the decompressed kernel.  These tests hold the second
to the first (outputs and packed-weight gradients, on the CPU), and read
what the TPU dispatch stages with the backend reported as ``"tpu"``
(``jax.make_jaxpr`` only: nothing is lowered or run for the TPU here).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.jaxpr_walk import iter_eqns
from repro.core import decompress
from repro.core.api import SparsityConfig, observe_dispatch
from repro.core.kwta import kwta_channel
from repro.core.layers import (im2col, packed_conv2d_apply,
                               packed_conv2d_init, packed_conv2d_mxu,
                               packed_linear_apply)

# (c_in, n, k_frac, input (H, W)) of GSC's conv1 (the dense network input;
# d_out 64 pads to 65) and conv2 (channel k-WTA input, 8 of 64).
LAYOUTS = {"conv1": (1, 5, None, (12, 12)),
           "conv2": (64, 16, 8 / 64, (9, 9))}


def _case(name, seed=0):
    c_in, n, k_frac, (h, w) = LAYOUTS[name]
    cfg = SparsityConfig(n=n, k_frac=k_frac)
    params, _ = packed_conv2d_init(jax.random.PRNGKey(seed), 5, 5, c_in, 64,
                                   cfg, seed=seed + 41)
    params["b"] = 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                          params["b"].shape)
    x = jax.nn.relu(jax.random.normal(jax.random.PRNGKey(seed + 2),
                                      (2, h, w, c_in)))
    if k_frac is not None:
        x = kwta_channel(x, round(k_frac * c_in))
    return params, x, cfg


def _hadamard(params, x, cfg):
    """The faithful form, as the parent's ``packed_conv2d_apply`` staged it."""
    return packed_linear_apply(params, im2col(x, 5, 5), cfg,
                               x_is_sparse=cfg.activation_sparse)


def _close(a, b):
    """Equal to f32 rounding: sums of the same products in another order."""
    b = np.asarray(b)
    np.testing.assert_allclose(np.asarray(a), b, rtol=1e-5,
                               atol=1e-5 * float(np.max(np.abs(b))))


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_conv_mxu_matches_hadamard_outputs_and_grads(name):
    params, x, cfg = _case(name)
    if name == "conv1":
        assert params["packed"].shape[0] * 5 == 65  # d_out padded 64 -> 65
    _close(packed_conv2d_mxu(params, x, 5, 5), _hadamard(params, x, cfg))

    t = jax.random.normal(jax.random.PRNGKey(7), (2, x.shape[1] - 4,
                                                  x.shape[2] - 4, 64))

    def loss(form):
        def f(packed):
            return jnp.sum(form({**params, "packed": packed}) * t)
        return jax.grad(f)(params["packed"])

    g_mxu = loss(lambda p: packed_conv2d_mxu(p, x, 5, 5))
    g_had = loss(lambda p: _hadamard(p, x, cfg))
    _close(g_mxu, g_had)

    # The convolution form's gradient is the dense kernel's gradient on the
    # packed support, and nothing off it (nor on the padded output).
    c_in = x.shape[-1]
    mask = np.asarray(decompress(jnp.ones_like(params["packed"]),
                                 params["route"])) != 0
    w = decompress(params["packed"], params["route"])[:25 * c_in, :64]

    def dense_loss(w):
        y = jax.lax.conv_general_dilated(
            x, w.reshape(5, 5, c_in, 64), (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)
        return jnp.sum((y + params["b"]) * t)

    g_dense = np.asarray(jax.grad(dense_loss)(w))
    g_spread = np.asarray(decompress(g_mxu, params["route"]))
    assert np.all(g_spread[~mask] == 0)
    assert np.all(g_spread[:, 64:] == 0)
    _close(g_spread[:25 * c_in, :64],
           np.where(mask[:25 * c_in, :64], g_dense, 0))


def _tpu_jaxpr(monkeypatch, name):
    params, x, cfg = _case(name)
    events = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with observe_dispatch(events.append):
        jaxpr = jax.make_jaxpr(
            lambda p, x: packed_conv2d_apply(
                p, x, cfg, 5, 5, x_is_sparse=cfg.activation_sparse))(
            params, x)
    return jaxpr, events


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_tpu_dispatch_stages_one_highest_conv(monkeypatch, name):
    jaxpr, events = _tpu_jaxpr(monkeypatch, name)
    prims = [at.eqn.primitive.name for at in iter_eqns(jaxpr)]
    convs = [at.eqn for at in iter_eqns(jaxpr)
             if at.eqn.primitive.name == "conv_general_dilated"]
    assert len(convs) == 1
    precision = convs[0].params["precision"]
    precision = precision if isinstance(precision, tuple) else (precision,)
    assert precision and all(p == jax.lax.Precision.HIGHEST
                             for p in precision)
    assert "gather" not in prims           # no routed activation
    assert "dot_general" not in prims
    assert [e["path"] for e in events] == ["conv_mxu"]
    c_in, n = LAYOUTS[name][:2]
    assert events[0]["batch"] == 2 * (LAYOUTS[name][3][0] - 4) ** 2
    assert events[0]["d_in"] == 25 * c_in and events[0]["n"] == n


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_cpu_stages_the_faithful_jaxpr(name):
    params, x, cfg = _case(name)
    sparse = cfg.activation_sparse
    got = jax.make_jaxpr(lambda p, x: packed_conv2d_apply(
        p, x, cfg, 5, 5, x_is_sparse=sparse))(params, x)
    want = jax.make_jaxpr(lambda p, x: _hadamard(p, x, cfg))(params, x)
    assert str(got) == str(want)
    assert "gather" in str(got)
