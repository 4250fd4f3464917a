"""Plain reference of the paper's GSC keyword-spotting network (Table 1),
sparse-sparse variant, and the weights both it and the program serve.

Input 32x32x1 -> conv 5x5, 64 channels (VALID) -> ReLU -> k-WTA over the
channels (8 of 64) -> max-pool 2 -> conv 5x5, 64 -> ReLU -> k-WTA (8 of
64) -> max-pool 2 -> flatten 1600 -> linear 1500 (padded to a multiple of
its pack factor, 1504) -> ReLU -> k-WTA (180 winners) -> linear 12.  The
convolutions and the first linear layer are complementary-sparse: each
output keeps one weight in every partition of N inputs (the flattened
receptive field, in (row, column, channel) order, is the input axis).

Written from that description in plain ``jax.numpy`` at float32 with the
highest matmul precision; the packed weights are expanded to dense masked
matrices and the convolutions run as convolutions.  It imports nothing of
the program.  :func:`make_weights` builds the parameters from a key in
the layout the program serves, in one jitted call on the device.
"""

from __future__ import annotations

import functools
import json
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def _pad(d: int, n: int) -> int:
    return -(-d // n) * n


def _packed(key, d_in: int, d_out: int, n: int):
    """Packed layer with one route table per group and a bias."""
    d_in_p, d_out_p = _pad(d_in, n), _pad(d_out, n)
    g, p = d_out_p // n, d_in_p // n
    kw, kr, kb = jax.random.split(key, 3)
    scale = float(np.sqrt(n / d_in_p))
    return {"packed": jax.random.uniform(kw, (g, p, n), jnp.float32,
                                         -scale, scale),
            "route": jnp.argsort(jax.random.uniform(kr, (g, p, n)),
                                 axis=-1).astype(jnp.int8),
            "b": jax.random.uniform(kb, (d_out,), jnp.float32, -0.05, 0.05)}


def make_weights(key, m: Dict):
    c, hidden_p = m["channels"], _pad(m["hidden"], m["linear_n"])
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    scale = hidden_p ** -0.5
    return {
        "conv1": _packed(k1, 25, c, m["conv1_n"]),
        "conv2": _packed(k2, 25 * c, c, m["conv2_n"]),
        "linear": _packed(k3, 25 * c, hidden_p, m["linear_n"]),
        "out": {"w": jax.random.uniform(k4, (hidden_p, m["n_classes"]),
                                        jnp.float32, -scale, scale),
                "b": jax.random.uniform(k5, (m["n_classes"],), jnp.float32,
                                        -0.05, 0.05)},
    }


def unpack(packed, route):
    """Dense ``(P·N, G·N)`` weight: output ``g·N + s`` keeps input
    ``p·N + route[g, p, s]`` with the weight ``packed[g, p, s]``."""
    g, p, n = packed.shape
    hit = route[:, :, :, None] == jnp.arange(n, dtype=route.dtype)
    w = jnp.where(hit, packed[..., None], jnp.zeros((), packed.dtype))
    return w.transpose(1, 3, 0, 2).reshape(p * n, g * n)  # (p i, g s)


def _kwta(x, k):
    """Keep the ``k`` largest entries of the last axis, zero the rest."""
    kth = jnp.sort(x, axis=-1)[..., -k][..., None]
    return jnp.where(x >= kth, x, 0)


def _pool(x):
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def logits(params, x, m: Dict, low: bool = False):
    """Class logits ``(B, n_classes)`` of inputs ``x (B, 32, 32, 1)``;
    ``low`` computes every step in bfloat16 (the control)."""
    dt = jnp.bfloat16 if low else jnp.float32
    prec = None if low else jax.lax.Precision.HIGHEST
    c = m["channels"]

    def conv(x, layer, c_in):
        w = unpack(layer["packed"].astype(dt), layer["route"])[:25 * c_in, :c]
        y = jax.lax.conv_general_dilated(
            x, w.reshape(5, 5, c_in, c), (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=prec,
            preferred_element_type=dt)
        return y + layer["b"].astype(dt)

    h = _pool(_kwta(jax.nn.relu(conv(x.astype(dt), params["conv1"], 1)),
                    m["conv_k"]))
    h = _pool(_kwta(jax.nn.relu(conv(h, params["conv2"], c)), m["conv_k"]))
    h = h.reshape(h.shape[0], -1)
    lin = params["linear"]
    w = unpack(lin["packed"].astype(dt), lin["route"])
    h = jnp.matmul(h, w, precision=prec, preferred_element_type=dt)
    h = _kwta(jax.nn.relu(h + lin["b"].astype(dt)), m["linear_k"])
    out = jnp.matmul(h, params["out"]["w"].astype(dt), precision=prec,
                     preferred_element_type=dt)
    return (out + params["out"]["b"].astype(dt)).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("m_json", "low"))
def batch_logits(params, x, m_json: str, low: bool = False):
    return logits(params, x, json.loads(m_json), low)
