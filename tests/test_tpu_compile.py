"""Compile the main-path Pallas kernels for a TPU v5e that is described,
not attached.

Interpret mode never checks tiling alignment, memory spaces or VMEM
limits; the TPU compiler does.  These tests hand it the kernels at the
shapes decode gives them (smollm-360m's FFN down-projection) and fail on
whatever it refuses.  Nothing runs, so they say nothing about results or
times.  The topology is described inside a fixture, never at import:
only one process at a time may load the TPU compiler's library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import (kwta_exact_pallas, kwta_hist_pallas,
                           topk_gather_matmul)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _down_proj_shape():
    """(P, G, N, K) of smollm-360m's FFN down-projection."""
    cfg = get_config("smollm-360m")
    n = cfg.ffn_sparsity.n
    return cfg.d_ff // n, cfg.d_model // n, n, cfg.ffn_sparsity.k_for(cfg.d_ff)


@pytest.mark.parametrize("b", [1, 4, 7])
def test_topk_gather_compiles_at_smollm_down_proj(one_chip, b):
    p, g, n, k = _down_proj_shape()
    assert b * k < p * n, "decode batch must sit on the topk path"

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = topk_gather_matmul.lower(
        sds((b, k), jnp.float32), sds((b, k), jnp.int32),
        sds((b, k), jnp.int32), sds((p, g, n), jnp.bfloat16),
        sds((p, g, n), jnp.int8)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_kwta_hist_compiles_at_smollm_width(one_chip):
    d_ff = get_config("smollm-360m").d_ff
    x = jax.ShapeDtypeStruct((8, d_ff), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda x: kwta_hist_pallas(x, d_ff // 8)).lower(
        x).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("g,d,k", [(784, 64, 8), (1, 1504, 180)])
def test_kwta_exact_compiles_at_gsc_sites(one_chip, g, d, k):
    """conv1's channel k-WTA at batch 1024 (802816 rows of 64), and the
    linear layer's (1024 rows of 1504)."""
    x = jax.ShapeDtypeStruct((g, d, 1024), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda x: kwta_exact_pallas(x, k)).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("c_in,hw,n,k_frac", [(1, 32, 5, None),
                                               (64, 14, 16, 8 / 64)])
def test_packed_conv_compiles_at_gsc_sites(one_chip, monkeypatch, c_in, hw,
                                           n, k_frac):
    """GSC's conv1 and conv2 at batch 1024 in their TPU form: one
    convolution over the decompressed kernel, with no routed gather.  The
    backend is reported as ``"tpu"`` so that the layer takes that form."""
    from repro.core.api import SparsityConfig
    from repro.core.layers import packed_conv2d_apply, packed_conv2d_init

    cfg = SparsityConfig(n=n, k_frac=k_frac)
    params = jax.eval_shape(lambda k: packed_conv2d_init(
        k, 5, 5, c_in, 64, cfg)[0], jax.random.PRNGKey(0))
    params, x = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        (params, jax.ShapeDtypeStruct((1024, hw, hw, c_in), jnp.float32)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = jax.jit(lambda p, x: packed_conv2d_apply(
        p, x, cfg, 5, 5, x_is_sparse=k_frac is not None)).lower(
        params, x).compile()
    text = compiled.as_text()
    assert " convolution(" in text
    assert " gather(" not in text
