"""Distribution tests on a small multi-device CPU mesh.

conftest.py pins XLA_FLAGS to 8 host devices for the test session (small,
so smoke tests stay fast) — these tests exercise real GSPMD partitioning,
shard_map pipeline parallelism, and compressed gradient sync.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import TrainConfig, get_config
from repro.data import batch_for
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_step, zero1_specs
from repro.models import init_model, loss_fn
from repro.optim import init_state, init_residuals, make_compressed_grad_sync
from repro.runtime import pipeline_apply
from repro.sharding import make_rules, param_sharding, use_rules

needs_devices = pytest.mark.skipif(jax.device_count() < 8,
                                   reason="needs 8 fake CPU devices")


class _Shape:
    seq_len = 32
    global_batch = 4


@needs_devices
def test_sharded_train_step_matches_single_device():
    """The same train step on a (2,2) mesh and a (1,1) mesh must produce
    identical losses and parameters — SPMD correctness end to end."""
    cfg = get_config("smollm_360m").reduced(n_heads=4, n_kv_heads=2)
    tcfg = TrainConfig(lr=1e-3, zero1=True)
    batch_np = batch_for(cfg, _Shape, step=0)

    def run(mesh_dims):
        mesh = make_mesh(mesh_dims, ("data", "model"))
        rules = make_rules(mesh, "train")
        with use_rules(rules):
            params, specs = init_model(jax.random.PRNGKey(0), cfg)
            p_shard = param_sharding(specs, params, rules)
            params = jax.device_put(params, p_shard)
            train_step, acfg = make_train_step(cfg, tcfg)
            opt = init_state(params, acfg)
            batch = {k: jax.device_put(
                jnp.asarray(v),
                rules.sharding_for(("batch",) + (None,) * (v.ndim - 1),
                                   v.shape)) for k, v in batch_np.items()}
            params, opt, m = jax.jit(train_step)(params, opt, batch)
            leaves = [np.asarray(x, np.float32)
                      for x in jax.tree.leaves(params)
                      if jnp.issubdtype(x.dtype, jnp.floating)]
            return float(m["loss"]), leaves

    loss1, p1 = run((1, 1))
    loss2, p2 = run((2, 2))
    assert abs(loss1 - loss2) < 5e-3
    for a, b in zip(p1, p2):
        np.testing.assert_allclose(a, b, atol=5e-3)


@needs_devices
def test_zero1_specs_shard_moments():
    cfg = get_config("smollm_360m").reduced()
    mesh = make_mesh((4, 2), ("data", "model"))
    rules = make_rules(mesh, "train")
    with use_rules(rules):
        params, specs = init_model(jax.random.PRNGKey(0), cfg)
    z = zero1_specs(specs, params, rules)
    # the embed table spec gained a dp axis on a previously-None dim
    emb = z["embed"]["table"]
    assert ("data",) in emb or "data" in str(emb)


@needs_devices
def test_pipeline_parallel_matches_reference():
    mesh = make_mesh((4,), ("pipe",))
    n_stages, d = 4, 16
    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (n_stages, d, d)) / np.sqrt(d)

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    x = jax.random.normal(jax.random.PRNGKey(1), (8, d))
    y_pipe = pipeline_apply(stage_fn, mesh, "pipe", ws, x, n_micro=4)
    y_ref = x
    for i in range(n_stages):
        y_ref = stage_fn(ws[i], y_ref)
    np.testing.assert_allclose(np.asarray(y_pipe), np.asarray(y_ref),
                               atol=1e-5)


@needs_devices
def test_compressed_grad_sync_cross_pod():
    """int8 EF sync over the pod axis ~= exact mean; residual holds the
    difference."""
    mesh = make_mesh((2, 4), ("pod", "data"))
    sync = make_compressed_grad_sync(mesh, "pod")
    rng = np.random.default_rng(0)
    g_global = jnp.asarray(rng.normal(size=(2, 64)).astype(np.float32))
    # per-pod grads: place with pod-major sharding so pod p sees row p
    grads = {"w": jax.device_put(
        g_global, NamedSharding(mesh, P("pod", None)))}
    # trick: treat the (2, 64) array as per-pod rows; inside shard_map with
    # spec P() it would be full — instead emulate by calling sync on the
    # mean semantics directly:
    resid = init_residuals({"w": jnp.zeros((64,))}, n_pods=2)
    # feed per-pod values via the replicated-in path: each pod's local
    # value is its own row; emulate by running the local function under
    # shard_map with in_spec P('pod') for grads as well.
    from jax.sharding import PartitionSpec
    import jax as _jax

    def local(g, r):
        # g: (1, 64) this pod's grads; psum/EF inside
        from repro.optim.compression import _ef_psum_leaf
        out, r_new = _ef_psum_leaf(g[0], r[0], "pod", 2)
        return out[None], r_new[None]

    out, resid_new = jax.shard_map(
        local, mesh=mesh, in_specs=(P("pod"), P("pod")),
        out_specs=(P("pod"), P("pod")), check_vma=False,
    )(grads["w"][:, :], resid["w"])
    # both pods converge to (approximately) the mean
    mean_true = np.asarray(g_global).mean(axis=0)
    got = np.asarray(out)
    np.testing.assert_allclose(got[0], mean_true, atol=0.05)
    np.testing.assert_allclose(got[0], got[1], atol=1e-6)


@needs_devices
def test_rules_divisibility_fallback():
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = make_rules(mesh, "train")
    # 6 heads can't shard over model=4 -> replicated
    assert rules.spec_for(("batch", "heads"), (8, 6)) == P(("data",), None)
    assert rules.spec_for(("batch", "heads"), (8, 8)) == P(("data",), "model")
    # batch=1 can't shard over data -> replicated
    assert rules.spec_for(("batch", None), (1, 8)) == P(None, None)


@needs_devices
def test_decode_rules_shard_kv_seq():
    mesh = make_mesh((2, 4), ("data", "model"))
    rules = make_rules(mesh, "decode")
    spec = rules.spec_for(("batch", "kvseq", "kv", None), (8, 64, 4, 16))
    assert spec == P(("data",), "model", "kv" if False else None, None) or \
        spec[1] == "model"
    rules_long = make_rules(mesh, "decode_long")
    spec = rules_long.spec_for(("batch", "kvseq", None, None), (1, 64, 4, 16))
    assert spec[0] is None and spec[1] == ("data", "model")
