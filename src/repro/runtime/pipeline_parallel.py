"""GPipe-style pipeline parallelism over a dedicated mesh axis, built with
``shard_map`` + ``ppermute``.

The stacked stage params (leading dim = n_stages) shard over the ``pipe``
axis, so each device holds one stage.  A microbatched GPipe schedule runs
``n_micro + n_stages - 1`` ticks; at each tick every stage processes the
activation it holds and ``ppermute`` shifts activations to the next stage.
Bubble fraction = (S-1)/(M+S-1), reported by :func:`bubble_fraction`.

This is the optional PP building block (DESIGN.md §6): the assigned
production mesh is (data, model), but the trainer can carve a ``pipe``
axis for deeper models; tests validate numerics against the unpipelined
reference on a 4-device CPU mesh.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_apply(stage_fn: Callable, mesh: Mesh, axis: str,
                   stage_params, x, n_micro: int):
    """Run ``x`` through ``n_stages`` of ``stage_fn`` as a GPipe pipeline.

    Args:
      stage_fn: (params_slice, activation) -> activation; applied by every
        stage (homogeneous stages).
      stage_params: pytree with leading dim n_stages on every leaf.
      x: (batch, ...) global input; batch must divide n_micro.
      n_micro: number of microbatches.

    Returns: y with x's shape (the pipeline output of the last stage).
    """
    n_stages = mesh.shape[axis]
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(f"batch {b} must divide n_micro {n_micro}")
    mb = b // n_micro

    def local(params, x_local):
        # params: this stage's slice (leading dim 1); x_local: full batch
        # (replicated input; stage 0 feeds the pipe).
        params = jax.tree.map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)
        micro = x_local.reshape(n_micro, mb, *x_local.shape[1:])
        n_ticks = n_micro + n_stages - 1
        buf = jnp.zeros((mb, *x_local.shape[1:]), x_local.dtype)
        outs = jnp.zeros((n_micro, mb, *x_local.shape[1:]), x_local.dtype)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any remain)
            feed = micro[jnp.minimum(t, n_micro - 1)]
            buf = jnp.where(stage == 0,
                            jnp.where(t < n_micro, feed, buf), buf)
            y = stage_fn(params, buf)
            # last stage emits microbatch (t - (n_stages - 1))
            out_idx = t - (n_stages - 1)
            emit = jnp.logical_and(stage == n_stages - 1, out_idx >= 0)
            outs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(out_idx, 0), 0),
                lambda o: o, outs)
            # shift activations forward one stage
            buf = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return buf, outs

        _, outs = jax.lax.fori_loop(0, n_ticks, tick, (buf, outs))
        # all-gather the last stage's outputs so every shard returns y
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs.reshape(b, *x_local.shape[1:])

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec_params, P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)
