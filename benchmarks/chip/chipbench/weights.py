"""Weights from the seed, made by the reference file on the device."""

from __future__ import annotations

import numpy as np

from .traffic import seed_words


def seed_key(seed: int):
    """A PRNG key from all the bits of ``seed`` (``PRNGKey`` keeps 32)."""
    import jax
    words = seed_words(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def make_params(run):
    """The reference's ``make_weights`` for this run's seed, in one
    jitted call on the device."""
    import jax
    m = run.cell.config["model"]
    make = jax.jit(lambda k: run.reference.make_weights(k, m))
    return jax.block_until_ready(make(seed_key(run.seed)))


def check_layout(params, program_init) -> None:
    """``params`` must have the tree, shapes and dtypes of what the
    program's own ``program_init(key)`` makes."""
    import jax
    want = jax.eval_shape(program_init, jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if got != want:
        raise ValueError("the reference's weights do not match the "
                         "program's parameter layout")
