"""The compile-cache helper: the environment's directory wins; without it
the cache sits at one fixed path inside the checkout."""

import pathlib

import jax
import pytest

from repro.launch import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_wins(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: the helper sets no other directory
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.setup_compile_cache()
    assert first == compile_cache.setup_compile_cache()
    assert pathlib.Path(first) == REPO / ".jax_cache"
    assert jax.config.jax_compilation_cache_dir == first
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()
