"""Where the entry points keep JAX's persistent compile cache.

The tests never turn the cache on: with the variable set, ``enable`` only
reports the directory JAX already took from the environment.
"""
import os

import jax

from repro.launch import compile_cache


def test_env_dir_is_used_as_set(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.DEFAULT_DIR == os.path.join(root, ".jax_cache")


def test_cache_key_holds_the_metadata(monkeypatch, tmp_path):
    key = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, key)
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    try:
        compile_cache.enable()
        assert getattr(jax.config, key) is True
    finally:
        jax.config.update(key, before)
