"""The persistent compilation cache helper of the chip entry points."""
import os

import jax

from repro.launch import compile_cache


def _restore(was):
    jax.config.update("jax_compilation_cache_dir", was)


def test_honours_environment_variable(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    try:
        assert compile_cache.enable_compile_cache() == "/somewhere/else"
        # JAX reads the variable itself; the helper sets nothing over it
        assert jax.config.jax_compilation_cache_dir == was
    finally:
        _restore(was)


def test_fixed_path_inside_the_checkout(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    try:
        first = compile_cache.enable_compile_cache()
        second = compile_cache.enable_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert first == second == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        _restore(was)
