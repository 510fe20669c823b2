"""Where entry points keep JAX's persistent compilation cache."""

from pathlib import Path

import jax

from repro import compile_cache


def test_env_dir_wins_else_checkout_dir(monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR is left to JAX; without it the cache goes
    to .jax_cache/ at the checkout root, a fixed path."""
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.configure_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.configure_compile_cache()
        root = Path(__file__).resolve().parents[1]
        assert got == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
