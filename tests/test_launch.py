"""Entry-point plumbing: where the compile cache goes."""

import jax

from repro.launch import compile_cache


def _restore_cache_dir(prev):
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert compile_cache.use_compile_cache() == str(tmp_path)
        # nothing was set: JAX reads the variable itself
        assert jax.config.jax_compilation_cache_dir == prev
    finally:
        _restore_cache_dir(prev)


def test_compile_cache_defaults_to_fixed_checkout_path(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.use_compile_cache()
        assert path == str(compile_cache.REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        # fixed: a second call (another process, another time) agrees
        assert compile_cache.use_compile_cache() == path
        assert compile_cache.REPO_CACHE_DIR.parent.joinpath(
            "src", "repro").is_dir()
    finally:
        _restore_cache_dir(prev)
