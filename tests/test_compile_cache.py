"""Placement of JAX's persistent compilation cache."""
import jax

from repro import compile_cache


def test_env_var_wins_and_nothing_is_set(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_the_fixed_repo_path(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.configure_compile_cache()
        assert got == str(compile_cache.DEFAULT_DIR)
        assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
        assert (compile_cache.DEFAULT_DIR.parent / "chip_smoke.py").exists()
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
