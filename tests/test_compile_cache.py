"""The persistent compilation cache lives in one fixed place."""

import jax

from repro.launch import compile_cache


def test_env_dir_wins_and_nothing_is_set(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(compile_cache.CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = compile_cache.CACHE_DIR.parent
    assert compile_cache.CACHE_DIR.name == ".jax_cache"
    assert (root / "src" / "repro").is_dir()
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
