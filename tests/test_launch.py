"""Where the entry points (``chip_smoke.py``, ``repro.launch.serve``) keep
JAX's persistent compilation cache."""

import jax

from repro.launch import compile_cache


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache(tmp_path) == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before    # sets no other


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    want = str(compile_cache.REPO / ".jax_cache")
    try:
        assert compile_cache.use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert compile_cache.use_compile_cache() == want        # stable
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert (compile_cache.REPO / "chip_smoke.py").is_file()
