"""Every entry point keeps JAX's compilation cache at one fixed place."""
from pathlib import Path

import jax

from repro.launch.compile_cache import DEFAULT_DIR, ENV, use_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_environment_directory_is_used_as_is(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(ENV, str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads ENV


def test_default_directory_is_fixed_and_ignored_by_git(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(ENV, raising=False)
    try:
        assert use_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
