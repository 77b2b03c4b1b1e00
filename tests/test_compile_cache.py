"""The persistent compilation cache helper of the entry points."""
from pathlib import Path

import jax

from repro.utils.compile_cache import ENV, enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_cache_dir_follows_env_and_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv(ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_fixed_ignored_checkout_dir(monkeypatch):
    monkeypatch.delenv(ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert enable_compile_cache() == path       # stable across calls
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored
