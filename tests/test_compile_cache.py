"""Where `repro.compile_cache.enable_compile_cache` puts JAX's persistent
compilation cache."""

from pathlib import Path

import jax
import pytest

from repro.compile_cache import CACHE_ENV, enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, tmp_path,
                                            restore_cache_dir):
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_env_uses_one_fixed_ignored_repo_dir(monkeypatch,
                                                   restore_cache_dir):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    path = enable_compile_cache()
    assert path == str(REPO / ".jax_cache") == enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == path
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()
