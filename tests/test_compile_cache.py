"""repro.compile_cache: where the entry points keep JAX's persistent
compilation cache, and that importing the package sets none."""
import os
from pathlib import Path

import jax
import pytest

import repro.exp  # noqa: F401 - the import must not place a cache
import repro.fl  # noqa: F401
from repro.compile_cache import DEFAULT_CACHE_DIR, use_compile_cache

REPO_ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def keep_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_import_sets_no_cache():
    assert jax.config.jax_compilation_cache_dir == \
        os.environ.get("JAX_COMPILATION_CACHE_DIR")


def test_env_dir_is_left_in_place(monkeypatch, keep_cache_dir, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == keep_cache_dir


def test_default_dir_is_in_the_checkout(monkeypatch, keep_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    used = use_compile_cache()
    assert used == str(REPO_ROOT / ".jax_cache") == str(DEFAULT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == used
    assert use_compile_cache() == used        # fixed: no pid or timestamp
