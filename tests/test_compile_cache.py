"""Placement of JAX's persistent compilation cache (``repro.compile_cache``):
the environment variable wins untouched; otherwise one fixed directory
inside the checkout, which git ignores."""

from pathlib import Path

import jax
import pytest

from repro.compile_cache import enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_set_means_nothing_set_in_code(monkeypatch, tmp_path,
                                               restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == restore_cache_dir


def test_env_var_unset_means_fixed_dir_in_checkout(monkeypatch,
                                                   restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == first
    assert Path(first) == ROOT / ".jax_cache"
    assert enable_compile_cache() == first          # fixed, not per call
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
