"""Placement of the persistent compilation cache used by the entry points."""

import jax
import pytest

from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache


@pytest.fixture()
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_default_cache_dir_is_fixed_under_the_repo(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    assert REPO_CACHE_DIR.name == ".jax_cache"
    assert (REPO_CACHE_DIR.parent / "chip_smoke.py").exists()
    ignored = (REPO_CACHE_DIR.parent / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_environment_cache_dir_is_left_to_jax(monkeypatch, tmp_path, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
