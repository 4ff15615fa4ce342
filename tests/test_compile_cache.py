"""Where the entry scripts put JAX's persistent compilation cache."""

import jax
import pytest

from repro.platform.compile_cache import enable_compile_cache


@pytest.fixture
def cache_config():
    """Restore the process-wide cache directory after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_environment_places_the_cache(cache_config, monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert enable_compile_cache(tmp_path / "default") == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before


def test_default_directory_without_environment(cache_config, monkeypatch,
                                               tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = enable_compile_cache(tmp_path / "default")
    assert got == str((tmp_path / "default").resolve())
    assert jax.config.jax_compilation_cache_dir == got
