"""Where the entry points keep JAX's persistent compilation cache."""
import jax
import pytest

from repro.compile_cache import DEFAULT_CACHE_DIR, REPO_ROOT, enable_compile_cache


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory after a test changes it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_is_left_to_jax(monkeypatch, tmp_path, cache_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    # nothing is set in code: JAX reads the variable itself
    assert jax.config.jax_compilation_cache_dir is None


def test_unset_env_uses_the_fixed_checkout_path(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    assert first == enable_compile_cache() == str(DEFAULT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
    assert DEFAULT_CACHE_DIR.parent == REPO_ROOT
    assert (REPO_ROOT / "pyproject.toml").is_file()
