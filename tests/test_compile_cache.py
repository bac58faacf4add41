"""The persistent compilation cache goes where the entry points put it:
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``."""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch.compile_cache import use_compile_cache


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path, restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache(tmp_path) == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_under_repo(monkeypatch, tmp_path,
                                         restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = tmp_path / ".jax_cache"
    assert use_compile_cache(tmp_path) == str(want)
    assert use_compile_cache(tmp_path) == str(want)   # same path every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.arange(7.0)).block_until_ready()
    assert any(want.iterdir())
    assert [p.name for p in tmp_path.iterdir()] == [".jax_cache"]
