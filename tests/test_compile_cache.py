"""repro.utils.compile_cache: where the entry points put JAX's compile cache."""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.utils import compile_cache


@pytest.fixture
def restore_cache_config():
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def test_env_var_directory_receives_the_entries(tmp_path, monkeypatch,
                                                restore_cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.arange(7.0)).block_until_ready()
    assert any(tmp_path.iterdir())


def test_default_is_a_fixed_path_in_the_checkout(monkeypatch,
                                                 restore_cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.DEFAULT_DIR)
    assert compile_cache.DEFAULT_DIR.name == ".jax_cache"
    assert (compile_cache.DEFAULT_DIR.parent / "src" / "repro").is_dir()
    assert jax.config.jax_compilation_cache_dir == path
