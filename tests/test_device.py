"""repro.device.enable_compile_cache: where JAX's persistent compile cache
goes, and that sub-second compiles are worth caching."""
from pathlib import Path

import jax
import pytest

from repro import device


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache
    saved = {name: getattr(jax.config, name) for name in
             ("jax_compilation_cache_dir",
              "jax_persistent_cache_min_compile_time_secs")}
    yield
    for name, value in saved.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def test_cache_goes_to_the_checkout_by_default(monkeypatch,
                                               restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = device.enable_compile_cache()
    assert Path(path).name == ".jax_cache"
    assert (Path(path).parent / "chip_smoke.py").is_file()
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_cache_dir_from_the_environment_is_left_to_jax(monkeypatch, tmp_path,
                                                       restore_cache_config):
    # JAX reads JAX_COMPILATION_CACHE_DIR itself when it is imported
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert device.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
