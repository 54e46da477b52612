"""The persistent compile cache is placed from outside (utils/compile_cache):
JAX_COMPILATION_CACHE_DIR when set, else the fixed <checkout>/.jax_cache."""

import os

import jax
import pytest

from capital_tpu.utils import compile_cache


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("env_dir", [None, "/some/dir"])
def test_enable_places_cache(monkeypatch, restore_cache_config, env_dir):
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(compile_cache.CHECKOUT, ".jax_cache")
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        want = env_dir
    assert compile_cache.enable() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert os.path.isfile(os.path.join(compile_cache.CHECKOUT, "chip_smoke.py"))
