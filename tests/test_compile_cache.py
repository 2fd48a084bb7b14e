"""The persistent compilation cache directory the entry points use."""
import os

import jax
import pytest

from repro import compile_cache as CC

_KNOBS = ("jax_compilation_cache_dir",
          "jax_persistent_cache_min_compile_time_secs",
          "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def restore_config():
    saved = {k: getattr(jax.config, k) for k in _KNOBS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("env", [None, "set"])
def test_cache_dir_is_env_or_fixed_checkout_path(restore_config, monkeypatch,
                                                 tmp_path, env):
    if env is None:
        monkeypatch.delenv(CC.CACHE_ENV, raising=False)
        want = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache")
    else:
        want = str(tmp_path / "cache")
        monkeypatch.setenv(CC.CACHE_ENV, want)
    assert CC.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
