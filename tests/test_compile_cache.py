"""Where the persistent compilation cache lives: the directory set from
outside when there is one, else one fixed path inside the checkout."""
import os

import jax
import pytest

from repro.launch import compile_cache as cc

_KEYS = ("jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs",
         "jax_persistent_cache_min_entry_size_bytes")
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def jax_cache_config():
    """Restore the process-wide cache settings the test changes."""
    saved = {k: getattr(jax.config, k) for k in _KEYS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_env_var_dir_is_used_as_given(monkeypatch, tmp_path,
                                      jax_cache_config):
    target = str(tmp_path / "from-outside")
    monkeypatch.setenv(cc.ENV_VAR, target)
    assert cc.enable_compilation_cache() == target
    assert jax.config.jax_compilation_cache_dir == target
    assert os.path.isdir(target)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


def test_unset_env_var_gives_fixed_in_checkout_dir(monkeypatch,
                                                   jax_cache_config):
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    first = cc.enable_compilation_cache()
    second = cc.enable_compilation_cache()
    assert first == second == cc.DEFAULT_DIR
    assert first == os.path.join(_REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
