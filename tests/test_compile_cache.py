"""``repro.compile_cache.enable``: where entry points keep JAX's cache."""
import os

import jax
import jax.numpy as jnp
import pytest

from repro import compile_cache

_KEYS = ("jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def restore_config():
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved = {k: getattr(jax.config, k) for k in _KEYS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    cc.reset_cache()


def _compile_something(tag: float):
    jax.jit(lambda x: jnp.sin(x) * tag + 1.0)(jnp.ones(7)).block_until_ready()


def test_default_dir_is_repo_jax_cache(tmp_path, monkeypatch, restore_config):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    d = compile_cache.enable(tmp_path)
    assert d == str(tmp_path.resolve() / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == d
    _compile_something(3.25)
    assert os.listdir(d)  # entries are written there


def test_env_dir_is_used_and_no_other_set(tmp_path, monkeypatch,
                                          restore_config):
    env_dir = tmp_path / "from_env"
    monkeypatch.setenv(compile_cache.ENV, str(env_dir))
    before = jax.config.jax_compilation_cache_dir
    d = compile_cache.enable(tmp_path / "repo")
    assert d == str(env_dir)
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "repo" / ".jax_cache").exists()
