"""Process set-up helpers (kube_batch_tpu/utils/backend.py)."""

import os

import jax
import pytest

from kube_batch_tpu.utils import backend


@pytest.fixture
def cache_dir_config():
    """Restore the process's compile-cache directory after the test, so
    no later test in this worker writes a persistent cache."""
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_defaults_to_fixed_checkout_path(
    monkeypatch, cache_dir_config
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)
    )))
    want = os.path.join(repo, ".jax_cache")
    assert backend.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # Same path on every call: it is part of the cache's key.
    assert backend.enable_compile_cache() == want


def test_compile_cache_honours_env_dir(
    monkeypatch, tmp_path, cache_dir_config
):
    # JAX reads JAX_COMPILATION_CACHE_DIR into its config when it is
    # imported (as it did here, before the test set it); the helper must
    # leave that choice alone and set no directory of its own.
    env_dir = str(tmp_path / "envcache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    jax.config.update("jax_compilation_cache_dir", env_dir)
    assert backend.enable_compile_cache() == env_dir
    assert jax.config.jax_compilation_cache_dir == env_dir


def test_set_host_device_count_only_grows():
    env = {"XLA_FLAGS": "--foo --xla_force_host_platform_device_count=2"}
    backend.set_host_device_count(8, env)
    assert env["XLA_FLAGS"] == "--foo --xla_force_host_platform_device_count=8"
    backend.set_host_device_count(4, env)
    assert env["XLA_FLAGS"].endswith("device_count=8")
    env = {}
    backend.set_host_device_count(4, env)
    assert env["XLA_FLAGS"] == "--xla_force_host_platform_device_count=4"
