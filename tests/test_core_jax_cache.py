"""enable_compilation_cache's two rules: JAX_COMPILATION_CACHE_DIR set → the
module sets no directory; unset → <checkout>/.jax_cache, resolved from the
package's own location."""
from pathlib import Path

import jax
import pytest

import vnsum_tpu
from vnsum_tpu.core import jax_cache

CHECKOUT_CACHE = str(Path(vnsum_tpu.__file__).resolve().parents[1] / ".jax_cache")


@pytest.fixture()
def _restore_cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_set_means_module_sets_nothing(
    tmp_path, monkeypatch, _restore_cache_config
):
    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    jax.config.update("jax_compilation_cache_dir", "sentinel")
    assert jax_cache.enable_compilation_cache() == placed
    # JAX's own handling stands: the config was not touched, nothing created
    assert jax.config.jax_compilation_cache_dir == "sentinel"
    assert not Path(placed).exists()


def test_env_unset_means_checkout_dir(monkeypatch, _restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    assert jax_cache.enable_compilation_cache() == CHECKOUT_CACHE
    assert jax.config.jax_compilation_cache_dir == CHECKOUT_CACHE
    assert Path(CHECKOUT_CACHE).is_dir()
    # idempotent
    assert jax_cache.enable_compilation_cache() == CHECKOUT_CACHE


def test_default_never_leaves_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert not jax_cache._DEFAULT_DIR.startswith(str(Path.home() / ".cache"))
    assert Path(jax_cache._DEFAULT_DIR).parent == Path(
        vnsum_tpu.__file__
    ).resolve().parents[1]
