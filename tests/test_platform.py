"""Platform plumbing: where the Pallas kernels run, and where JAX keeps its
persistent compilation cache."""
from types import SimpleNamespace

import jax
import pytest

from repro.kernels import common
from repro.launch import compile_cache


@pytest.mark.parametrize('platform,expect', [('cpu', True), ('tpu', False)])
def test_default_interpret_by_platform(monkeypatch, platform, expect):
    monkeypatch.setattr(common.jax, 'devices',
                        lambda: [SimpleNamespace(platform=platform)])
    assert common.default_interpret() is expect
    assert common.resolve_interpret(None) is expect
    assert common.resolve_interpret(not expect) is (not expect)


def test_default_interpret_refuses_other_platforms(monkeypatch):
    """No silent fall back to the interpreter off the CPU and the TPU."""
    monkeypatch.setattr(common.jax, 'devices',
                        lambda: [SimpleNamespace(platform='gpu')])
    with pytest.raises(RuntimeError, match="'gpu'"):
        common.default_interpret()


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update('jax_compilation_cache_dir', was)


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                cache_dir_config):
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.REPO_CACHE_DIR.parent.joinpath(
        'chip_smoke.py').is_file()      # the root of this checkout
    assert compile_cache.enable_compile_cache() == path   # stable


def test_compile_cache_env_wins(monkeypatch, tmp_path, cache_dir_config):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it and the helper sets
    no other directory."""
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
