"""The compile-cache rule (utils/compile_cache.py) and the exact
device_kind peak table (utils/flops.py)."""

import os

import pytest

import jax

from oryx_tpu.utils import compile_cache, flops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Leave jax's cache setting as the session had it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_set_means_nothing_is_set_in_code(monkeypatch, cache_config):
    jax.config.update("jax_compilation_cache_dir", "/session/choice")
    monkeypatch.setenv(compile_cache.ENV_VAR, "/placed/from/outside")
    assert compile_cache.configure_compile_cache() == "/placed/from/outside"
    assert jax.config.jax_compilation_cache_dir == "/session/choice"


def test_env_unset_uses_the_fixed_path_in_the_checkout(
    monkeypatch, cache_config
):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.configure_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_same_path_from_two_working_directories(monkeypatch, tmp_path):
    here = compile_cache.default_cache_dir()
    monkeypatch.chdir(tmp_path)
    assert compile_cache.default_cache_dir() == here
    assert os.path.isabs(here) and here.startswith(REPO)


def test_cache_dir_is_gitignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_peak_table_is_exact_by_device_kind():
    assert flops.chip_peak_flops("TPU v5 lite") == 197e12


@pytest.mark.parametrize(
    "kind", ["TPU v5", "TPU v5p", "tpu v5 lite", "TPU v5 lite pod", "cpu", ""]
)
def test_unknown_device_kind_has_no_peak(kind):
    """Never a neighbour's number: no substring or case folding."""
    assert flops.chip_peak_flops(kind) is None
