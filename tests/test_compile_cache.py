"""The one compile-cache helper: where the environment names a
directory, code sets none; where it does not, the fixed in-checkout
path."""
import os

import jax
import pytest

from paddle_tpu import compile_cache as cc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_set_means_code_sets_no_directory(monkeypatch,
                                              restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    jax.config.update("jax_compilation_cache_dir", "sentinel-value")
    assert cc.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == "sentinel-value"


def test_env_unset_means_the_fixed_in_checkout_path(monkeypatch,
                                                    restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert cc.enable_compile_cache() == cc.CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == cc.CACHE_DIR
    assert cc.CACHE_DIR == os.path.join(REPO, ".jax_cache")
    # nothing of a home, a temporary directory, a pid or a clock
    assert cc.enable_compile_cache() == cc.CACHE_DIR


def test_cache_dir_is_git_ignored():
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_no_other_code_sets_a_cache_directory():
    """Every entry point goes through the helper (a test that needs a
    private, empty cache around one compile sets its own and puts the
    old one back — that is not an entry point)."""
    hits = []
    for root in ("paddle_tpu", "tools"):
        for dirpath, _, files in os.walk(os.path.join(REPO, root)):
            for name in files:
                if name.endswith(".py"):
                    hits.append(os.path.join(dirpath, name))
    hits += [os.path.join(REPO, n) for n in (
        "bench.py", "chip_smoke.py", "tests/conftest.py")]
    setters = []
    for path in hits:
        with open(path) as f:
            if '"jax_compilation_cache_dir",' in f.read():
                setters.append(os.path.relpath(path, REPO))
    assert setters == ["paddle_tpu/compile_cache.py"]
