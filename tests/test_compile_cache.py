"""The persistent compile cache helper: JAX_COMPILATION_CACHE_DIR wins
and nothing else is set; otherwise one fixed, git-ignored directory of
the checkout. Each test restores JAX's config, so the suite itself never
runs with the cache on."""
import tempfile
from pathlib import Path

import jax
import pytest

from repro import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_is_honoured_and_nothing_is_set(monkeypatch,
                                                restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV, "/some/where/else")
    assert compile_cache.enable() == "/some/where/else"
    assert jax.config.jax_compilation_cache_dir == restore_cache_dir


def test_default_is_one_fixed_ignored_path_in_checkout(monkeypatch,
                                                      restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    first = compile_cache.enable()
    assert first == compile_cache.enable() == str(compile_cache.DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == first
    path = Path(first)
    assert path.parent == REPO
    assert not path.is_relative_to(tempfile.gettempdir())
    ignored = (REPO / ".gitignore").read_text().split()
    assert f"{path.name}/" in ignored
