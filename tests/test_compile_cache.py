"""Where the entry points put JAX's persistent compilation cache.

The helper is exercised with ``jax.config.update`` replaced by a
recorder, so these tests never turn the cache on in the test process.
"""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def updates(monkeypatch):
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.append((name, value)))
    return seen


def test_env_dir_is_left_to_jax(monkeypatch, updates, tmp_path):
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert updates == []


def test_default_is_fixed_dir_at_repo_root(monkeypatch, updates):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    root = Path(__file__).resolve().parents[1]
    want = str(root / ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]
    # the same directory on every call: the path is part of the cache key
    assert compile_cache.enable_compile_cache() == want
