"""Placement of JAX's persistent compilation cache (launch/compile_cache.py).

The helper is called by the entry points, never by the tests: here it
runs with ``jax.config.update`` replaced, so the suite's own process
keeps the cache as it found it.
"""

from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

CHECKOUT = Path(__file__).resolve().parents[1]


@pytest.fixture
def config_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *args: calls.append(args))
    return calls


def test_environment_directory_wins(monkeypatch, tmp_path, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == tmp_path
    assert config_updates == []  # JAX reads the variable itself


def test_default_is_one_fixed_path_in_the_checkout(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    paths = {compile_cache.enable_compile_cache() for _ in range(3)}
    assert paths == {CHECKOUT / ".jax_cache"}
    assert config_updates == [
        ("jax_compilation_cache_dir", str(CHECKOUT / ".jax_cache"))
    ] * 3


def test_cache_directory_is_not_committed():
    ignored = (CHECKOUT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
