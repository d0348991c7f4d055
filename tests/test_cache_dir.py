"""Placement of the persistent compilation cache (runtime/compile_cache)."""
from pathlib import Path

import jax

from repro.runtime import compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_env_cache_dir_is_honoured_untouched(monkeypatch, tmp_path):
    where = str(tmp_path / "elsewhere")
    assert compile_cache.resolve_cache_dir({compile_cache.CACHE_ENV: where}) \
        == (where, True)
    monkeypatch.setenv(compile_cache.CACHE_ENV, where)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == where
    # JAX reads the variable itself: nothing is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_default_cache_dir_is_fixed_and_in_checkout():
    first, from_env = compile_cache.resolve_cache_dir({})
    second, _ = compile_cache.resolve_cache_dir({})
    assert not from_env
    assert first == second
    assert Path(first) == REPO / ".jax_cache"
    assert Path(first).resolve().is_relative_to(REPO.resolve())
