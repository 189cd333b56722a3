"""Placement of JAX's persistent compilation cache (repro.utils)."""
import pathlib

import jax

from repro.utils import compile_cache_dir, enable_compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_env_var_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache_dir() == str(tmp_path)


def test_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache_dir()
    assert first == compile_cache_dir()
    assert pathlib.Path(first) == ROOT / ".jax_cache"


def test_enable_points_jax_at_it(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    try:
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
