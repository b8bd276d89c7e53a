"""Entry points keep JAX's persistent compilation cache at one fixed place:
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``.  The
config update is intercepted, so these tests never turn the cache on."""
from pathlib import Path

import jax

from repro.common import compile_cache


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    calls = _record_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_default_dir_is_repo_jax_cache(monkeypatch):
    calls = _record_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = Path(__file__).resolve().parents[1]
    path = compile_cache.enable_compile_cache()
    assert path == str(repo / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]
