"""Where the entry points keep JAX's persistent compilation cache."""
from pathlib import Path

import jax

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    return calls


def test_env_var_is_respected(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself and the
    helper sets no other directory."""
    calls = _record_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_default_is_fixed_inside_the_checkout(monkeypatch):
    calls = _record_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == compile_cache.enable_compile_cache() == \
        compile_cache.CACHE_DIR
    assert Path(path).parent == REPO
    assert calls == [("jax_compilation_cache_dir", path)] * 2


def test_default_directory_is_ignored_by_git():
    rel = Path(compile_cache.CACHE_DIR).relative_to(REPO)
    ignored = (REPO / ".gitignore").read_text().split()
    assert f"{rel}/" in ignored
