"""The persistent compilation cache: placed from outside when
``JAX_COMPILATION_CACHE_DIR`` is set, else at one fixed checkout path."""
from repro.launch import compile_cache


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: calls.append(a))
    return calls


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    calls = _record_updates(monkeypatch)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert calls == []


def test_default_dir_is_fixed_in_checkout(monkeypatch):
    calls = _record_updates(monkeypatch)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.use_compile_cache()
    assert first == compile_cache.use_compile_cache()
    root = compile_cache.CHECKOUT_CACHE.parent
    assert first == str(root / ".jax_cache")
    assert (root / "pyproject.toml").exists()
    assert calls == [("jax_compilation_cache_dir", first)] * 2
