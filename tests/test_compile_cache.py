"""The persistent compilation cache can be placed from outside."""

import jax

from repro.runtime import compile_cache


def _spy_config(monkeypatch):
    # record instead of applying: a persistent cache switched on here would
    # stay on for every later test in this process
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    return calls


def test_env_dir_wins_and_config_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    calls = _spy_config(monkeypatch)
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []  # JAX reads the variable itself


def test_default_is_the_checkout_cache(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    calls = _spy_config(monkeypatch)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.CHECKOUT_CACHE)
    assert compile_cache.CHECKOUT_CACHE.name == ".jax_cache"
    assert (compile_cache.CHECKOUT_CACHE.parent / "pyproject.toml").exists()
    assert calls == [("jax_compilation_cache_dir", path)]
