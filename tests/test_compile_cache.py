"""The persistent compilation cache's directory: the environment variable
wins, and the fallback is one fixed directory inside the checkout."""

import pathlib

import jax

from repro import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_env_var_wins(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)


def test_fallback_is_fixed_in_repo_path(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.cache_dir()
    assert path == str(REPO / ".jax_cache")
    assert compile_cache.cache_dir() == path      # no pid, time or temp name
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_enable_points_jax_at_the_cache_dir(monkeypatch, tmp_path):
    old = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv(compile_cache.ENV_VAR)
        assert compile_cache.enable() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
