"""CPU rehearsal of the training launcher and its compile-cache helper."""

from __future__ import annotations

import math

import jax
import pytest

from repro.launch import compile_cache, train


@pytest.fixture
def cache_env(tmp_path, monkeypatch):
    """A cache directory named by the environment, so nothing here sets
    JAX's process-wide cache config."""
    path = tmp_path / "jax_cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(path))
    return path


def test_debug_run_has_finite_losses(cache_env):
    res = train.main(["--debug", "--steps", "2", "--workers", "4",
                      "--byzantine", "1", "--attack", "sign_flip"])
    assert len(res.losses) == 2
    assert all(math.isfinite(x) for x in res.losses)
    assert len(res.step_seconds) == 2
    assert "ENTRY" in res.compiled.as_text()


def test_layers_cuts_depth_only():
    args = train.parse_args(["--layers", "20"])
    full = train.model_config(args, cut=False)
    cut = train.model_config(args)
    assert cut.num_layers == 20
    assert cut.replace(num_layers=full.num_layers) == full


def test_cache_helper_honours_env(cache_env):
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(cache_env)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_helper_defaults_to_repo_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.REPO_CACHE_DIR)
        assert path.endswith(".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
