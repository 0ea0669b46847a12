"""Entry-point plumbing: the compile cache location and the chip smoke's
refusal to run without a TPU."""
from __future__ import annotations

import os
import subprocess
import sys

import jax

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_uses_env_dir_and_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_dir_in_checkout(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache as cc

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable() == compile_cache.CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == compile_cache.CACHE_DIR
        assert compile_cache.CACHE_DIR == os.path.join(REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        cc.reset_cache()


def test_chip_smoke_refuses_a_cpu_backend():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr
