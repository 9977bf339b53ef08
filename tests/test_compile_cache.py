"""The persistent compilation cache lands where ``enable_compile_cache``
says: ``JAX_COMPILATION_CACHE_DIR`` when it is set, else a fixed
``<checkout>/.jax_cache``."""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")

from repro.launch import cache  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def test_unset_env_uses_the_checkout_directory(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert cache.enable_compile_cache() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_set_env_is_left_alone(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def _compile_in_checkout(tmp_path: Path, env_dir):
    """Compile one program in a fresh process whose ``repro.launch.cache``
    comes from a minimal checkout under ``tmp_path``; returns that
    checkout."""
    checkout = tmp_path / "checkout"
    dest = checkout / "src" / "repro" / "launch"
    dest.mkdir(parents=True)
    shutil.copy(ROOT / "src" / "repro" / "launch" / "cache.py", dest)
    env = dict(os.environ, JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PLATFORMS="cpu", PYTHONPATH=str(checkout / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    code = ("from repro.launch.cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "import jax, jax.numpy as jnp\n"
            "jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()\n")
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return checkout, out.stdout.strip()


def test_compiles_land_only_in_the_env_directory(tmp_path):
    """With the env var set, a compile is written there and nothing is
    written into the checkout."""
    target = tmp_path / "cache"
    checkout, printed = _compile_in_checkout(tmp_path, target)
    assert printed == str(target)
    assert any(target.iterdir())
    assert not (checkout / ".jax_cache").exists()


def test_compiles_land_in_the_checkout_when_the_env_is_unset(tmp_path):
    checkout, printed = _compile_in_checkout(tmp_path, None)
    local = checkout / ".jax_cache"
    assert printed == str(local)
    assert any(local.iterdir())
