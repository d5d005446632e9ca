"""The entry points' persistent compilation cache (repro/compile_cache.py):
``JAX_COMPILATION_CACHE_DIR`` wins untouched, else one fixed directory at
the root of the checkout; importing the package turns nothing on."""
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, tmp_path,
                                            restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_at_the_checkout_root(monkeypatch,
                                                   restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert compile_cache.enable_compile_cache() == first
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()


def test_importing_the_package_enables_no_cache():
    code = ("import jax, repro, repro.run, repro.runtime.live; "
            "print(jax.config.jax_compilation_cache_dir)")
    env = {"PYTHONPATH": str(REPO / "src"), "JAX_PLATFORMS": "cpu",
           "PATH": "/usr/bin:/bin"}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, check=True)
    assert out.stdout.strip() == "None"
