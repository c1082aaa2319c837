"""Where the persistent compilation cache lives (runtime/compile_cache.py)."""
import os
import subprocess
import sys

import jax
import pytest

from repro.runtime import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def fresh_cache_state(monkeypatch):
    """Undo the module's and JAX's cache settings after the test."""
    prev_dir = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(compile_cache, "_enabled_dir", None)
    monkeypatch.delenv("REPRO_NO_COMPILE_CACHE", raising=False)
    yield
    jax.config.update("jax_compilation_cache_dir", prev_dir)


def test_cache_follows_jax_variable(fresh_cache_state, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_persistent_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    assert compile_cache.cache_dir() == str(tmp_path)


def test_cache_defaults_to_fixed_checkout_dir(fresh_cache_state, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_persistent_cache()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_cache_opt_out(fresh_cache_state, monkeypatch):
    monkeypatch.setenv("REPRO_NO_COMPILE_CACHE", "1")
    assert compile_cache.enable_persistent_cache() is None


WRITE_SCRIPT = r"""
import jax, jax.numpy as jnp
from repro.runtime.compile_cache import enable_persistent_cache
print(enable_persistent_cache())
jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)(jnp.arange(8.0)).block_until_ready()
"""


def test_cache_written_only_under_jax_variable(tmp_path):
    """With the variable set, compiled programs land there and nowhere
    in HOME or TMPDIR."""
    cache, home, tmp = (tmp_path / n for n in ("cache", "home", "tmp"))
    for d in (home, tmp):
        d.mkdir()
    r = subprocess.run(
        [sys.executable, "-c", WRITE_SCRIPT], cwd=ROOT, capture_output=True,
        text=True, timeout=300,
        env={"PATH": os.environ.get("PATH", ""), "PYTHONPATH": "src",
             "JAX_PLATFORMS": "cpu", "HOME": str(home), "TMPDIR": str(tmp),
             "JAX_COMPILATION_CACHE_DIR": str(cache)},
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == str(cache)
    assert any(cache.iterdir())
    assert not any(home.iterdir()) and not any(tmp.iterdir())
