"""chip_smoke.py refuses to run without a TPU, and the entry points' compile
cache lands where ``launch/compile_cache.py`` says."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache
from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, use_compile_cache

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def cache_dir_config():
    """Restore jax_compilation_cache_dir after a test that may set it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("four_chips", [False, True])
def test_chip_smoke_exits_nonzero_without_tpu(four_chips):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"))
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"] + (["--four-chips"] * four_chips),
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "no TPU chip" in r.stderr
    # no result line of any kind
    assert not any(line.lstrip().startswith("{")
                   for line in r.stdout.splitlines()), r.stdout


def test_env_cache_dir_is_left_to_jax(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    monkeypatch.setattr(compile_cache.jax, "default_backend", lambda: "tpu")
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_tpu_cache_goes_to_the_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache.jax, "default_backend", lambda: "tpu")
    assert use_compile_cache() == str(CHECKOUT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
    # one fixed directory at the root of the checkout, ignored by git
    assert CHECKOUT_CACHE_DIR.parent == REPO
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert f"{CHECKOUT_CACHE_DIR.name}/" in ignored


def test_cpu_runs_keep_no_cache(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert jax.default_backend() == "cpu"
    assert use_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
