"""chip_smoke.py refuses to run without a TPU, and the entry points' compile
cache lands where ``launch/compile_cache.py`` says."""
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache
from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, use_compile_cache

REPO = Path(__file__).resolve().parent.parent


CACHE_CONFIG = ("jax_compilation_cache_dir",
                "jax_compilation_cache_include_metadata_in_key",
                "jax_hlo_source_file_canonicalization_regex")


@pytest.fixture
def cache_dir_config():
    """Restore the compile cache's settings after a test that may set
    them."""
    before = {k: getattr(jax.config, k) for k in CACHE_CONFIG}
    yield
    for k, v in before.items():
        jax.config.update(k, v)


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
    before = {k: getattr(jax.config, k) for k in CACHE_CONFIG}
    assert jax.default_backend() == "cpu"
    assert use_compile_cache() is None
    assert {k: getattr(jax.config, k) for k in CACHE_CONFIG} == before


def _cache_key(scope: str) -> str:
    import numpy as np
    from jax._src import cache_key, compiler

    def f(x):
        with jax.named_scope(scope):
            return x * 2

    lowered = jax.jit(f).lower(jax.ShapeDtypeStruct((8,), np.int32))
    return cache_key.get(lowered.compiler_ir("stablehlo"),
                         np.array(jax.devices()[:1]),
                         compiler.get_compile_options(1, 1),
                         jax.devices()[0].client)


@pytest.mark.parametrize("where", ["env", "checkout"])
def test_cache_key_carries_the_scopes(monkeypatch, cache_dir_config, where):
    """Wherever a cache is on, two programs that differ by a scope alone
    get two entries, so a profile of the one never shows the other's
    scopes; source paths are keyed relative to the checkout."""
    if where == "env":
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(compile_cache.jax, "default_backend",
                            lambda: "tpu")
    def keys():
        # one call site: the key holds the traceback of the tracing call
        return [_cache_key(scope) for scope in ("stage/a", "stage/a",
                                                "stage/b")]

    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    a, again, b = keys()
    assert a == again == b  # JAX's default key leaves the scopes out
    use_compile_cache()
    a, again, b = keys()
    assert a == again != b
    regex = jax.config.jax_hlo_source_file_canonicalization_regex
    assert re.sub(regex, "", str(REPO / "src/repro/core/forest.py")) == \
        "src/repro/core/forest.py"
