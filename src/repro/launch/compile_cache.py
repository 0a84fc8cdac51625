"""Where the entry points keep JAX's persistent compilation cache.

A full-width program of this repo takes a minute or more to compile for a
TPU, so ``chip_smoke.py``, ``launch/serve_bridges.py`` and
``benchmarks/run.py`` keep compiled TPU programs on disk. The directory is
fixed, because the cache only hits where its path is the same from one run
to the next.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout's own cache directory (listed in .gitignore)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str | None:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing here sets another path. Otherwise, on a TPU, the cache goes to
    ``.jax_cache`` at the root of the checkout. Elsewhere nothing is
    cached (returns None): CPU programs compile in seconds, and XLA:CPU
    entries are tied to the CPU features of the host that wrote them.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
