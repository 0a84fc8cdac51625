"""Where the entry points keep JAX's persistent compilation cache.

A full-width program of this repo takes a minute or more to compile for a
TPU, so ``chip_smoke.py``, ``launch/serve_bridges.py`` and
``benchmarks/run.py`` keep compiled TPU programs on disk. The directory is
fixed, because the cache only hits where its path is the same from one run
to the next.

The programs' op metadata is part of the cache key. A profile attributes
device time by the metadata's name stack (the ``jax.named_scope`` labels),
and JAX's default key leaves metadata out: a program that differs from a
cached one by its scopes alone would load the cached executable and report
the cached program's scopes. Source paths in the metadata are taken
relative to the checkout, so the key does not depend on where it lies.
"""
from __future__ import annotations

import os
import re
from pathlib import Path

import jax

#: the root of the checkout
CHECKOUT = Path(__file__).resolve().parents[3]
#: the checkout's own cache directory (listed in .gitignore)
CHECKOUT_CACHE_DIR = CHECKOUT / ".jax_cache"


def use_compile_cache() -> str | None:
    """Turn on the persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing here sets another path. Otherwise, on a TPU, the cache goes to
    ``.jax_cache`` at the root of the checkout. Elsewhere nothing is
    cached (returns None): CPU programs compile in seconds, and XLA:CPU
    entries are tied to the CPU features of the host that wrote them.
    Wherever a cache is on, its key includes the programs' metadata.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env and jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(f"{CHECKOUT}/"))
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
