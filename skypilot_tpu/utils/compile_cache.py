"""The one rule for JAX's persistent compilation cache.

Every entry point that compiles (the serving and training recipes,
``bench.py``, ``tools/bench_moe_decode.py``, ``chip_smoke.py`` and
``tests/conftest.py``) calls :func:`enable` once, before its first
compile:

  * ``JAX_COMPILATION_CACHE_DIR`` set: nothing sets a directory in
    code. JAX reads the variable itself and children inherit it. (Set
    to the empty string, that leaves the cache off.)
  * not set: the cache lives in ``<checkout>/.jax_cache``, resolved from
    this file's location, so every process and working directory
    agrees on it. The path is part of a cache entry's key, so a
    directory that moves (a temp name, a pid, a home) never hits.

``.jax_cache/`` is listed in ``.gitignore`` and ``.chiprunignore``.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
_IN_CHECKOUT = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir() -> str:
    """The directory compiled programs persist in ('' = cache off)."""
    if ENV in os.environ:
        return os.environ[ENV]
    return str(_IN_CHECKOUT)


def enable() -> str:
    """Turn the persistent cache on by the rule above; returns the
    directory. Programs that compile in under half a second are not
    worth a file each; any size is."""
    import jax
    if ENV not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(_IN_CHECKOUT))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return cache_dir()
