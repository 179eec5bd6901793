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

import gc
import os
import pathlib
import threading

from skypilot_tpu.observability import metrics

ENV = "JAX_COMPILATION_CACHE_DIR"
_IN_CHECKOUT = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"

# Every program the process had to build, counted where JAX says so
# (jax.monitoring). A serving window or a steady training loop should
# add none: each one is a stall of seconds on the thread that hit it.
_COMPILES = metrics.counter(
    "stpu_xla_compiles_total",
    "XLA programs built for this process: compiled = the backend "
    "compiled it, cache = read back from the persistent compilation "
    "cache (a re-trace of an already cached program counts here).",
    ("source",))
_COMPILE_SECONDS = metrics.counter(
    "stpu_xla_compile_seconds_total",
    "Seconds spent building XLA programs (backend compile, or the "
    "cache lookup and load), by the same sources.", ("source",))
_BY_SOURCE = {s: (_COMPILES.labels(source=s),
                  _COMPILE_SECONDS.labels(source=s))
              for s in ("compiled", "cache")}
# JAX 0.9.0 wraps compile_or_get_cached() in ONE duration event whether
# the backend compiled or the cache answered; on a hit, and only then,
# the retrieval-time event fires first, inside it and on the same
# thread.
_BUILT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_retrieval_time_sec"
_hit = threading.local()
_listening = False


def _on_duration(event: str, seconds: float, **_kwargs) -> None:
    if event == _CACHE_HIT:
        _hit.seen = True
    elif event == _BUILT:
        source = "cache" if getattr(_hit, "seen", False) else "compiled"
        _hit.seen = False
        count, total = _BY_SOURCE[source]
        count.inc()
        total.inc(seconds)


def cache_dir() -> str:
    """The directory compiled programs persist in ('' = cache off)."""
    if ENV in os.environ:
        return os.environ[ENV]
    return str(_IN_CHECKOUT)


def enable() -> str:
    """Turn the persistent cache on by the rule above and start
    counting compilations and the garbage collector's seconds (once
    per process); returns the directory. Programs that compile in
    under half a second are not worth a file each; any size is. The
    process's first device query is made here if nothing made it
    before (start-up phase ``import`` ends with it,
    observability/phases.py), so a gang calls
    ``distributed.initialize_from_env()`` first, as every recipe
    does."""
    import jax

    from skypilot_tpu.observability import phases
    global _listening
    if not _listening:
        _listening = True
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        gc.callbacks.append(phases.on_gc)
    if ENV not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(_IN_CHECKOUT))
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    phases.import_done()
    return cache_dir()
