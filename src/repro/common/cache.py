"""Warm-start caches shared by the benchmark/figure drivers.

The JAX persistent compilation cache keeps XLA executables on disk, so a
fresh process re-running an already-compiled sweep (the 21 s policy-axis
cold compile, the 3.3 s headline) loads the binary instead of
recompiling.  ``enable_compilation_cache()`` turns it on:

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX already points at that
  directory and this module sets no other;
* otherwise the cache lives at ``<checkout>/.cache/jax_compilation``,
  resolved from this file's location, so every process of one checkout
  shares it whatever its working directory (the path is part of the
  cache key: a directory that moves never hits).

``CACHE_ROOT`` (``<checkout>/.cache``) is also where ``repro.core.sweep``
persists backend calibrations.  Disable the compilation cache with
``REPRO_COMPILATION_CACHE=0``.  ``backend_compiles()`` counts the XLA
compiles inside a timed window (a warm window should have none).
"""
from __future__ import annotations

import contextlib
import os

# src/repro/common/cache.py -> <checkout>
CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".cache")


def default_cache_dir() -> str:
    """The compilation-cache directory this process uses."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(CACHE_ROOT, "jax_compilation"))


def enable_compilation_cache(min_compile_secs: float = 0.2) -> str | None:
    """Enable the JAX persistent compilation cache.

    Returns the directory in use, or None when disabled
    (``REPRO_COMPILATION_CACHE=0``) or unavailable (unwritable dir, jax
    without the config knob).  Safe to call more than once.
    ``min_compile_secs`` skips persisting trivial compiles so the cache
    holds the executables worth warm-starting.
    """
    if os.environ.get("REPRO_COMPILATION_CACHE", "1") == "0":
        return None
    import jax
    cache_dir = default_cache_dir()
    try:
        os.makedirs(cache_dir, exist_ok=True)
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_compile_secs))
    except (OSError, AttributeError, ValueError):
        return None
    return cache_dir


def compilation_cache_entries(cache_dir: str | None = None) -> int:
    """Number of persisted executables currently in the cache dir."""
    cache_dir = cache_dir or default_cache_dir()
    try:
        return sum(1 for n in os.listdir(cache_dir)
                   if not n.startswith("."))
    except OSError:
        return 0


_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@contextlib.contextmanager
def backend_compiles():
    """Collect the XLA backend compiles that run inside the block: yields
    a list that receives one duration (seconds) per compile.  Executables
    loaded from the persistent cache are not compiles and are not
    listed."""
    import jax
    got: list = []

    def listen(event, duration, **_):
        if event == _BACKEND_COMPILE_EVENT:
            got.append(duration)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield got
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
