"""Persistent XLA compile cache for the process entry points that run on
the chip (``chip_smoke.py``, ``perfbench/run.py``).

The client compiles the full tick for every tick shape at ``start()`` and
again whenever a rule load changes the feature set, and a chip call starts
cold — without a cache every run pays every compile again.

Where the cache lives is decided from outside: ``JAX_COMPILATION_CACHE_DIR``
wins (JAX reads the variable itself, so nothing is set in code), otherwise a
fixed ``<checkout>/.jax_cache``.  The path is part of the cache key, so it
is never built from a temp dir, a pid or a clock.  Called first thing by an
entry point, never at package import: a library must not turn on disk
caching for whoever imports it.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def compile_cache_dir() -> str:
    """The directory this process's compile cache belongs in."""
    return os.environ.get(_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    import jax

    path = compile_cache_dir()
    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    # Every program, however quick to compile.  Beside the tick's shapes a
    # start compiles some sixty small programs (the wire's unpack, the
    # telemetry folds, eager helpers) of a tenth of a second each; JAX's
    # default leaves out what compiled in under a second, so every start
    # compiled them again (PERF.md section 6, PR 32).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
