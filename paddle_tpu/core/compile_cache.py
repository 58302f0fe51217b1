"""Where jax's persistent compilation cache lives — decided outside the
program.

If ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and nothing
here sets a directory.  Otherwise the cache is ONE fixed, git-ignored
directory at the root of the checkout: a cache's path is part of its key,
so a directory named after a temp file, a pid or the time never hits.

The entry threshold is 0.5 s of compile time: it keeps every jitted step —
the 1.3B train step and the serving step compile in 2-8 s, jax's own
default of 1 s sits too close under them — and skips the eager primitives,
which compile in tens of milliseconds and would only bloat the directory.

Callers: ``chip_smoke.py``'s legs, ``benchmark/run.py`` and
``tests/conftest.py``, each before its first compile.
"""
from __future__ import annotations

import os

__all__ = ["CHECKOUT_CACHE_DIR", "MIN_COMPILE_SECS", "use_compile_cache"]

MIN_COMPILE_SECS = 0.5

CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent cache on and return the directory in use."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
