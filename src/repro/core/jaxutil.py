"""Shared JAX configuration: the f64 guard and the compile cache.

The fused scenario engine's bit-exactness claims (water-filling and cohort
step vs their NumPy planes) hold only under double precision; JAX defaults
to f32 unless ``jax_enable_x64`` is flipped *before* the arrays involved are
created.  Tests, benchmarks and ``sim/scenarios.py`` all route through
:func:`enable_f64` so the flag is set exactly once, idempotently, and there
is a single place asserting it actually took (guarding against an import
that raced a traced function).
"""

from __future__ import annotations

import os

_enabled = False


def enable_f64() -> None:
    """Idempotently enable 64-bit JAX types (safe to call repeatedly)."""
    global _enabled
    if _enabled:
        return
    import jax

    jax.config.update("jax_enable_x64", True)
    _enabled = True


def f64_enabled() -> bool:
    import jax

    return bool(jax.config.jax_enable_x64)


# A fixed path, so that a later run finds what an earlier one cached.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to JAX (it reads the
    variable itself).  Otherwise the cache lives in ``<repo>/.jax_cache``.
    Every compile is cached, however short: a cold run makes hundreds of
    sub-second ones (one Mosaic kernel per cohort size)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.normpath(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
