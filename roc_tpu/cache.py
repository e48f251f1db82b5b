"""Where derived artefacts live: ``<checkout>/.cache`` (git-ignored).

Compiled programs, built plans, the tuned store and the bench dataset are
all made from the code beside them.  Keeping them inside the checkout
means two checkouts on one machine (a parent commit and a change, the
driver's A/B) never read what the other wrote, and a fresh checkout
builds everything from what git committed.  Nothing here reads ``$HOME``
or ``/tmp``; an environment variable may place a cache elsewhere.
"""

from __future__ import annotations

import os

CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".cache")


def cache_dir(name: str) -> str:
    """``<checkout>/.cache/<name>`` (not created)."""
    return os.path.join(CACHE_ROOT, name)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a path that does not
    move (the path is part of the cache key) and return it.  Initialises
    the backend, so call it after ``jax.distributed.initialize()``.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it — nothing is
    set in code.  Unset: ``<checkout>/.cache/jax``.  On a TPU every
    program is kept however quick its compile, so a second run of the
    same command compiles nothing the first one compiled; elsewhere
    JAX's own one-second threshold stays (the CPU loader logs a long
    machine-feature line per cache hit — keep those to the programs
    that are worth it)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    from roc_tpu.device import on_tpu
    path = cache_dir("jax")
    jax.config.update("jax_compilation_cache_dir", path)
    if on_tpu():
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
