"""JAX's persistent compilation cache, switched on by every entry point
before its first compile.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing here
names another directory.  Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (listed in ``.gitignore``): the directory is part
of the cache key, so a per-process or temporary name would never hit.  The
minimum compile time is lowered to zero so the Pallas kernels, which
compile in well under JAX's default one-second threshold, are cached too.
"""
from __future__ import annotations

import os
import pathlib

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_DIR = CHECKOUT / ".jax_cache"

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileStats:
    """Seconds spent in backend compiles (cache reads included) and the
    persistent cache's hits and misses, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            self.hits += 1
        elif event == CACHE_MISS_EVENT:
            self.misses += 1
