"""Where the persistent XLA compile cache lives, decided in one place.

Every process that compiles the serving programs — ``fleet_main``,
``bench.py``, the test suite, and through its children ``chip_smoke.py`` —
calls ``enable()`` before its first dispatch.  The rule:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX's own handling of the variable
  stands and this module sets no directory (the machine placed the cache
  from outside; overriding it in code would make the next run miss).
- not set: the cache is ``<checkout>/.jax_compile_cache`` (gitignored) —
  a fixed path, never a temporary, pid- or time-derived one, because the
  directory is part of what a later process must find again.

``CompileStats`` reads JAX's own monitoring events, so a status line can
say how many programs were compiled, how many of those the persistent
cache served, and how long the compiler ran — the split between set-up
and serving time that a cold start on an accelerator needs.
"""

from __future__ import annotations

import os
import threading
from pathlib import Path

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_compile_cache"


def cache_dir() -> str:
    """The directory the persistent compile cache uses in this process."""
    return os.environ.get(CACHE_DIR_ENV) or str(_DEFAULT_DIR)


def enable() -> str:
    """Turn the persistent compile cache on (idempotent); returns its
    directory.  Call before the first compile; touches no backend."""
    import jax

    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", str(_DEFAULT_DIR))
    # The serving programs are many small executables: cache all of them.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir()


_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HITS = "/jax/compilation_cache/cache_hits"


class CompileStats:
    """Process-wide compile accounting from ``jax.monitoring`` events:
    programs that went to the compiler or the persistent cache
    (``requests``), how many the cache served (``cache_hits``), and the
    seconds spent in ``backend_compile`` (cache loads included)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.requests = 0
        self.cache_hits = 0
        self.compile_seconds = 0.0

    def install(self) -> "CompileStats":
        import jax.monitoring

        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        return self

    def _on_event(self, event: str, **_kw) -> None:
        with self._lock:
            if event == _CACHE_REQUESTS:
                self.requests += 1
            elif event == _CACHE_HITS:
                self.cache_hits += 1

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == _BACKEND_COMPILE:
            with self._lock:
                self.compile_seconds += duration

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "requests": self.requests,
                "cache_hits": self.cache_hits,
                "compile_seconds": round(self.compile_seconds, 3),
                "cache_dir": cache_dir(),
            }
