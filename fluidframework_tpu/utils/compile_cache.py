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

The cache key includes the programs' metadata (scope names, the innermost
source line).  JAX's default key ignores metadata, so a process that names
or renames a ``jax.named_scope`` is served the executable an older build
left in the same directory, and a device trace of it shows the old names
(or none): wherever one directory outlives a build (a machine that sets
``JAX_COMPILATION_CACHE_DIR``, a checkout that is updated in place), every
change of a scope meets this, not only the first.  Keying on metadata alone
would put absolute paths and every caller's line into the key; so file
names are made relative to the checkout and only the innermost frame is
kept: the same source hits from any directory and under any caller, and
what misses is an edit that moves a line which emits device ops.
``tests/test_tracing_loop.py`` checks what a lowered step carries.

``CompileStats`` reads JAX's own monitoring events, so a status line can
say how many programs were compiled, how many of those the persistent
cache served, how long the compiler ran, and how long JAX spent tracing
and lowering before it could even ask the cache — the split between
set-up and serving time that a cold start on an accelerator needs.
"""

from __future__ import annotations

import os
import re
import threading
from pathlib import Path

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = Path(__file__).resolve().parents[2]
_DEFAULT_DIR = _CHECKOUT / ".jax_compile_cache"


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
    # A device trace shows the scope names compiled in: key on them, and on
    # nothing that moves with the directory or the caller (module doc).
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    # One frame, not none: with tracebacks off JAX drops the scope path
    # from the op_name of every op a kernel function emits itself.
    jax.config.update("jax_traceback_in_locations_limit", 1)
    jax.config.update(
        "jax_hlo_source_file_canonicalization_regex",
        re.escape(str(_CHECKOUT) + os.sep),
    )
    return cache_dir()


_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_JAXPR_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_CACHE_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HITS = "/jax/compilation_cache/cache_hits"


class CompileStats:
    """Process-wide compile accounting from ``jax.monitoring`` events:
    programs that went to the compiler or the persistent cache
    (``requests``), how many the cache served (``cache_hits``), the
    seconds spent in ``backend_compile`` (cache loads included), and what
    comes before the cache is even asked: every trace of a function to a
    jaxpr (``traces``, ``trace_seconds``) and every lowering of one to an
    MLIR module (``lower_seconds``).  A program shape's first dispatch in
    a process pays those two with every executable already cached."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.requests = 0
        self.cache_hits = 0
        self.compile_seconds = 0.0
        self.traces = 0
        self.trace_seconds = 0.0
        self.lower_seconds = 0.0

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
        with self._lock:
            if event == _BACKEND_COMPILE:
                self.compile_seconds += duration
            elif event == _JAXPR_TRACE:
                self.traces += 1
                self.trace_seconds += duration
            elif event == _LOWER:
                self.lower_seconds += duration

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "requests": self.requests,
                "cache_hits": self.cache_hits,
                "compile_seconds": round(self.compile_seconds, 3),
                "traces": self.traces,
                "trace_seconds": round(self.trace_seconds, 3),
                "lower_seconds": round(self.lower_seconds, 3),
                "cache_dir": cache_dir(),
            }
