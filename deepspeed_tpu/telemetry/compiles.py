"""Process-wide XLA compile counter.

"Zero compilations in steady state" is the serving contract every perf
number leans on, and the engines compile on worker threads (a replica's
tick thread, the pipeline's stage threads).  JAX's own test counters are
thread-local, so they see nothing of that.  This one listens to
``jax.monitoring`` — the events fire in whichever thread compiles — and
counts under a lock for the whole process.

    with count_compiles() as compiles:
        serve(...)
    assert compiles.count == 0
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading

import jax

# one per executable XLA is asked for: fired around the backend compile
# whether the persistent cache then answers it or not, never for an
# in-memory jit cache hit
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


@dataclasses.dataclass
class CompileCount:
    """What compiled while a :func:`count_compiles` block was open."""
    count: int = 0            # executables requested (any thread)
    seconds: float = 0.0      # wall seconds inside those requests
    cache_hits: int = 0       # answered by the persistent cache
    cache_misses: int = 0     # compiled and written to it

    def __post_init__(self):
        self._lock = threading.Lock()

    def _on_duration(self, event: str, secs: float, **_):
        if event == _COMPILE_EVENT:
            with self._lock:
                self.count += 1
                self.seconds += secs

    def _on_event(self, event: str, **_):
        if event == _CACHE_HIT_EVENT:
            with self._lock:
                self.cache_hits += 1
        elif event == _CACHE_MISS_EVENT:
            with self._lock:
                self.cache_misses += 1


@contextlib.contextmanager
def count_compiles():
    """Count every XLA compilation in the process, on any thread, while
    the block is open."""
    c = CompileCount()
    jax.monitoring.register_event_duration_secs_listener(c._on_duration)
    jax.monitoring.register_event_listener(c._on_event)
    try:
        yield c
    finally:
        jax.monitoring.unregister_event_duration_listener(c._on_duration)
        jax.monitoring.unregister_event_listener(c._on_event)
