"""Persistent XLA compilation cache setup (shared by every entry point).

The wide SRTP programs take a minute each to compile cold; caching them
on disk makes a second process in the same checkout start warm.  The
directory is part of the cache key, so it never moves: where
`JAX_COMPILATION_CACHE_DIR` is set JAX already reads it and nothing is
set in code; otherwise it is `<checkout>/.jax_cache`.  Failures
propagate — a cache that cannot be set up is a broken checkout, not a
slow one.
"""

from __future__ import annotations

import ctypes
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Iterable, Optional

#: fixed default, beside the package (listed in .gitignore)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


class CompileCacheStats:
    """Process-wide compile/cache counters fed by `jax.monitoring`
    events, matched by substring: "cache_hit" / "cache_miss" are the
    persistent cache's `/jax/compilation_cache/cache_hits|cache_misses`
    (a miss is an entry WRITTEN, so compiles under the min-compile-time
    threshold count as neither), "compil" covers the trace / lower /
    backend-compile durations every in-process jit miss emits.
    `LoopPerf.register_metrics` exports
    them as `compile_cache_hits` / `compile_cache_misses` /
    `compile_events` (+ `compile_seconds_total`): a recompile landing
    on the data path shows up as a counter step in the scrape, not a
    mystery latency spike."""

    def __init__(self) -> None:
        self.hits = 0
        self.misses = 0
        self.compile_events = 0
        self.compile_seconds = 0.0

    def on_event(self, event: str, **kwargs) -> None:
        if "cache_hit" in event:
            self.hits += 1
        elif "cache_miss" in event:
            self.misses += 1

    def on_duration(self, event: str, duration_secs: float,
                    **kwargs) -> None:
        if "compil" in event:
            self.compile_events += 1
            self.compile_seconds += float(duration_secs)


_STATS: Optional[CompileCacheStats] = None


def compile_stats() -> CompileCacheStats:
    """Singleton stats, registering the jax.monitoring listeners on
    first use (listener registration is additive and process-global,
    so exactly one registration per process)."""
    global _STATS
    if _STATS is None:
        from jax import monitoring

        _STATS = CompileCacheStats()
        monitoring.register_event_listener(_STATS.on_event)
        monitoring.register_event_duration_secs_listener(
            _STATS.on_duration)
    return _STATS


#: compiles side by side at most.  HOST-SPECIFIC: sized for the 40 GiB
#: host of the one-chip v5e machine and not derived from the memory of
#: the host it runs on.  There the compiler holds 2.4-4.6 GB of host
#: memory for one served program (the 4,096-row class, measured PR 31);
#: a GCM rung of eight at once peaked at 38.5 GB beside a bridge of
#: 10,240 endpoints and was killed, six peak at 33.6 GB and the ladder
#: takes no longer.  A host with less memory a chip needs fewer
#: (roughly: what the bridge leaves free / 4.6 GB); one with more
#: gains nothing past its cores
MAX_COMPILE_WORKERS = 6


def compile_concurrently(thunks: Iterable[Callable[[], None]]) -> None:
    """Run independent warm-up thunks on one thread pool, at most one
    per core and `MAX_COMPILE_WORKERS` in all, and wait for all.

    Each thunk's time is an XLA compile, which releases the GIL, so the
    programs of one warm-up rung compile side by side instead of one
    after another.  Nothing that is TIMED belongs in here.  The first
    exception propagates once every thunk has finished."""
    thunks = list(thunks)
    if not thunks:
        return
    workers = min(len(thunks), os.cpu_count() or 1, MAX_COMPILE_WORKERS)
    try:
        with ThreadPoolExecutor(max_workers=workers) as pool:
            for fut in [pool.submit(t) for t in thunks]:
                fut.result()
    finally:
        _trim_heap()


def _trim_heap() -> None:
    """Hand the compile threads' freed memory back to the system.  A
    TPU compile of one served program peaks at 2.4-4.6 GB of host
    memory, a rung runs eight side by side, and glibc leaves what they
    free in their threads' arenas: the process then carries a rung's
    peak for good, next to everything admission allocates after it."""
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):    # another libc: nothing to trim
        pass


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return path
