"""Process-level replay compile cache + JAX persistent-cache wiring.

Two cooperating layers keep hyperscale sweeps compile-bound only once:

  * an in-process function cache keyed on :class:`ReplayStatics` — one
    donating ``jax.jit`` wrapper per (policy, cfg, model-set).  XLA's own
    jit cache then holds one *executable* per argument-shape signature,
    i.e. per shape bucket (``repro.core.bucketing``), so the effective
    replay cache key is ``(bucket_shape, policy, cfg, model-set)``;
  * JAX's persistent compilation cache (on-disk), in the directory that
    ``JAX_COMPILATION_CACHE_DIR`` names or else at ``<checkout>/.jax_cache``,
    so repeated *processes* — CI runs, sweep drivers — also skip XLA for
    already-seen buckets.

This module holds no jax arrays, only callables, so it is safe to import
before device initialization.
"""
from __future__ import annotations

import os
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional

import jax

_RUN_CACHE: "OrderedDict[Any, Callable]" = OrderedDict()
_STATS = {"hits": 0, "misses": 0, "evictions": 0}
_MAX_ENTRIES: Optional[int] = None
_PERSISTENT_DIR: str = ""
# A fixed path (the cache key includes it): the checkout root, found from
# this file (src/repro/core/), never from the current directory.
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def cached_replay_fn(key: Any, build: Callable[[], Callable]) -> Callable:
    """Return the process-cached replay callable for ``key`` (any
    hashable — a :class:`repro.core.batched.ReplayStatics`, or a
    ``(statics, variant, ...)`` tuple such as the sharded engine's
    ``(st, K)`` and the streaming engine's ``(st, "chunk", chunk)`` /
    ``(st, "finalize")`` keys), building it on miss.

    When a bound is set with :func:`set_max_entries` the cache evicts
    least-recently-used wrappers (a hit refreshes recency); unbounded by
    default, which matches the historical behavior."""
    fn = _RUN_CACHE.get(key)
    if fn is None:
        _STATS["misses"] += 1
        fn = _RUN_CACHE[key] = build()
        if _MAX_ENTRIES is not None:
            while len(_RUN_CACHE) > _MAX_ENTRIES:
                _RUN_CACHE.popitem(last=False)
                _STATS["evictions"] += 1
    else:
        _STATS["hits"] += 1
        _RUN_CACHE.move_to_end(key)
    return fn


def set_max_entries(n: Optional[int]) -> Optional[int]:
    """Bound the wrapper cache to ``n`` LRU entries (None = unbounded,
    the default).  Evicts immediately if already over.  Returns the
    previous bound so callers can restore it (try/finally)."""
    global _MAX_ENTRIES
    prev, _MAX_ENTRIES = _MAX_ENTRIES, n
    if n is not None:
        while len(_RUN_CACHE) > n:
            _RUN_CACHE.popitem(last=False)
            _STATS["evictions"] += 1
    return prev


def cache_stats() -> Dict[str, int]:
    """Hit/miss/eviction counters plus the number of live cached replay
    fns (the flight recorder snapshots this into its JSONL stream)."""
    return dict(_STATS, entries=len(_RUN_CACHE))


def clear_cache() -> None:
    _RUN_CACHE.clear()
    _STATS["hits"] = _STATS["misses"] = _STATS["evictions"] = 0


def ensure_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, places it (JAX
    reads the variable itself; no other directory is set); otherwise it
    is ``<checkout>/.jax_cache``.  Idempotent; cheap to call per replay."""
    global _PERSISTENT_DIR
    if not _PERSISTENT_DIR:
        path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if not path:
            path = _DEFAULT_DIR
            jax.config.update("jax_compilation_cache_dir", path)
        os.makedirs(path, exist_ok=True)
        # Replay scans compile in ~0.5 s; cache them all, not just the
        # >1 s default.
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
        _PERSISTENT_DIR = path
    return _PERSISTENT_DIR


__all__ = ["cached_replay_fn", "cache_stats", "clear_cache",
           "set_max_entries", "ensure_persistent_cache"]
