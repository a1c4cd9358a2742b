"""Bounding XLA compile-cache growth in long-lived processes.

Observed pathology (this environment's jaxlib CPU build): one process
that keeps compiling DISTINCT programs eventually segfaults inside the
XLA CPU compiler — the full test suite run in one process (several
programs a test) dies at ~85% unless compiled executables drop between
modules (tests/conftest.py's between-modules `jax.clear_caches()`
fixture, gated on resident memory).
Thousands of distinct TINY programs do NOT crash (the trigger is the
suite's program population, SPMD collectives/donation/scans, not raw
count), so the suite-scale evidence is the operative fact. A long-lived
serving daemon that keeps admitting new program shapes (models,
adapters, pooling variants, padded-length buckets) accumulates the
same compiled-artifact volume over days.

This module is the daemon-side guard: count the entries of the
process's OWN jitted entry points (`fn._cache_size()`, the same counter
tests/test_prefix_cache.py pins) and, when a budget is exceeded, call
`jax.clear_caches()` at a SAFE BOUNDARY — a moment the caller
guarantees no compiled program is mid-flight (the LM worker's idle
point: no active slots, empty queue). Cleared programs recompile
transparently on next use; steady-state servers (three programs) never
trip the budget, so the guard costs nothing until the pathology-shaped
workload appears.
"""

from __future__ import annotations

from typing import Callable, List

__all__ = ["jit_cache_entries", "CompileCacheGuard"]


def jit_cache_entries(*fns) -> int:
    """Total compiled-executable entries across `fns` (0 for anything
    without a `_cache_size` — plain callables pass through silently, so
    callers can register hooks without caring which are jitted)."""
    total = 0
    for f in fns:
        size = getattr(f, "_cache_size", None)
        if callable(size):
            total += int(size())
    return total


class CompileCacheGuard:
    """Budgeted `jax.clear_caches()` for a long-lived serving loop.

    `register(fn)` adds a jitted entry point (or a zero-arg callable
    returning a LIST of them — for lazily-created program families like
    the daemon's per-pooling embed fns; return a snapshot copy, not a
    live dict view, so the guard never iterates a structure another
    thread is inserting into). `add_busy_check(fn)` adds a zero-arg
    predicate; while any returns True the guard holds off — device work
    that runs OUTSIDE the calling loop (the daemon's embed endpoint
    runs on asyncio.to_thread) must register one AND flip the state it
    reads under `guard.lock` (the check and the clear run atomically
    under it, so a correctly-locked transition can never slip between
    them). `maybe_clear()` — call it ONLY at a safe boundary — clears
    every XLA cache when the registered entry count reaches `budget`.
    budget <= 0 disables."""

    def __init__(self, budget: int):
        import threading

        self.budget = int(budget)
        self.clears = 0  # observability: soak test + ops metrics
        self._fns: List[Callable] = []
        self._busy: List[Callable] = []
        # check+clear run atomically under this lock; out-of-loop device
        # work must flip its busy state UNDER THE SAME LOCK (the
        # daemon's embed path does), or the busy check could pass just
        # before the work enters its program and the clear land mid-
        # flight anyway
        self.lock = threading.Lock()

    def register(self, fn):
        self._fns.append(fn)
        return fn

    def add_busy_check(self, fn):
        self._busy.append(fn)
        return fn

    def _entries(self) -> int:
        flat = []
        for f in self._fns:
            if getattr(f, "_cache_size", None) is None and callable(f):
                try:
                    flat.extend(f())
                    continue
                except TypeError:
                    pass  # a plain non-jitted registrant: counts as 0
            flat.append(f)
        return jit_cache_entries(*flat)

    def maybe_clear(self) -> bool:
        if self.budget <= 0 or self._entries() < self.budget:
            return False
        with self.lock:  # atomic with the busy transitions (see __init__)
            if any(b() for b in self._busy):
                return False  # device work in flight on another thread
            import jax

            jax.clear_caches()
            self.clears += 1
            return True
