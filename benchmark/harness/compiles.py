"""The compile counter: jax's own compile events, counted by the benchmark.

jax reports every backend compilation on its monitoring bus, and with the
persistent cache on it says for each whether the executable came from the
cache. ``lookups`` counts both kinds: inside a measured window even a cache
load is a stall, so ``entry.compiles_in_window`` reads ``lookups``.
``misses`` are the compilations proper; a second run of a cell in the same
checkout must show none during set-up.
"""

from __future__ import annotations

import threading

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class CompileCounter:
    """Process-wide tallies since ``install``; ``snapshot`` is cheap and
    thread-safe, so a window is ``after - before``."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counts = {"lookups": 0, "hits": 0, "misses": 0, "seconds": 0.0}
        self._installed = False

    def install(self) -> "CompileCounter":
        if self._installed:
            return self
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        self._installed = True
        return self

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event == _BACKEND_COMPILE:
            with self._lock:
                self._counts["lookups"] += 1
                self._counts["seconds"] += float(duration)

    def _on_event(self, event: str, **_kw) -> None:
        key = {_CACHE_HIT: "hits", _CACHE_MISS: "misses"}.get(event)
        if key:
            with self._lock:
                self._counts[key] += 1

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._counts)


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}
