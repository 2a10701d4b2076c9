"""Runtime capture: jit compile events, compilation-cache hits, device
memory stats.

**Compile events.** A recompile storm (a shape drifting per step, a
donation mismatch, an eval path missing its cache) shows up as minutes of
silence on the rank that hits it — invisible in rank-0 logs. JAX's
monitoring bus emits a duration event for every backend compile;
``install_compile_listener`` counts them into the registry
(``jit.compiles`` / ``jit.compile_s``) and drops one ``kind="compile"``
record per compile in the per-rank sink, so both the run report (count +
wall) and the Perfetto trace (a slice on the ``jit`` track) carry them.

**Compilation-cache events.** With the persistent compilation cache on
(``COMPILE_CACHE`` — asyncplane/compile_cache.py), the bus additionally
reports a cache hit or miss per lookup. A HIT still flows through the
``backend_compile`` duration event (jax wraps compile-or-retrieve in one
timer), but retrieving a serialized executable is NOT a compilation: the
listener counts it as ``jit.cache_hits`` + a ``kind="compile.cache"``
record and SUPPRESSES the ``jit.compiles``/``kind="compile"`` emission
for that lookup — so a deliberately warm restart reads as zero
recompiles, not a recompile storm. The hit→compile pairing is
thread-local (concurrent compiles on other threads cannot steal each
other's suppression).

The listener registers once per process and stays registered (JAX has no
public unregister). It counts into the process-wide registry ALWAYS
(``jit.compiles`` / ``jit.compile_s`` / ``jit.cache_hits`` / … — a few
counter increments per compile, none on the step path) and writes records
only while the telemetry sink is open: a server without a sink still
answers ``jit_compiles`` in its ``stats`` op.

**Memory stats.** ``device.memory_stats()`` (bytes_in_use /
peak_bytes_in_use on TPU; ``None`` on the CPU backend — skipped) sampled
once per epoch into ``kind="memstats"`` records: the slow-leak and
fragmentation signal at epoch granularity, costing one host call per
device per epoch.
"""

from __future__ import annotations

import threading
import time

from distribuuuu_tpu.telemetry import registry as registry_lib, spans

# the monitoring key of one backend compilation (jax 0.9.0:
# /jax/core/compile/backend_compile_duration); the other
# /jax/core/compile/* keys are sub-phases of the same compile
_COMPILE_EVENT = "backend_compile"
# persistent-compilation-cache lookup outcomes (same bus, plain events);
# on a hit the sequence is cache_hits → ... → backend_compile_duration,
# all on the compiling thread
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_state = {"installed": False, "hits": 0, "misses": 0}
_tls = threading.local()  # per-thread "this compile was a cache hit" flag


def _on_event(event: str, **_kw) -> None:
    """Plain (non-duration) bus events: the compilation-cache outcomes."""
    if event == _CACHE_HIT_EVENT:
        _tls.cache_hit = True
        _state["hits"] += 1
        outcome = "hit"
    elif event == _CACHE_MISS_EVENT:
        _tls.cache_hit = False
        _state["misses"] += 1
        outcome = "miss"
    else:
        return
    registry_lib.get_registry().counter(
        "jit.cache_hits" if outcome == "hit" else "jit.cache_misses"
    ).inc(1)
    spans.emit_event(
        "compile.cache", event=outcome,
        hits=_state["hits"], misses=_state["misses"],
    )


def _on_event_duration(event: str, duration: float, **_kw) -> None:
    if _COMPILE_EVENT not in event:
        return
    was_hit = getattr(_tls, "cache_hit", False)
    _tls.cache_hit = False
    reg = registry_lib.get_registry()
    if was_hit:
        reg.counter("jit.cache_hit_s").inc(float(duration))
        return  # a deserialization, not a compilation
    reg.counter("jit.compiles").inc(1)
    reg.counter("jit.compile_s").inc(float(duration))
    # mono stamp approximates the compile's END (the bus reports after)
    spans.emit_event(
        "compile", event=event, dur_s=round(float(duration), 6),
        mono=round(time.perf_counter(), 6),
    )


def install_compile_listener() -> bool:
    """Idempotent; returns False when the monitoring bus is unavailable
    (never raises — observability must not take a run down)."""
    if _state["installed"]:
        return True
    try:
        from jax import monitoring
    except Exception:  # pragma: no cover — jax without the bus
        return False
    monitoring.register_event_duration_secs_listener(_on_event_duration)
    monitoring.register_event_listener(_on_event)
    _state["installed"] = True
    return True


def cache_tallies() -> tuple[int, int]:
    """(hits, misses) of the persistent compilation cache this process —
    process-lifetime totals, independent of the telemetry sink state."""
    return _state["hits"], _state["misses"]


def sample_memstats(**attrs) -> int:
    """One ``kind="memstats"`` record per local device that reports
    (TPU/GPU backends; the CPU backend returns None and is skipped).
    Returns the number of records emitted."""
    if not spans.enabled():
        return 0
    import jax

    n = 0
    for i, d in enumerate(jax.local_devices()):
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        spans.emit_event(
            "memstats", device=i,
            bytes_in_use=int(stats.get("bytes_in_use", 0)),
            peak_bytes_in_use=int(stats.get("peak_bytes_in_use", 0)),
            **attrs,
        )
        n += 1
    return n
