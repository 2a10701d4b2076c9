"""Per-rank telemetry sink + span API — the event half of the telemetry
layer (docs/DESIGN.md "telemetry" row).

Every process (rank) appends one JSON object per line to its OWN file,
``{dir}/rank{NNNNN}.jsonl`` — unlike ``utils/jsonlog.py``'s primary-only
``metrics.jsonl``, signals that are rank-local by nature (a straggler's
step times, a rank-3 data stall, a lone recompile storm) survive on every
rank and merge later (telemetry/export.py, tools/run_report.py).

One API, two sinks. Every program span also becomes a
``jax.profiler.TraceAnnotation`` named ``dtpu.<layer>.<name>``
(telemetry/schema.py ``SPANS``) around the very statements it measures, so
it lands in any profiler capture (``PROF.*``, the benchmark's) on the
DEVICE's clock, beside the device's operations — whether or not the JSONL
sink is open. ``span()`` does both; ``emit_span()`` takes finished stamps
and cannot annotate after the fact, so its hot-path sites wrap the measured
statements in :func:`annotate` themselves. Outside a profiler session an
annotation is a sub-microsecond no-op.

Two timestamp domains in the JSONL sink, bridged per file:

* ``t``    — ``time.time()`` unix seconds (event kinds mirrored from
             jsonlog, resilience events);
* ``t0``   — ``time.perf_counter()`` monotonic seconds (spans — the same
             clock the trainer's timeline stamps use, so intervals are
             exact).

The first record of every file is a ``kind="clock"`` anchor holding one
(unix, mono) pair sampled back-to-back; the exporter maps every mono
stamp of that file onto the shared unix timebase through it, which is how
N rank files (and ``metrics.jsonl``'s timeline records) land on ONE
Perfetto track-per-rank timeline.

Trajectory neutrality is a hard contract: nothing here touches RNG,
jitted code, or training state — telemetry on ≡ off bit-identically
(tests/test_telemetry.py proves it end-to-end).

Module-level singleton like ``utils/jsonlog.py``: ``setup_telemetry`` in
``train_model``/``serve_net``, then ``span()``/``emit_event()`` from
anywhere; a cheap no-op until set up. Writes are lock-serialized — loader
worker threads and the heartbeat thread emit concurrently.
"""

from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager

from distribuuuu_tpu.telemetry import schema
from distribuuuu_tpu.telemetry.registry import get_registry

SPAN_SCHEMA = 1

_sink = {"f": None, "rank": 0, "path": None}
_lock = threading.Lock()
_tls = threading.local()  # per-thread span stack (nesting depth/track)


def setup_telemetry(tdir: str, rank: int = 0) -> str:
    """Open (append) this rank's sink ``{tdir}/rank{NNNNN}.jsonl`` and
    write the clock anchor. Returns the file path. Unlike the jsonlog
    sink there is no ``primary`` gate — per-rank files are the point.
    (Convention: ``tdir`` = ``{OUT_DIR}/telemetry`` — where the exporter
    and run_report look; ``telemetry.setup_from_cfg`` applies it.)"""
    close_telemetry()
    os.makedirs(tdir, exist_ok=True)
    path = os.path.join(tdir, f"rank{int(rank):05d}.jsonl")
    with _lock:
        _sink["f"] = open(path, "a", buffering=1)
        _sink["rank"] = int(rank)
        _sink["path"] = path
    # (unix, mono) sampled back-to-back: the exporter's timebase bridge
    emit_event("clock", unix=round(time.time(), 6),
               mono=round(time.perf_counter(), 6))
    return path


def enabled() -> bool:
    return _sink["f"] is not None


def sink_path() -> str | None:
    return _sink["path"] if _sink["f"] is not None else None


def close_telemetry() -> None:
    with _lock:
        if _sink["f"] is not None:
            _sink["f"].close()
            _sink["f"] = None
            _sink["path"] = None


def emit_event(kind: str, **fields) -> None:
    """Append one record: {"kind", "rank", "t", **fields}. No-op until
    ``setup_telemetry`` ran. Every ``kind`` must be declared in
    telemetry/schema.py (tools/check_telemetry_schema.py enforces call
    sites statically; tests validate emitted files dynamically)."""
    f = _sink["f"]
    if f is None:
        return
    rec = {"kind": kind, "rank": _sink["rank"], "t": round(time.time(), 3)}
    rec.update(fields)
    with _lock:
        if _sink["f"] is not None:
            _sink["f"].write(json.dumps(rec) + "\n")


def mirror_event(kind: str, fields: dict) -> None:
    """The jsonlog bridge: ``utils/jsonlog.metrics_log`` forwards every
    record here so rank-local kinds (stall, data_error, nonfinite, ...)
    survive on ranks > 0 instead of being silently dropped by the
    primary-only sink. ``timeline`` is excluded — per-batch timeline
    records stay in ``metrics.jsonl`` (primary) and the exporter reads
    them from there; mirroring would double them."""
    if _sink["f"] is None or kind == "timeline":
        return
    emit_event(kind, **fields)


def emit_span(name: str, t0: float, t1: float, *, track: str = "main",
              **attrs) -> None:
    """One completed span from precomputed ``time.perf_counter`` stamps
    (the trainer's hot path measures first, emits after — the write never
    sits inside the measured interval). ``track`` groups spans onto one
    Perfetto line per (rank, track)."""
    if _sink["f"] is None:
        return
    emit_event(
        "span", v=SPAN_SCHEMA, name=name, t0=round(t0, 6),
        dur=round(t1 - t0, 6), track=track, **attrs,
    )


_TraceAnnotation = None  # jax.profiler.TraceAnnotation, imported at first use


def annotate(name: str):
    """The profiler half of a program span: a context manager that marks
    the enclosed statements as ``dtpu.<layer>.<name>`` in whatever profiler
    capture is open (none: a no-op). ``span()`` applies it itself; the
    sites that stamp first and ``emit_span`` later (``wait``, ``h2d``,
    ``step`` on the loop's thread, ``decode`` and ``assemble`` on the
    loader's workers) wrap the stamped statements in it. jax is imported here, at
    first use, so importing this module stays jax-free (the fleet router)."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        from jax.profiler import TraceAnnotation

        _TraceAnnotation = TraceAnnotation
    return _TraceAnnotation(schema.ANNOTATIONS[name])


@contextmanager
def setup_timer(name: str):
    """Seconds of one piece of set-up (``lower``, ``init_state``) into the
    process-wide registry counter ``setup.<name>_s``, whether or not a sink
    is open: what a reader in the same process (the benchmark's
    ``entry.*_s``) finds. Not a span — set-up is over before any profiler
    capture starts, so an annotation there would land nowhere. One pair of
    ``perf_counter`` per call, a handful of calls per process. Also a
    decorator (``@setup_timer("lower")``), as every ``contextmanager`` is."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        get_registry().counter(f"setup.{name}_s").inc(time.perf_counter() - t0)


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


@contextmanager
def span(name: str, *, track: str | None = None, **attrs):
    """Context-manager span with nesting: depth and parent name come from
    a thread-local stack, so ``span("ckpt_save")`` inside
    ``span("epoch")`` renders nested in Perfetto and carries
    ``depth``/``parent`` for programmatic consumers. The same interval
    is annotated for the profiler (:func:`annotate`). With telemetry off
    that annotation and one truthiness check are all it costs."""
    with annotate(name):
        if _sink["f"] is None:
            yield
            return
        st = _stack()
        if track is None:
            track = (
                st[-1][1] if st else f"thread-{threading.get_ident() % 10000}"
            )
        st.append((name, track))
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            st.pop()
            extra = {}
            if st:
                extra = {"depth": len(st), "parent": st[-1][0]}
            emit_span(name, t0, t1, track=track, **attrs, **extra)
