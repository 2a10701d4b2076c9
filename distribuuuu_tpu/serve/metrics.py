"""Serving observability: latency histogram, batch occupancy, throughput.

Per-request latency is measured enqueue → response-demux (the engine-level
number a load balancer would see, excluding client transport). Snapshots
report p50/p90/p99 from a bounded reservoir, batch occupancy (requests
per dispatched bucket slot — the padding-waste gauge), and throughput
over the observation window; ``emit()`` lands a snapshot in the existing
``utils/jsonlog.py`` JSONL sink (kind="serve"), the same machine-readable
channel train/eval metrics use.

Since the telemetry layer (ISSUE 5) the meters are the SHARED registry
instruments (telemetry/registry.py) — the same Counter/Histogram
machinery, reservoir, and nearest-rank percentile math train-side
telemetry reports through, so serve and train speak one schema. Each
``ServeMetrics`` owns a fresh ``Registry`` instance because it is a
bounded observation WINDOW (benches install a new one per load point);
pass ``registry=`` to aggregate into an external one instead. The
serve_bench JSON fields are unchanged — snapshot() is field-for-field
what it was before the migration.
"""

from __future__ import annotations

import time

from distribuuuu_tpu.telemetry.registry import Registry, percentile
from distribuuuu_tpu.utils.jsonlog import metrics_log


class ServeMetrics:
    """Thread-safe accumulator; one instance per observation window (the
    engine's is swappable — benches install a fresh one per load point)."""

    def __init__(self, max_samples: int = 65536, registry: Registry | None = None):
        self.max_samples = max_samples
        self.registry = registry or Registry()
        self._lat = self.registry.histogram("serve.latency_s", max_samples)
        self._t0 = time.perf_counter()

    def record_batch(
        self, n: int, bucket: int, batch_s: float, latencies_s: list[float]
    ) -> None:
        reg = self.registry
        reg.counter("serve.requests").inc(n)
        reg.counter("serve.batches").inc(1)
        reg.counter("serve.occ_filled").inc(n)
        reg.counter("serve.occ_slots").inc(bucket)
        reg.counter("serve.batch_s").inc(batch_s)
        for lat in latencies_s:
            self._lat.observe(lat)

    def record_rejection(self) -> None:
        self.registry.counter("serve.rejected").inc(1)

    def _count(self, name: str) -> float:
        return self.registry.counter(name).value

    def mean_batch_ms(self) -> float:
        """Recent per-batch service time — drives retry-after estimates."""
        n_b = self._count("serve.batches")
        if not n_b:
            return 0.0
        return self._count("serve.batch_s") / n_b * 1e3

    def snapshot(self) -> dict:
        lat = self._lat.values()  # sorted reservoir
        n_req = self._count("serve.requests")
        n_rej = self._count("serve.rejected")
        n_b = self._count("serve.batches")
        filled = self._count("serve.occ_filled")
        slots = self._count("serve.occ_slots")
        batch_s = self._count("serve.batch_s")
        window = max(time.perf_counter() - self._t0, 1e-9)
        return {
            "requests": int(n_req),
            "rejected": int(n_rej),
            "batches": int(n_b),
            "throughput_rps": round(n_req / window, 2),
            "p50_ms": round(percentile(lat, 0.50) * 1e3, 3),
            "p90_ms": round(percentile(lat, 0.90) * 1e3, 3),
            "p99_ms": round(percentile(lat, 0.99) * 1e3, 3),
            "mean_ms": round(sum(lat) / len(lat) * 1e3, 3) if lat else 0.0,
            "batch_occupancy": round(filled / slots, 4) if slots else 0.0,
            # the ratio's raw counters: a reader differences them over its
            # own window instead of a ratio that runs from engine start
            "occ_filled": int(filled),
            "occ_slots": int(slots),
            "mean_batch_ms": round(batch_s / n_b * 1e3, 3) if n_b else 0.0,
            "window_s": round(window, 3),
        }

    def emit(self, **extra) -> None:
        """One JSONL record via the shared sink (no-op until
        ``setup_metrics_log`` ran — same contract as train metrics; the
        record also mirrors into the per-rank telemetry sink)."""
        metrics_log("serve", **self.snapshot(), **extra)
