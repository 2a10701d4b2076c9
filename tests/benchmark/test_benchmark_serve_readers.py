"""The serving readers and the served-vs-reference comparison, on made-up
observations: the cell they belong to waits in benchmark/pending/."""

import numpy as np
import pytest

from benchmark.harness.discovery import Catalog
from benchmark.harness.observation import Observed
from benchmark_testlib import pending_entries

CATALOG = Catalog()


def _observed(**counters):
    return Observed(cell=None, section=None, traffic={}, end_to_end={},
                    counters=counters, device={}, peaks={}, catalog=CATALOG,
                    trace=None)


def _read(name, observed):
    (entry,) = [m for e in pending_entries() for m in e["per_layer"]
                if m["name"] == name]
    return CATALOG.layer_metric(entry).read(observed)


def test_engine_counters_are_differenced_over_the_window():
    # warm-up: 64 requests in 16 batches of 9 ms; window: 2000 in 400 of 11 ms
    before = {"requests": 64, "batches": 16, "mean_batch_ms": 9.0, "p50_ms": 20.0}
    after = {"requests": 2064, "batches": 416,
             "mean_batch_ms": (16 * 9.0 + 400 * 11.0) / 416, "p50_ms": 14.0}
    observed = _observed(stats_before=before, stats_after=after,
                         client_latency_p50_ms=21.5)
    assert _read("engine.mean_batch_size", observed) == pytest.approx(5.0)
    assert _read("engine.batch_ms_mean", observed) == pytest.approx(11.0)
    assert _read("protocol.host_ms_p50", observed) == pytest.approx(7.5)
    idle = _observed(stats_before=before, stats_after=before)
    assert _read("engine.mean_batch_size", idle) is None
    assert _read("engine.batch_ms_mean", idle) is None
    assert _read("protocol.host_ms_p50", _observed()) is None


def test_generator_lateness_is_a_tail_in_milliseconds():
    late = [0.0001] * 98 + [0.002, 0.5]
    assert _read("loadgen.late_ms_p99", _observed(late_s=late)) == pytest.approx(2.0)
    assert _read("loadgen.late_ms_p99", _observed()) is None
    assert _read("serve_device.idle_frac", _observed()) is None


def test_served_scores_are_compared_not_class_order():
    driver = CATALOG.driver("image_serve")
    rng = np.random.default_rng(0)
    want = rng.normal(0.0, 10.0, 1000)
    order = [0, 0]  # request index -> payload id
    reference = {"0": want.tolist()}
    near = want + rng.normal(0.0, 0.01, 1000)   # bf16-sized noise: reorders ties
    worst, agrees = driver.agreement({0: near.tolist()}, reference, order, 0.02)
    assert agrees and worst < 0.01
    far = want.copy()
    far[np.argmax(want)] -= 1.0  # a tenth of the spread off at the top class
    worst, agrees = driver.agreement({1: far.tolist()}, reference, order, 0.02)
    assert not agrees and worst == pytest.approx(1.0 / want.std())
