"""How late after its due time the generator wrote a request to the socket,
99th percentile. Validity of the serve metrics: a starved generator must not
be read as a fast server. Small against the median latency, or the run does
not count."""

from benchmark.harness import stats

METRIC = {"layer": "loadgen", "unit": "ms", "source": "host_clock",
          "moves": "serve_latency_ms_p99"}


def read(observed):
    late = observed.counters.get("late_s")
    return stats.percentile(late, 0.99) * 1e3 if late else None
