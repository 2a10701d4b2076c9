"""What the path around the engine costs a median request: the client's
median latency minus the engine's own median (enqueue to result), i.e.
framing, JPEG decode, val transform, response. Timed from outside; the
engine's median runs from its start, warm-up included. To be replaced by
spans inside ``protocol`` (the next tracing issue)."""

METRIC = {"layer": "protocol", "unit": "ms", "source": "host_clock",
          "moves": "serve_latency_ms_p50"}


def read(observed):
    client = observed.counters.get("client_latency_p50_ms")
    after = observed.counters.get("stats_after")
    if client is None or not after:
        return None
    return client - after["p50_ms"]
