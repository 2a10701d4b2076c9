"""Mean time of one batch on the device inside the window, dispatch to
result on the host, from the engine's own counters (``stats`` op before and
after): the change in batches x mean_batch_ms over the change in batches."""

METRIC = {"layer": "engine", "unit": "ms", "source": "program_counter",
          "moves": "serve_latency_ms_p50"}


def read(observed):
    before = observed.counters.get("stats_before")
    after = observed.counters.get("stats_after")
    if not (before and after):
        return None
    batches = after["batches"] - before["batches"]
    if not batches:
        return None
    total = (after["batches"] * after["mean_batch_ms"]
             - before["batches"] * before["mean_batch_ms"])
    return total / batches
