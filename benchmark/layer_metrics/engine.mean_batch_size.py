"""Requests per dispatched batch inside the window, from the engine's own
counters over the wire (``stats`` op read before and after): requests over
batches. (Occupancy proper needs the raw filled/slots counters, which the
snapshot only gives as a ratio since start.)"""

METRIC = {"layer": "engine", "unit": "requests", "source": "program_counter",
          "moves": "serve_goodput_per_s_per_chip"}


def read(observed):
    before = observed.counters.get("stats_before")
    after = observed.counters.get("stats_after")
    if not (before and after):
        return None
    batches = after["batches"] - before["batches"]
    return (after["requests"] - before["requests"]) / batches if batches else None
