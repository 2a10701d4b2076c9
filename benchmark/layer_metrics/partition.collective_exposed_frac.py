"""Share of the collectives' time during which no other operation ran on the
same device: 0 = hidden behind compute, 1 = the device waited for the wire."""

METRIC = {"layer": "partition", "unit": "fraction", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.trace.collective_exposed_frac() if observed.trace else None
