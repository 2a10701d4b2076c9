"""Share of the traced steady-state section in which no operation ran on the
device: 1 - union of busy intervals / window, averaged over the chips used."""

METRIC = {"layer": "device", "unit": "fraction", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.trace.idle_frac() if observed.trace else None
