"""Device time per step of the collectives the partition lowering induces
(all-reduce, all-gather, reduce-scatter, ...), averaged over the chips."""

METRIC = {"layer": "partition", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.per_step_ms(lambda trace: trace.collective_s())
