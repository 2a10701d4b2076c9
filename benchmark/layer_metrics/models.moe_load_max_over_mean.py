"""Rows the fullest expert receives over the mean, averaged over the
window's steps: the step metric ``moe_load_max_over_mean``
(``ops/moe.load_max_over_mean`` on the router's counts) as the driver read
it. 1.0 is a uniform routing; the grouped matmul's tail grows with it.
Nothing where the program reports no such metric."""

METRIC = {"layer": "models", "unit": "ratio", "source": "program_counter",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.counters.get("moe_load_max_over_mean")
