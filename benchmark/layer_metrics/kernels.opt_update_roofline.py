"""The optimizer update's share of its roofline, in percent: the least time
one pass over parameters, gradients and momentum could take at the published
HBM bandwidth (``costs/opt_update.py``; the kernel is bandwidth-bound), over
the device time measured under the ``optimizer_update`` scope."""

METRIC = {"layer": "kernels", "unit": "%", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    ms = observed.per_step_ms(lambda trace: trace.scope_s("optimizer_update"))
    if not ms:
        return None
    costs = observed.catalog.costs("opt_update")
    c = observed.counters
    # gradients arrive in the parameters' dtype and layout
    moved = costs.one_pass_bytes(c["param_bytes"], c["param_bytes"], c["moment_bytes"])
    return 100.0 * costs.roofline_seconds(moved, observed.peaks) / (ms / 1e3)
