"""Device time per step under the block's ``short_conv`` named scope
(``models/lfm2_moe.py``): the whole gated short-convolution mixer, which is
the operator norm, the in and out projections and, inside
``short_conv_gate``, the two gates and the filter; forward, the forward the
backward runs again where blocks are recomputed, and backward. Nothing for a
program without the scope."""

METRIC = {"layer": "models", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.per_step_ms(lambda trace: trace.scope_s("short_conv")) or None
