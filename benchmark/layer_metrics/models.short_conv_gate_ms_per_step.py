"""Device time per step under the ``short_conv_gate`` named scope
(``ops/short_conv.py``): everything of the gated short convolution that is
no matmul (the gate before the filter, the filter's shifted multiply-adds,
the gate after it, their casts), forward, the forward the backward runs
again where blocks are recomputed, and backward (the shifts the other way
and the filter's gradient). It reads the XLA fusions or a kernel, whichever
runs under the scope. Nothing for a program without the scope."""

METRIC = {"layer": "models", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.per_step_ms(lambda trace: trace.scope_s("short_conv_gate")) or None
