"""Device time per step under the block's ``mlp`` named scope
(``models/ouro.py``): the two MLP norms of the sandwich, the gate, up and
down projections and the gated activation, forward, the forward the backward
runs again, and backward. Nothing for a program without the scope."""

METRIC = {"layer": "models", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.per_step_ms(lambda trace: trace.scope_s("mlp")) or None
