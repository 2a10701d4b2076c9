"""Device time per step under the ``attn_gate`` named scope
(``models/lfm2_moe.Attention`` with ``gated``, ``models/afmoe.py``): the
attention output's gate of every layer of either kind, which is its
projection (as wide as the query's), the float32 sigmoid and the product with
the heads' output; forward, recomputed and backward. Nothing for a program
without the scope."""

METRIC = {"layer": "models", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.per_step_ms(lambda trace: trace.scope_s("attn_gate")) or None
