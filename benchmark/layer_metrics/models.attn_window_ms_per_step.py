"""Device time per step under the ``attn_window`` named scope
(``models/lfm2_moe.Attention`` as ``models/afmoe.py`` builds a
``sliding_attention`` layer's): the whole mixer of every sliding-window layer,
which is its five projections, the per-head norms, rotary, the flash kernels
walking the window's tiles and the output's gate; forward, the forward the
backward runs again where blocks are recomputed, and backward. A
full-attention layer's mixer lies under ``attn`` alone. Nothing for a program
without the scope."""

METRIC = {"layer": "models", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.per_step_ms(lambda trace: trace.scope_s("attn_window")) or None
