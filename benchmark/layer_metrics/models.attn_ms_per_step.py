"""Device time per step under the block's ``attn`` named scope
(``models/olmoe.py``): the attention norm, the four projections, QK-norm,
rotary and the attention kernels, forward and backward. Nothing for a
program without the scope."""

METRIC = {"layer": "models", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.per_step_ms(lambda trace: trace.scope_s("attn")) or None
