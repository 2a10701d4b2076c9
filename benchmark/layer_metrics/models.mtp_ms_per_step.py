"""Device time per step of the multi-token-prediction module
(``models/glm_moe.py``): the operations under ``mtp``: the two norms, the
projection of [embedding ; state] and the module's block (its attention and
mixture are under ``attn`` and ``moe`` too), forward, recomputed and
backward. Its head is in the one walk under ``lm_head``. Nothing for a
program without the scope."""

METRIC = {"layer": "models", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.per_step_ms(lambda trace: trace.scope_s("mtp")) or None
