"""Device time per step of the shared expert (``models/glm_moe.py``): the
operations under ``moe_shared``, a dense gated MLP of the experts' width
that every token visits, forward, recomputed and backward. Nothing for a
program without the scope."""

METRIC = {"layer": "models", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.per_step_ms(lambda trace: trace.scope_s("moe_shared")) or None
