"""Device time per step of the operations under the step's
``optimizer_update`` named scope (the fused Pallas update where ``auto``
selects it)."""

METRIC = {"layer": "kernels", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.per_step_ms(lambda trace: trace.scope_s("optimizer_update")) or None
