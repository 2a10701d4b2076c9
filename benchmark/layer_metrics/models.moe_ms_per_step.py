"""Device time per step of the mixture of experts (``models/olmoe.py``,
``ops/moe.py``): the operations under the block's ``moe`` named scope (the
expert norm, the router, top-k, sort and gathers under ``moe_route``, the
activation under ``moe_experts``), forward and backward, and the grouped
matmuls themselves, which XLA:TPU lowers to kernels it names
``ragged-dot-*`` and whose scope it drops (their ``op_name`` is the kernel's
own). Nothing for a program with neither."""

from benchmark.harness.trace import in_scope

METRIC = {"layer": "models", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}

GROUPED_MATMUL = "ragged-dot"


def read(observed):
    return observed.per_step_ms(lambda trace: trace.seconds_where(
        lambda e: in_scope(e["op_name"], "moe")
        or e["name"].startswith(GROUPED_MATMUL)
    )) or None
