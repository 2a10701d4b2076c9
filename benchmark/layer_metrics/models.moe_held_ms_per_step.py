"""Device time per step of the routed part of the mixtures as one chip of
the expert-parallel group runs it (``models/glm_moe.py``, ``ops/moe.py``):
the operations under the block's ``moe`` named scope that are NOT under
``moe_shared``: the expert norm, the sigmoid router, top-k, the sort of the
held rows and the gathers (``moe_route``), the grouped matmuls on the held
experts (``moe_experts``), forward, recomputed and backward. Nothing for a
program without a ``moe_shared`` scope (a mixture with no share of its
own)."""

from benchmark.harness.trace import in_scope

METRIC = {"layer": "models", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    if not observed.per_step_ms(lambda trace: trace.scope_s("moe_shared")):
        return None
    return observed.per_step_ms(lambda trace: trace.seconds_where(
        lambda e: in_scope(e["op_name"], "moe")
        and not in_scope(e["op_name"], "moe_shared")
    )) or None
