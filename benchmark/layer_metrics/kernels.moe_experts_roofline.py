"""The grouped expert matmuls' share of the MXU's peak, in percent: the
operations a step's routed rows need (``costs/olmoe.py``
``expert_macs_per_token`` x the tokens one chip steps, forward once and
backward twice) at the published bf16 peak, over the device time of the
grouped matmuls and what is fused around them: XLA:TPU's ``ragged-dot-*``
kernels (it names them itself and drops their scope) and the operations
under the ``moe_experts`` named scope (``ops/moe.sorted_experts``: the
weights' casts, the gated activation), forward and backward. Nothing for a
program with neither."""

from benchmark.harness.trace import in_scope

METRIC = {"layer": "kernels", "unit": "%", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}

GROUPED_MATMUL = "ragged-dot"


def read(observed):
    ms = observed.per_step_ms(lambda trace: trace.seconds_where(
        lambda e: in_scope(e["op_name"], "moe_experts")
        or e["name"].startswith(GROUPED_MATMUL)
    ))
    tokens = observed.counters.get("tokens_per_step")
    if not (ms and tokens):
        return None
    costs = observed.catalog.costs(observed.cell.config["costs"])
    macs = costs.expert_macs_per_token(observed.section("architecture"))
    flops = observed.catalog.costs("common").train_flops(macs)
    flops *= tokens / observed.device["count"]
    return 100.0 * flops / observed.peaks["bf16_flops_per_s"] / (ms / 1e3)
