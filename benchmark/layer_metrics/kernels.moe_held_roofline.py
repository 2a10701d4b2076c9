"""The held experts' grouped matmuls' share of the MXU's peak, in percent:
the operations of the rows that really landed on this chip's experts
(``costs/glm_moe.py`` ``held_expert_macs_per_token`` at the share of the
(token, slot) choices the window's steps counted, ``moe_held_row_share``;
forward once and backward twice) at the published bf16 peak, over the device
time under the ``moe_experts`` named scope (``ops/moe.sorted_experts``: the
six ``dtpu_moe_gmm_*`` calls and the weights' casts, forward, the forward
the backward runs again, and backward). The recomputed forward is time and
not work, as everywhere. Nothing where the program counts no held share."""

from benchmark.harness.trace import in_scope

METRIC = {"layer": "kernels", "unit": "%", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    ms = observed.per_step_ms(lambda trace: trace.seconds_where(
        lambda e: in_scope(e["op_name"], "moe_experts")
    ))
    tokens = observed.counters.get("tokens_per_step")
    share = observed.counters.get("moe_held_row_share")
    if not (ms and tokens) or share is None:
        return None
    costs = observed.catalog.costs(observed.cell.config["costs"])
    macs = costs.held_expert_macs_per_token(observed.section("architecture"), share)
    flops = observed.catalog.costs("common").train_flops(macs)
    flops *= tokens / observed.device["count"]
    return 100.0 * flops / observed.peaks["bf16_flops_per_s"] / (ms / 1e3)
