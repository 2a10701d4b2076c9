"""The flash attention kernels' share of the MXU's peak at head dim 256, in
percent: the USEFUL operations of causal attention (``costs/glm_moe.py``
``attention_macs_per_token``: scores and values under the mask at score dim
256 and value dim 256, forward once and backward twice; the scores the
backward computes again, the masked half of the diagonal tiles and the
forward kernel's second run in the recomputation are not counted) at the
published bf16 peak, over the device time of the ``dtpu_flash_*`` Pallas
calls (``ops/flash_attention.py``). Nothing for a program whose trace holds
no such call, or whose costs know no latent attention."""

METRIC = {"layer": "kernels", "unit": "%", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}

KERNELS = "dtpu_flash_"


def read(observed):
    ms = observed.per_step_ms(lambda trace: trace.seconds_where(
        lambda e: e["name"].startswith(KERNELS)
    ))
    tokens = observed.counters.get("tokens_per_step")
    architecture = observed.section("architecture")
    if not (ms and tokens) or "qk_rope_head_dim" not in architecture:
        return None
    costs = observed.catalog.costs(observed.cell.config["costs"])
    flops = observed.catalog.costs("common").train_flops(
        costs.attention_macs_per_token(architecture))
    flops *= tokens / observed.device["count"]
    return 100.0 * flops / observed.peaks["bf16_flops_per_s"] / (ms / 1e3)
