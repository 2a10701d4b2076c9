"""The flash attention kernels' share of the MXU's peak, in percent: the
USEFUL operations of causal attention (the ``attention_macs_per_token`` of
the configuration's own ``costs`` module, ``costs/olmoe.py`` or
``costs/ouro.py``: scores and values under the mask, forward once and
backward twice; the scores the backward kernel computes again and the masked
half of the diagonal blocks are not counted) at the published bf16 peak,
over the device time of every ``dtpu_flash_*`` Pallas call
(``ops/flash_attention.py``: ``dtpu_flash_fwd`` and, since PR 31, the one
backward kernel ``dtpu_flash_bwd``; the two empty calls that keep the names
``dtpu_flash_dq`` and ``dtpu_flash_dkdv`` add 0.000 ms). Nothing for a
program whose trace holds no such call."""

METRIC = {"layer": "kernels", "unit": "%", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}

KERNELS = "dtpu_flash_"


def read(observed):
    ms = observed.per_step_ms(lambda trace: trace.seconds_where(
        lambda e: e["name"].startswith(KERNELS)
    ))
    tokens = observed.counters.get("tokens_per_step")
    if not (ms and tokens):
        return None
    costs = observed.catalog.costs(observed.cell.config["costs"])
    macs = costs.attention_macs_per_token(observed.section("architecture"))
    flops = observed.catalog.costs("common").train_flops(macs)
    flops *= tokens / observed.device["count"]
    return 100.0 * flops / observed.peaks["bf16_flops_per_s"] / (ms / 1e3)
