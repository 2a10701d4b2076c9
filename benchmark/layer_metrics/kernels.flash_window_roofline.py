"""The flash attention kernels' share of the MXU's peak in the
sliding-window layers, in percent: the USEFUL operations of windowed
attention (``window_attention_macs_per_token`` of the configuration's
``costs`` module: scores and values over the (query, key) pairs the window
KEEPS at the published head dim, forward once and backward twice; the masked
part of the two tiles a row of blocks that the diagonal and the window's edge
cross, the scores the backward computes again and the forward kernel's
second run in a recomputation are not counted) at the published bf16 peak,
over the device time of the ``dtpu_flash_*`` Pallas calls whose ``op_name``
lies under ``attn_window``. Nothing for a program without the scope, a trace
without such a call, or a configuration whose costs count no window."""

from benchmark.harness.trace import in_scope

METRIC = {"layer": "kernels", "unit": "%", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}

KERNELS = "dtpu_flash_"


def read(observed):
    ms = observed.per_step_ms(lambda trace: trace.seconds_where(
        lambda e: e["name"].startswith(KERNELS)
        and in_scope(e["op_name"], "attn_window")
    ))
    tokens = observed.counters.get("tokens_per_step")
    costs = observed.catalog.costs(observed.cell.config["costs"])
    count = getattr(costs, "window_attention_macs_per_token", None)
    if not (ms and tokens and count):
        return None
    flops = observed.catalog.costs("common").train_flops(
        count(observed.section("architecture")))
    flops *= tokens / observed.device["count"]
    return 100.0 * flops / observed.peaks["bf16_flops_per_s"] / (ms / 1e3)
