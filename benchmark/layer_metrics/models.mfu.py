"""Model FLOP/s utilization: the operations forward and backward need per item
(from the configuration's shapes, ``costs/``; no recomputation) times items
per second per chip, over the chip's published bf16 peak. An end-to-end
utilization: it says nothing about a kernel's roofline or about idle time."""

METRIC = {"layer": "models", "unit": "fraction", "source": "host_clock",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    rate = observed.end_to_end.get("train_items_per_s_per_chip")
    if rate is None:
        return None
    macs = observed.catalog.costs(observed.cell.config["costs"]).forward_macs_per_item(
        observed.section("architecture")
    )
    flops = observed.catalog.costs("common").train_flops(macs)
    return flops * rate / observed.peaks["bf16_flops_per_s"]
