"""The short convolution's gates and filter as a share of the HBM's peak
bandwidth, in percent: the bytes a PERFECT fusion of gate -> filter -> gate
must move (``short_conv_gate_bytes_per_token`` of the configuration's
``costs`` module, at the itemsize of ``train_job.dtype``: forward 3 d in and
d out, backward 4 d in and 3 d out, a ``conv`` layer; a recomputed forward
is time and not bytes) over the device time under the ``short_conv_gate``
named scope (``ops/short_conv.py``: the XLA fusions, or a kernel, whichever
runs there). What the program reads or writes beyond that (the gates read
twice, float32 copies between fusions) lowers the share. Nothing for a
program without the scope or a configuration whose costs count no such
bytes."""

METRIC = {"layer": "kernels", "unit": "%", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def read(observed):
    ms = observed.per_step_ms(lambda trace: trace.scope_s("short_conv_gate"))
    tokens = observed.counters.get("tokens_per_step")
    costs = observed.catalog.costs(observed.cell.config["costs"])
    count = getattr(costs, "short_conv_gate_bytes_per_token", None)
    if not (ms and tokens and count):
        return None
    itemsize = ITEMSIZE[observed.section("train_job")["dtype"]]
    moved = count(observed.section("architecture"), itemsize)
    moved *= tokens / observed.device["count"]
    return 100.0 * moved / observed.peaks["hbm_bytes_per_s"] / (ms / 1e3)
