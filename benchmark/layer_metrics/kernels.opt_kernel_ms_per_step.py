"""Device time per step of the fused update's Pallas calls alone: the
operations under ``opt_kernel`` (``ops/pallas/opt_update.py`` ``_call``), one
``dtpu_opt_update_<kind>`` custom call per parameter leaf, under ``shard_map``
too. The kernel's part of ``kernels.opt_update_ms_per_step``."""

METRIC = {"layer": "kernels", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.per_step_ms(lambda trace: trace.scope_s("opt_kernel")) or None
