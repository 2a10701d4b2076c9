"""Device time per step of the pad/reshape of every leaf to ``[rows, 128]``
and back around the fused update's Pallas calls: the operations under
``opt_tile`` (``ops/pallas/opt_update.py`` ``_tiled``, ``_untiled``). The part
of ``kernels.opt_update_ms_per_step`` that is not the kernel."""

METRIC = {"layer": "kernels", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.per_step_ms(lambda trace: trace.scope_s("opt_tile")) or None
