"""Device-idle time of the traced window lying under ``dtpu.trainer.wait``
(the loop blocked on the loader) and not at a fence, over the window: the
loader's share of ``device.idle_frac``. Nothing without a traced epoch."""

from benchmark.harness import loop_capture

METRIC = {"layer": "trainer", "unit": "fraction", "source": "program_span",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return loop_capture.idle_frac(observed.counters, "wait")
