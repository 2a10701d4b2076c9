"""Device-idle time of the traced window lying under
``dtpu.trainer.metrics_fetch`` and from there to the next
``dtpu.trainer.step`` (after the last fetch: to the window's end), over the
window: what the print's fence and the epoch's flush cost the device. Nothing
without a traced epoch."""

from benchmark.harness import loop_capture

METRIC = {"layer": "trainer", "unit": "fraction", "source": "program_span",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return loop_capture.idle_frac(observed.counters, "fence")
