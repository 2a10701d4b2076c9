"""A step's share of the time the loop is held at the print's fence
(``flush_pending``: the ``float()`` reads of the pending steps' metrics, the
loop's only wait for the device): the registry counter ``trainer.fetch_s``
over ``trainer.steps``, both over the window. Nothing where the program has no
such counters."""

from benchmark.harness import loop_capture

METRIC = {"layer": "trainer", "unit": "ms", "source": "program_counter",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return loop_capture.per_step_ms(observed.counters, "trainer.fetch_s")
