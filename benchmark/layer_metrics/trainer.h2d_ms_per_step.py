"""Host time a step to hand a batch to the device (``shard_batch`` inside
``device_prefetch``: the dispatch of the transfer, not the transfer): the
registry counter ``trainer.h2d_s`` over ``trainer.steps``, both over the
window. Nothing where the program has no such counters."""

from benchmark.harness import loop_capture

METRIC = {"layer": "trainer", "unit": "ms", "source": "program_counter",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return loop_capture.per_step_ms(observed.counters, "trainer.h2d_s")
