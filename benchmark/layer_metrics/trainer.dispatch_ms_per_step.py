"""Host time a traced step inside ``dtpu.trainer.step``: the dispatch of the
compiled step through ``sequencer.dispatch``. Nothing without a traced epoch
or where the trace holds no such span."""

from benchmark.harness import loop_capture

METRIC = {"layer": "trainer", "unit": "ms", "source": "program_span",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return loop_capture.span_ms_per_step(observed.counters, loop_capture.STEP)
