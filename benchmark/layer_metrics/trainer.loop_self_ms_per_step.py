"""SELF time of ``dtpu.trainer.epoch`` a traced step: the loop's own host
work that no child span holds (heartbeat, fault hooks, cost capture, meters,
log lines, the generator's turn). Nothing where the program has no ``epoch``
span."""

from benchmark.harness import loop_capture

METRIC = {"layer": "trainer", "unit": "ms", "source": "program_span",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return loop_capture.span_ms_per_step(
        observed.counters, loop_capture.EPOCH, "self_s"
    )
