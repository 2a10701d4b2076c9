"""A worker's time to make a batch: the totals of ``dtpu.loader.decode`` and
``dtpu.loader.assemble`` (``Loader._assemble`` on a worker thread) over the
batches assembled in the traced epoch, in ms. The batch size over this, times
``TRAIN.WORKERS``, is the rate the loader could feed. Nothing where the
program does not annotate its workers."""

from benchmark.harness import loop_capture

METRIC = {"layer": "loader", "unit": "ms", "source": "program_span",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    totals = observed.counters.get("program_spans") or {}
    decode, assemble = (totals.get(n) for n in (loop_capture.DECODE, loop_capture.ASSEMBLE))
    if not (decode and assemble and decode["count"]):
        return None
    return (decode["total_s"] + assemble["total_s"]) * 1e3 / decode["count"]
