"""Seconds this process spent in ``partition.lowering.lower`` during set-up
(the abstract initialiser traced for the state layout, the step builders):
the program's own registry counter ``setup.lower_s``. Nothing where the
counter is absent: a program without it, or a driver whose program runs in
another process."""

from distribuuuu_tpu.telemetry import get_registry

METRIC = {"layer": "entry", "unit": "s", "source": "program_counter",
          "moves": "setup_s"}


def read(observed):
    return get_registry().snapshot()["counters"].get("setup.lower_s")
