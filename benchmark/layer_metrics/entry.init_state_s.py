"""Seconds this process spent in ``trainer.create_train_state`` during
set-up (the initialiser traced again, compiled or loaded, and dispatched):
the program's own registry counter ``setup.init_state_s``. Nothing where the
counter is absent: a program without it, or a driver whose program runs in
another process."""

from distribuuuu_tpu.telemetry import get_registry

METRIC = {"layer": "entry", "unit": "s", "source": "program_counter",
          "moves": "setup_s"}


def read(observed):
    return get_registry().snapshot()["counters"].get("setup.init_state_s")
