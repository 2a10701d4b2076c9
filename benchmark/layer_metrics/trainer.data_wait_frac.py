"""The share of the measured window the loop's thread stood blocked on the
loader: the program's registry counter ``trainer.wait_s`` (``get1 - get0`` of
every batch pulled, ``trainer.train_epoch``) over the window, as the driver
differenced it, over the window's elapsed seconds. Nothing where the program
has no such counter."""

METRIC = {"layer": "trainer", "unit": "fraction", "source": "program_counter",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    waited = observed.counters.get("trainer.wait_s")
    window = observed.counters.get("window_s")
    return waited / window if waited is not None and window else None
