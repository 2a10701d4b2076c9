"""Compilations (cache loads included) between the first instant of the
window and the end of the run, from jax's own compile events as the harness
counts them. Must be 0: a non-zero value sets ``correct`` false."""

METRIC = {"layer": "entry", "unit": "count", "source": "program_counter",
          "moves": "setup_s"}


def read(observed):
    value = observed.counters.get("compiles_in_window")
    return None if value is None else float(value)
