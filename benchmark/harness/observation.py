"""What a driver hands back, and what a per-layer metric's reader sees."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Observation:
    """One run of one cell as its driver observed it."""

    correct: bool
    attempted: int
    failed: int
    # end-to-end values by metric name, taken by the benchmark on the host
    # clock (set-up time is the harness's own and is added by it)
    end_to_end: dict
    # counts: the program's counters read before and after the window, and
    # the driver's own (steps, bytes from shapes, compiles in the window)
    counters: dict
    # platform, kind, count, memory_peak_bytes, memory_limit_bytes, as jax
    # reports them in the process that holds the chip
    device: dict
    trace_path: str | None = None  # the traced section's .xplane.pb
    # JSON {HLO instruction name: op_name} of the traced program, from its
    # compiled HLO text: this installation's trace carries no name scopes
    trace_op_names_path: str | None = None
    # what decided ``correct``, where the driver gives it: short plain name ->
    # {"value", "limit"}; within its limit means value <= limit
    compared: dict | None = None


@dataclasses.dataclass
class Observed:
    """Everything a per-layer reader may read. A reader that finds nothing
    to read returns None, and the metric is left out of the line."""

    cell: object  # discovery.Cell
    section: object  # callable: the configuration's section, as it was run
    traffic: dict
    end_to_end: dict
    counters: dict
    device: dict
    peaks: dict
    catalog: object  # discovery.Catalog: costs by name
    trace: object | None  # trace.Reduction of the traced section, or None

    def per_step_ms(self, seconds_of) -> float | None:
        """``seconds_of(trace)`` over the traced section, in ms per step of
        the driver's ``trace_steps``; None without a trace or a step count."""
        steps = self.counters.get("trace_steps")
        if not (self.trace and steps):
            return None
        return seconds_of(self.trace) * 1e3 / steps
