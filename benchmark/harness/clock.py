"""The window clock: one monotonic host clock for set-up and the window."""

from __future__ import annotations

import time

now = time.perf_counter


class Window:
    """A measured window of at least ``seconds``: opened at the first
    instant of measurement, closed after the fence on the last work."""

    def __init__(self, seconds: float):
        self.seconds = float(seconds)
        self.t_open = self.t_close = None

    def open(self) -> float:
        self.t_open = now()
        return self.t_open

    def expired(self) -> bool:
        return now() - self.t_open >= self.seconds

    def close(self) -> float:
        self.t_close = now()
        return self.elapsed

    @property
    def elapsed(self) -> float:
        return self.t_close - self.t_open
