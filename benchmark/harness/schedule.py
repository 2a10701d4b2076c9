"""The open-loop arrival schedule: a pure function of the seed.

Independent users do not wait for each other, so requests are due at the
instants of a Poisson process whether or not earlier ones have finished.
Latency is taken from the instant a request was DUE, not from when the
generator got round to writing it: a stall then counts against every request
it delayed, and ``late_s`` says how far the generator itself ran behind.
"""

from __future__ import annotations

import numpy as np


def poisson_due_times(seed: int, rate_per_s: float, seconds: float) -> np.ndarray:
    """Due times in [0, seconds) of a Poisson process of ``rate_per_s``,
    drawn from ``seed`` alone."""
    if rate_per_s <= 0 or seconds <= 0:
        raise ValueError(f"rate {rate_per_s}/s over {seconds} s is no schedule")
    rng = np.random.default_rng(seed)
    # draw in blocks until the window is covered; the prefix of the stream
    # is the same whatever the block size
    n = max(16, int(rate_per_s * seconds * 1.2) + 16)
    gaps = rng.exponential(1.0 / rate_per_s, size=n)
    while gaps.sum() < seconds:
        gaps = np.concatenate([gaps, rng.exponential(1.0 / rate_per_s, size=n)])
    due = np.cumsum(gaps)
    return due[due < seconds]


def payload_order(seed: int, n_requests: int, n_payloads: int) -> np.ndarray:
    """Which payload each request carries: a seeded draw with replacement."""
    rng = np.random.default_rng([seed, 1])
    return rng.integers(0, n_payloads, size=n_requests)


def latency_s(due_s: float, done_s: float) -> float:
    """A request's latency: complete response minus the instant it was due."""
    return done_s - due_s


def late_s(due_s: float, sent_s: float) -> float:
    """How late after its due time a request was written to the socket."""
    return max(0.0, sent_s - due_s)
