"""Bytes of one fused optimizer update: the least the algorithm moves.

One pass reads parameters, gradients and each moment once and writes
parameters and each moment once. SGD with momentum keeps one moment, AdamW
two. The arithmetic (a few operations per element) is far under the ridge, so
the kernel's roofline is the memory bandwidth.
"""

from __future__ import annotations


def one_pass_bytes(param_bytes: int, grad_bytes: int, moment_bytes: int) -> int:
    """``moment_bytes`` is the total over all moments."""
    return (param_bytes + grad_bytes + moment_bytes) + (param_bytes + moment_bytes)


def roofline_seconds(bytes_moved: int, peaks: dict) -> float:
    return bytes_moved / peaks["hbm_bytes_per_s"]
