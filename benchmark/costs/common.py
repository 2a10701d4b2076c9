"""What every operation count shares: the convention, in one place.

2 operations per multiply-accumulate of every convolution, projection and
linear head (the usual convention for model FLOP/s utilization); batch norm,
activations, pooling and the loss are not counted. A training step needs the
forward pass once and twice its operations for the backward pass (gradients
with respect to inputs and to weights); recomputation is never counted.
"""

from __future__ import annotations

OPS_PER_MAC = 2
TRAIN_OVER_FORWARD = 3


def conv_macs(out_hw: int, kernel: int, cin: int, cout: int, groups: int = 1) -> int:
    return out_hw * out_hw * kernel * kernel * (cin // groups) * cout


def train_flops(forward_macs: int) -> float:
    return float(TRAIN_OVER_FORWARD * OPS_PER_MAC * forward_macs)
