"""Multiply-accumulates of one ResNet forward pass, from the configuration's
sizes (convolutions and the linear head; ``costs/common.py`` has the
convention that turns them into a training step's operations)."""

from __future__ import annotations

from benchmark.costs.common import conv_macs


def forward_macs_per_item(architecture: dict) -> int:
    a = architecture
    hw = a["image_size"] // 2  # 7x7/2 stem
    macs = conv_macs(hw, a["stem_kernel"], 3, a["stem_width"])
    hw //= 2  # 3x3/2 max pool
    cin = a["stem_width"]
    bottleneck = a["block"] == "Bottleneck"
    for stage, (width, blocks) in enumerate(
        zip(a["stage_widths"], a["stage_blocks"])
    ):
        cout = width * a["expansion"]
        for i in range(blocks):
            in_hw = hw
            if stage > 0 and i == 0:
                hw //= 2
            if bottleneck:  # 1x1, 3x3 (carries the stride), 1x1
                macs += conv_macs(in_hw, 1, cin, width)
                macs += conv_macs(hw, 3, width, width)
                macs += conv_macs(hw, 1, width, cout)
            else:  # 3x3 (carries the stride), 3x3
                macs += conv_macs(hw, 3, cin, width)
                macs += conv_macs(hw, 3, width, cout)
            if i == 0 and (in_hw != hw or cin != cout):
                macs += conv_macs(hw, 1, cin, cout)  # projection shortcut
            cin = cout
    return macs + cin * a["num_classes"]
