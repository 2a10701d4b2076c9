"""Multiply-accumulates of one RegNetX/Y forward pass, from the
configuration's sizes (convolutions, squeeze-and-excitation projections and
the linear head; ``costs/common.py`` has the convention)."""

from __future__ import annotations

from benchmark.costs.common import conv_macs


def forward_macs_per_item(architecture: dict) -> int:
    a = architecture
    hw = a["image_size"] // 2  # 3x3/2 stem
    macs = conv_macs(hw, 3, 3, a["stem_width"])
    cin = a["stem_width"]
    for width, depth in zip(a["stage_widths"], a["stage_depths"]):
        groups = width // min(a["group_width"], width)
        for i in range(depth):
            in_hw = hw
            if i == 0:
                hw //= 2
                macs += conv_macs(hw, 1, cin, width)  # projection shortcut
            macs += conv_macs(in_hw, 1, cin, width)
            macs += conv_macs(hw, 3, width, width, groups)
            if a["se_ratio"]:
                macs += 2 * width * int(round(cin * a["se_ratio"]))
            macs += conv_macs(hw, 1, width, width)
            cin = width
    return macs + cin * a["num_classes"]
