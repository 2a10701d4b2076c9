"""ResNet (He et al. 2015, arXiv:1512.03385), torchvision layout: 7x7/2 stem,
3x3/2 max pool, four stages of basic or bottleneck blocks with the stride on
the 3x3 (v1.5), global average pool, linear head. Plain float32."""

from __future__ import annotations

import functools

import jax

from benchmark.reference import common


def _block(x, params, stats, kind: str, stride: int, **bn):
    convs = 3 if kind == "Bottleneck" else 2
    out = x
    for i in range(convs):
        name = f"ConvBN_{i}"
        # the stride sits on the 3x3: conv 1 of a bottleneck, conv 0 of a
        # basic block; the last conv of a block has no ReLU before the add
        strided = i == (1 if kind == "Bottleneck" else 0)
        out = common.conv_bn(
            out, params[name], stats[name], stride=stride if strided else 1,
            relu=i < convs - 1, **bn,
        )
    shortcut = f"ConvBN_{convs}"
    if shortcut in params:
        x = common.conv_bn(
            x, params[shortcut], stats[shortcut], stride=stride, **bn
        )
    return common._held(jax.nn.relu(out + x))


def logits(params, stats, images_u8, *, architecture: dict, train: bool,
           bn_group: int = 0, recompute: bool = False):
    """``recompute``: a block's inner activations are computed again in the
    backward pass (same values, a fraction of the memory), so that a float32
    gradient at a training cell's batch fits beside nothing else."""
    bn = {"train": train, "bn_group": bn_group}
    x = common.normalize(images_u8)
    x = common.conv_bn(
        x, params["ConvBN_0"], stats["ConvBN_0"], stride=2, relu=True, **bn
    )
    x = common.max_pool_3x3_s2(x)
    kind, index = architecture["block"], 0
    for stage, blocks in enumerate(architecture["stage_blocks"]):
        for i in range(blocks):
            name = f"{kind}_{index}"
            stride = 2 if stage > 0 and i == 0 else 1
            block = functools.partial(_block, kind=kind, stride=stride, **bn)
            if recompute:
                block = jax.checkpoint(block)
            x = block(x, params[name], stats[name])
            index += 1
    return common.head(x, params)
