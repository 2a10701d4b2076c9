"""RegNetX/Y (Radosavovic et al. 2020, arXiv:2003.13678): 3x3/2 stem, four
stages of bottleneck-ratio-1 blocks (1x1, grouped 3x3 with the stage's
stride on its first block, squeeze-and-excitation for Y, 1x1) with a
projection shortcut on each stage's first block, global average pool, linear
head. The grouped conv is one ``feature_group_count`` convolution here; the
program slices it per group. Plain float32."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import common


def _squeeze_excite(x, params):
    s = x.mean(axis=(1, 2), keepdims=True)
    for i, act in ((0, jax.nn.relu), (1, jax.nn.sigmoid)):
        p = params[f"Conv_{i}"]
        s = act(
            jnp.einsum("nhwc,cd->nhwd", s, p["kernel"][0, 0],
                       precision=common.HIGHEST) + p["bias"]
        )
    return x * s


def _block(x, params, stats, first: bool, group_width: int, **bn):
    offset = 1 if first else 0  # a stage's first block leads with its shortcut
    names = [f"ConvBN_{offset + i}" for i in range(3)]
    stride = 2 if first else 1
    out = common.conv_bn(x, params[names[0]], stats[names[0]], relu=True, **bn)
    width = out.shape[-1]
    out = common.conv_bn(
        out, params[names[1]], stats[names[1]], stride=stride,
        groups=width // min(group_width, width), relu=True, **bn,
    )
    if "SqueezeExcite_0" in params:
        out = _squeeze_excite(out, params["SqueezeExcite_0"])
    out = common.conv_bn(out, params[names[2]], stats[names[2]], **bn)
    if first:
        x = common.conv_bn(
            x, params["ConvBN_0"], stats["ConvBN_0"], stride=stride, **bn
        )
    return jax.nn.relu(out + x)


def logits(params, stats, images_u8, *, architecture: dict, train: bool,
           bn_group: int = 0):
    bn = {"train": train, "bn_group": bn_group}
    x = common.normalize(images_u8)
    x = common.conv_bn(
        x, params["ConvBN_0"], stats["ConvBN_0"], stride=2, relu=True, **bn
    )
    index = 0
    for depth in architecture["stage_depths"]:
        for i in range(depth):
            name = f"RegNetBlock_{index}"
            x = _block(
                x, params[name], stats[name], i == 0,
                architecture["group_width"], **bn,
            )
            index += 1
    return common.head(x, params)
