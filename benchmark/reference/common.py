"""The plain layers the image references share: every one is the textbook
formula, in float32, at matmul precision ``highest`` (on a TPU a float32
convolution otherwise runs in bfloat16 passes)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)
BN_EPS = 1e-5


def normalize(images_u8):
    """uint8 NHWC pixels -> ImageNet-normalized float32."""
    x = images_u8.astype(jnp.float32) / 255.0
    return (x - jnp.asarray(IMAGENET_MEAN, jnp.float32)) / jnp.asarray(
        IMAGENET_STD, jnp.float32
    )


def conv(x, kernel, stride: int = 1, groups: int = 1):
    """Bias-free convolution, torch-style symmetric padding k // 2."""
    kh, kw = kernel.shape[:2]
    return jax.lax.conv_general_dilated(
        x, kernel.astype(jnp.float32), (stride, stride),
        [(kh // 2, kh // 2), (kw // 2, kw // 2)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=HIGHEST,
    )


def batch_norm(x, affine, stats, *, train: bool, bn_group: int):
    """BatchNorm2d. In training each group of ``bn_group`` consecutive samples
    (the per-chip batch; 0 = the whole batch) is normalized by its own mean
    and biased variance, two passes; in inference by the running statistics."""
    scale, bias = affine["scale"], affine["bias"]
    if not train:
        inv = jax.lax.rsqrt(stats["var"] + BN_EPS) * scale
        return (x - stats["mean"]) * inv + bias
    n = x.shape[0]
    g = bn_group if 0 < bn_group < n else n
    if n % g:
        raise ValueError(f"BN group {g} does not divide batch {n}")
    xg = x.reshape((n // g, g) + x.shape[1:])
    mean = xg.mean(axis=(1, 2, 3), keepdims=True)
    var = jnp.square(xg - mean).mean(axis=(1, 2, 3), keepdims=True)
    y = (xg - mean) * jax.lax.rsqrt(var + BN_EPS) * scale + bias
    return y.reshape(x.shape)


def conv_bn(x, params, stats, *, stride=1, groups=1, relu=False,
            train: bool, bn_group: int):
    """The zoo's unit as checkpoints name it: ``Conv_0/kernel`` then
    ``BatchNorm_0/BatchNorm_0/{scale,bias}`` (+ ``{mean,var}`` statistics)."""
    x = conv(x, params["Conv_0"]["kernel"], stride, groups)
    x = batch_norm(
        x, params["BatchNorm_0"]["BatchNorm_0"],
        stats["BatchNorm_0"]["BatchNorm_0"], train=train, bn_group=bn_group,
    )
    return jax.nn.relu(x) if relu else x


def max_pool_3x3_s2(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, 1), (1, 1), (0, 0)),
    )


def head(x, params):
    """Global average pool, then the linear classifier."""
    x = x.mean(axis=(1, 2))
    dense = params["Dense_0"]["Dense_0"]
    return jnp.dot(x, dense["kernel"], precision=HIGHEST) + dense["bias"]


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0].mean()
