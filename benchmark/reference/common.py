"""The plain layers the image references share: every one is the textbook
formula, in float32, at matmul precision ``highest`` (on a TPU a float32
convolution otherwise runs in bfloat16 passes)."""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)
BN_EPS = 1e-5
BN_MOMENTUM = 0.1  # torch's: running <- 0.9 running + 0.1 batch
_HOLD_IN = [None]  # the control's type; None = plain float32
_MOVED = [None]  # {id of a layer's statistics: what a training step leaves}


@contextlib.contextmanager
def holding_operands_in(dtype):
    """The CONTROL of a comparison, never the reference: a reference TRACED
    inside this block holds both operands of every convolution and of the head
    in ``dtype`` and accumulates in float32, the step down in precision that
    would tempt a later PR (``benchmark/reference/sgd_steps.py``)."""
    _HOLD_IN[0] = dtype
    try:
        yield
    finally:
        _HOLD_IN[0] = None


@contextlib.contextmanager
def moved_statistics(stats):
    """For a reference TRACED inside this block in training mode (and not
    under ``jax.checkpoint`` or a gradient): yields a function that returns
    ``stats`` as one training step leaves them, every BatchNorm's running mean
    and (unbiased) variance moved by ``BN_MOMENTUM`` towards its batch's,
    averaged over the ghost groups. Forward only, so not a number that the
    backward pass of a deep BatchNorm net scatters."""
    _MOVED[0] = found = {}

    def rebuilt(tree=stats):
        if "mean" in tree:
            return found[id(tree)]
        return {k: rebuilt(v) for k, v in tree.items()}

    try:
        yield rebuilt
    finally:
        _MOVED[0] = None


def _held(x):
    """``x`` as the control's type holds it; the gradient goes straight
    through. A type of small range (fp8, float16) gets the whole tensor scaled
    to it, as a careful low-precision path would do."""
    dtype = _HOLD_IN[0]
    if dtype is None:
        return x
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top if top < 1e6 else 1.0
    held = (x / scale).astype(dtype).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(held - x)


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
        _held(x), _held(kernel.astype(jnp.float32)), (stride, stride),
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
    if _MOVED[0] is not None:
        count = g * x.shape[1] * x.shape[2]
        _MOVED[0][id(stats)] = {
            "mean": (1 - BN_MOMENTUM) * stats["mean"]
            + BN_MOMENTUM * mean.mean(axis=0).reshape(-1),
            "var": (1 - BN_MOMENTUM) * stats["var"]
            + BN_MOMENTUM * count / (count - 1) * var.mean(axis=0).reshape(-1),
        }
    y = (xg - mean) * jax.lax.rsqrt(var + BN_EPS) * scale + bias
    return y.reshape(x.shape)


def conv_bn(x, params, stats, *, stride=1, groups=1, relu=False,
            train: bool, bn_group: int):
    """The zoo's unit as checkpoints name it: ``Conv_0/kernel`` then
    ``BatchNorm_0/BatchNorm_0/{scale,bias}`` (+ ``{mean,var}`` statistics)."""
    x = _held(conv(x, params["Conv_0"]["kernel"], stride, groups))
    x = _held(batch_norm(
        x, params["BatchNorm_0"]["BatchNorm_0"],
        stats["BatchNorm_0"]["BatchNorm_0"], train=train, bn_group=bn_group,
    ))
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
    return jnp.dot(
        _held(x), _held(dense["kernel"]), precision=HIGHEST
    ) + dense["bias"]


def cross_entropy(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0].mean()
