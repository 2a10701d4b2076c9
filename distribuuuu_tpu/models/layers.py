"""Shared layers and initializers for the model zoo.

TPU-first conventions used throughout the zoo:
  - NHWC layout (XLA:TPU's native conv layout; torch reference is NCHW).
  - Params in fp32, compute in ``cfg.DEVICE.COMPUTE_DTYPE`` (bfloat16 by
    default) so matmuls/convs hit the MXU at full rate.
  - BatchNorm supports two statistic regimes (``MODEL.SYNCBN``):
    ``group_size=0`` computes stats over the *global* batch under jit —
    with the batch sharded over the ``data`` mesh axis XLA inserts the
    cross-replica reductions automatically, i.e. SyncBatchNorm
    (ref: trainer.py:131) by construction. ``group_size=g`` computes
    "ghost" stats over independent g-sample groups, reproducing the
    reference's default non-synced regime (every published baseline used
    ``SYNCBN False`` ⇒ stats over one GPU's 32–64 samples,
    ref: config/resnet50.yaml). When g equals the per-chip batch the group
    dim lands on shard boundaries and ghost BN costs *zero* communication —
    cheaper than the global path, not just different.
"""

from __future__ import annotations

import os
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from einops import rearrange
from jax.nn.initializers import variance_scaling

from distribuuuu_tpu.parallel import tp

# torch nn.Conv2d's companion init is kaiming; the reference ResNet explicitly
# uses kaiming_normal(fan_out, relu) (ref: resnet.py:213-218).
kaiming_normal_fan_out = variance_scaling(2.0, "fan_out", "normal")
# torch nn.Linear default: kaiming_uniform(a=sqrt(5)) == U(±1/sqrt(fan_in)).
torch_linear_init = variance_scaling(1.0 / 3.0, "fan_in", "uniform")

# Partitioned variants: kernels carry ``model``-axis metadata so the trainer
# can lay params out for tensor parallelism (no-op at MESH.MODEL=1).
conv_kernel_init = tp.conv_init(kaiming_normal_fan_out)
conv_kernel_init_default = tp.conv_init(nn.initializers.lecun_normal())
dense_kernel_init = tp.column_init(torch_linear_init)


def resolve_dtype(name: str):
    # float64 needs jax_enable_x64 (CPU-mesh equivalence tests — the fp64
    # trajectory suite; TPUs have no f64 units)
    return {
        "bfloat16": jnp.bfloat16,
        "float32": jnp.float32,
        "float16": jnp.float16,
        "float64": jnp.float64,
    }[name]


class StemConv7x7(nn.Module):
    """The zoo's 7×7/s2 stem conv with a space-to-depth compute path
    (the MLPerf ResNet-on-TPU reformulation).

    The parameter is ALWAYS the canonical ``(7, 7, in, features)`` kernel —
    same tree path, shape, init, and gradient as the plain ``nn.Conv`` stem —
    so checkpoints, param counts (oracle: README.md:213) and torch-weight
    ingestion are mode-independent. The *compute* views the input as 2×2
    blocks folded into channels ``(H/2, W/2, 4·in)`` and folds the kernel the
    same way on device (zero-pad 7×7 → 8×8 at the top-left so the window
    origin aligns to a block boundary, then reshape to ``4×4×(4·in)``, ~12 KB
    — free). Exact reformulation up to float summation order. Why it wins on
    TPU: a 7×7/s2 conv over 3 channels leaves the MXU's 8-deep input lanes
    mostly padding; 4×4/s1 over 12 channels tiles cleanly and reads ~4× less
    HBM per output tile. Inputs with odd H/W fall back to the plain conv.
    """

    features: int
    s2d: bool = True
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        cin = x.shape[-1]
        kernel = self.param(
            "kernel", conv_kernel_init, (7, 7, cin, self.features), jnp.float32
        ).astype(self.dtype)
        x = x.astype(self.dtype)  # lax.conv requires matching dtypes
        dn = ("NHWC", "HWIO", "NHWC")
        if not self.s2d or x.shape[1] % 2 or x.shape[2] % 2:
            return jax.lax.conv_general_dilated(
                x, kernel, (2, 2), [(3, 3), (3, 3)], dimension_numbers=dn
            )
        # input: fold 2×2 spatial blocks into channels
        y = rearrange(x, "b (h bh) (w bw) c -> b h w (bh bw c)", bh=2, bw=2)
        # kernel: zero row/col at the top-left moves the window origin from
        # -3 to -4 (a block boundary); fold blocks with the SAME (bh bw c)
        # order as the input
        k8 = jnp.pad(kernel, ((1, 0), (1, 0), (0, 0), (0, 0)))
        k4 = rearrange(k8, "(kh bh) (kw bw) c f -> kh kw (bh bw c) f", bh=2, bw=2)
        # original windows start at row 2p-4, i.e. block p-2 … p+1 → pad (2,1)
        return jax.lax.conv_general_dilated(
            y, k4, (1, 1), [(2, 1), (2, 1)], dimension_numbers=dn
        )


class UnrolledGroupConv(nn.Module):
    """Grouped conv computed as per-group slices of ONE canonical kernel.

    XLA:TPU lowers ``feature_group_count`` convs through physical
    channel-retiling reshapes+copies — ~30% of a RegNetY-16GF train step
    (PERF.md). Slicing into per-group convs on the SAME ``(kh, kw, in/G,
    out)`` parameter avoids the retiling: measured 4.35→2.93 ms fwd+bwd on
    the [64,14,14,1232]/G=11 stage-3 block, and identical math up to bf16
    summation order. Only profitable when each group is MXU-wide — ConvBN
    auto-selects this path at per-group width ≥ 64 (RegNets qualify,
    ResNeXt's 4/8-wide groups do not).
    """

    features: int
    kernel_size: tuple[int, int]
    strides: Any
    padding: Any
    groups: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        kh, kw = self.kernel_size
        # the loud divisibility guard nn.Conv would otherwise provide
        # (ValueError, not assert: must survive python -O)
        if x.shape[-1] % self.groups or self.features % self.groups:
            raise ValueError(
                f"channels in={x.shape[-1]} out={self.features} must divide "
                f"groups={self.groups}"
            )
        cg = x.shape[-1] // self.groups
        fg = self.features // self.groups
        kernel = self.param(
            "kernel", conv_kernel_init, (kh, kw, cg, self.features), jnp.float32
        ).astype(self.dtype)
        x = x.astype(self.dtype)  # lax.conv requires matching dtypes
        s = self.strides
        strides = s if isinstance(s, (tuple, list)) else (s, s)
        outs = [
            jax.lax.conv_general_dilated(
                x[..., g * cg : (g + 1) * cg],
                kernel[..., g * fg : (g + 1) * fg],
                strides,
                self.padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
            for g in range(self.groups)
        ]
        return jnp.concatenate(outs, axis=-1)


class PointwiseKernel(nn.Module):
    """Param-holder for the fused conv epilogue (ops/pallas/): declares
    exactly nn.Conv's ``kernel`` param — (1, 1, in, features), fp32,
    conv init — and returns it, so the fused compute path shares the
    canonical parameter (the StemConv7x7/UnrolledGroupConv discipline:
    checkpoints are compute-path-independent). Instantiate under the
    same child name the nn.Conv would have used."""

    features: int

    @nn.compact
    def __call__(self, in_channels: int):
        return self.param(
            "kernel", conv_kernel_init,
            (1, 1, in_channels, self.features), jnp.float32,
        )


def fused_pointwise_path(kernel_size, strides, padding, groups, act,
                         train: bool, use_bn: bool = True) -> bool:
    """Whether THIS conv+BN+act site runs the fused Pallas epilogue
    (KERNELS.CONV_EPILOGUE): consult the kernel tier's one policy point
    with the site's qualification + disqualifying reason. Emits the
    kernel.select / kernel.fallback telemetry as a side effect; training
    forwards never consult (BN batch stats need the raw conv output, and
    a forced knob should not warn once per train step site)."""
    if train or not use_bn:
        return False
    from distribuuuu_tpu.ops import pallas as kernel_tier
    from distribuuuu_tpu.ops.pallas import conv_epilogue

    ok, reason = conv_epilogue.qualifies(
        kernel_size, strides, padding, groups, act, train
    )
    return kernel_tier.select(
        "conv_epilogue", supported=ok, reason=reason
    ) == "pallas"


class ConvBN(nn.Module):
    """Conv2D (no bias) + BatchNorm, the zoo's basic unit.

    ``s2d_stem=True`` (7×7/s2 stems only) swaps the conv computation for the
    space-to-depth path of :class:`StemConv7x7`; wide grouped convs route
    through :class:`UnrolledGroupConv`; on the eval path, pointwise convs
    with a kernel-known activation route through the fused Pallas
    conv+BN+act epilogue when ``KERNELS.CONV_EPILOGUE`` selects it
    (ops/pallas/conv_epilogue.py — one HBM pass, the BN affine and the
    activation ride the matmul tile). In every case the explicit submodule
    name keeps the param at the same ``ConvBN_*/Conv_0/kernel`` path with
    the same shape, so checkpoints are compute-path-independent.
    """

    features: int
    kernel_size: tuple[int, int] = (3, 3)
    strides: int | tuple[int, int] = 1
    padding: Any = None
    groups: int = 1
    dtype: Any = jnp.bfloat16
    use_bn: bool = True
    bn_scale_init: Callable = nn.initializers.ones
    bn_group: int = 0  # ghost-BN group size; 0 = global-batch stats
    act: Callable | None = None
    s2d_stem: bool = False

    def _group_conv_unrolled(self, in_channels: int) -> bool:
        """Grouped-conv compute path at trace time: unroll when the
        per-group width is MXU-wide (≥64); narrower groups keep
        ``nn.Conv``'s ``feature_group_count``. Params and checkpoints are
        identical either way (same canonical kernel)."""
        return in_channels // self.groups >= 64

    @nn.compact
    def __call__(self, x, train: bool = False):
        k = self.kernel_size
        pad = self.padding
        if pad is None:
            # torch-style symmetric "same" padding for odd kernels
            pad = [(k[0] // 2, k[0] // 2), (k[1] // 2, k[1] // 2)]
        if fused_pointwise_path(k, self.strides, pad, self.groups, self.act,
                                train, self.use_bn):
            from distribuuuu_tpu.ops import pallas as kernel_tier
            from distribuuuu_tpu.ops.pallas import conv_epilogue

            kernel = PointwiseKernel(self.features, name="Conv_0")(
                x.shape[-1]
            )
            a, c = BatchNorm(
                dtype=self.dtype,
                scale_init=self.bn_scale_init,
                group_size=self.bn_group,
            )(jnp.zeros((1, self.features), self.dtype), fold=True)
            return conv_epilogue.conv1x1_bn_act(
                x.astype(self.dtype), kernel.astype(self.dtype), a, c,
                conv_epilogue.act_code(self.act),
                interpret=kernel_tier.interpret_mode(),
            )
        if self.s2d_stem:
            assert (
                tuple(k) == (7, 7)
                and self.strides in (2, (2, 2))
                and self.groups == 1
                and list(map(tuple, pad)) == [(3, 3), (3, 3)]
            ), "s2d_stem is specifically the 7x7/s2/pad-3 ungrouped stem"
            x = StemConv7x7(self.features, dtype=self.dtype, name="Conv_0")(x)
        elif self.groups > 1 and self._group_conv_unrolled(x.shape[-1]):
            x = UnrolledGroupConv(
                self.features, tuple(k), self.strides, pad, self.groups,
                dtype=self.dtype, name="Conv_0",
            )(x)
        else:
            x = nn.Conv(
                self.features,
                k,
                strides=self.strides,
                padding=pad,
                feature_group_count=self.groups,
                use_bias=False,
                dtype=self.dtype,
                param_dtype=jnp.float32,
                kernel_init=conv_kernel_init,
            )(x)
        if self.use_bn:
            x = BatchNorm(
                dtype=self.dtype,
                scale_init=self.bn_scale_init,
                group_size=self.bn_group,
            )(x, train=train)
        if self.act is not None:
            x = self.act(x)
        return x


class _BNCore(nn.Module):
    """First-party BatchNorm core with ghost (grouped) batch statistics.

    ``group_size == 0`` → stats over the whole (global) batch: under jit
    with the batch sharded on ``data`` this IS SyncBatchNorm (ref:
    trainer.py:131). ``group_size == g`` → the batch is viewed as
    ``(n//g, g, ...)`` and each g-sample group is normalized by its own
    statistics — the reference's non-synced regime (``SYNCBN False``, BN
    over one GPU's samples) reproduced exactly, device-count-independently.
    When g divides the per-shard batch, the group dim lands on shard
    boundaries and the grouped stats need no cross-device reduction at all.

    torch-matching numerics (ref BN is torch nn.BatchNorm2d):
    normalization uses biased variance; the running-variance update uses
    the *unbiased* estimate (×count/(count-1)) — flax's nn.BatchNorm
    deviates from torch on the latter, which is one reason this core is
    first-party. Stats/params are fp32 regardless of compute dtype.
    """

    group_size: int = 0
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16
    scale_init: Callable = nn.initializers.ones

    @nn.compact
    def __call__(self, x, train: bool = False, fold: bool = False):
        feat = x.shape[-1]
        scale = self.param("scale", self.scale_init, (feat,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (feat,), jnp.float32)
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((feat,), jnp.float32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((feat,), jnp.float32)
        )
        if fold:
            # the fused conv-epilogue path (ops/pallas/conv_epilogue.py):
            # return the eval normalization as per-channel affine
            # constants (a, c) with y = x·a + c ≡ (x − mean)·inv + bias —
            # ``x`` only sizes the channel dim. Same params/variables
            # declared in the same order, so the tree is fold-independent.
            if train:
                raise ValueError(
                    "BatchNorm fold=True is the eval fusion path; batch "
                    "statistics cannot be folded into an affine"
                )
            inv = jax.lax.rsqrt(ra_var.value + self.epsilon) * scale
            return inv, bias - ra_mean.value * inv
        # stats compute in fp32 — promoted to fp64 only when the input is
        # f64 (the x64 CPU equivalence tests, where reduction-order
        # rounding must vanish); bf16/f32 production inputs stay fp32
        stats_dtype = jnp.promote_types(jnp.float32, x.dtype)
        if not train:
            inv = jax.lax.rsqrt(ra_var.value + self.epsilon) * scale
            y = (x.astype(stats_dtype) - ra_mean.value) * inv + bias
            return y.astype(self.dtype)

        n = x.shape[0]
        gs = self.group_size
        spatial = 1
        for d in x.shape[1:-1]:
            spatial *= d
        # One-pass shifted variance. The batch stats come from a SINGLE
        # read of the activations: d = x − m̂ with the shift m̂ a
        # per-channel constant *independent of this batch* (the running
        # mean), then mean = E[d] + m̂ and var = E[d²] − E[d]² — an exact
        # identity for any m̂. Because m̂ does not depend on x, XLA folds
        # both sums into the producing conv's epilogue; the centered
        # two-pass form (r3) needed the mean before the squared-deviation
        # pass, forcing an extra full HBM read of every BN input on a step
        # that is bandwidth-bound — measured at 7.5% of flagship
        # throughput (VERDICT r3, paired A/B 2570 vs 2390 img/s).
        # Cancellation now scales with |batch mean − m̂| ≈ 0 in steady
        # state rather than |batch mean| (the E[x²]−E[x]² failure mode,
        # ADVICE r2). Regime bound: a *cold-start* batch with
        # |mean| ≫ spread (m̂ still at its init of 0) rounds like the
        # uncentered form until the running mean tracks the scale; the
        # clamp keeps var ≥ 0 (finite rsqrt) in that corner. Post-conv
        # activations under fp32 accumulation do not occupy that regime.
        xf = x.astype(stats_dtype)

        def moments(v, axes):
            """(mean, biased var) over ``axes``."""
            shift = jax.lax.stop_gradient(ra_mean.value)
            d = v - shift
            s1 = d.mean(axes)  # E[d] — both sums in one pass over v
            s2 = jnp.square(d).mean(axes)  # E[d²]
            return s1 + shift, jnp.maximum(s2 - jnp.square(s1), 0.0)

        # n <= gs degenerates to one group = the whole batch (torch
        # semantics: a device with fewer samples normalizes over what it
        # has); only the indivisible case is an error.
        if gs > 0 and n > gs:
            if n % gs:
                raise ValueError(
                    f"ghost BN group_size={gs} does not divide batch {n}; "
                    "set MODEL.BN_GROUP to a divisor of the (micro-)batch"
                )
            g = n // gs
            xg = xf.reshape((g, gs) + x.shape[1:])
            axes = tuple(range(1, xg.ndim - 1))
            bshape = (g,) + (1,) * (xg.ndim - 2) + (feat,)
            gmean, gvar = moments(xg, axes)  # (g, C)
            inv = jax.lax.rsqrt(gvar + self.epsilon).reshape(bshape) * scale
            y = ((xg - gmean.reshape(bshape)) * inv + bias).reshape(x.shape)
            count = gs * spatial
            mean_upd = gmean.mean(0)
            # running stats average the per-group (unbiased) estimates —
            # strictly more informative than torch DDP's rank-0-only stats
            var_upd = gvar.mean(0) * count / max(count - 1, 1)
        else:
            axes = tuple(range(x.ndim - 1))
            mean, var = moments(xf, axes)
            inv = jax.lax.rsqrt(var + self.epsilon) * scale
            y = (xf - mean) * inv + bias
            count = n * spatial
            mean_upd, var_upd = mean, var * count / max(count - 1, 1)
        if not self.is_initializing():
            # DISTRIBUUUU_BN_MOMENTUM (trace-time) overrides EVERY BN
            # layer's running-stats decay — a bench/experiment knob (the
            # r5 eval-wobble investigation, PERF.md); unset ⇒ each
            # module's own momentum (torch parity)
            m = float(os.environ.get("DISTRIBUUUU_BN_MOMENTUM",
                                     self.momentum))
            # cast back to the stored (fp32) dtype: under promoted-f64
            # stats the update expression is f64 and must not change the
            # batch_stats tree's dtype between steps
            ra_mean.value = (
                m * ra_mean.value + (1.0 - m) * mean_upd
            ).astype(ra_mean.value.dtype)
            ra_var.value = (
                m * ra_var.value + (1.0 - m) * var_upd
            ).astype(ra_var.value.dtype)
        return y.astype(self.dtype)


class BatchNorm(nn.Module):
    """BatchNorm with torch-matching hyperparams (torch momentum 0.1 == flax
    momentum 0.9, eps 1e-5 by default; EfficientNet overrides). ``train``
    selects batch stats vs running averages; ``group_size`` selects ghost
    (per-group) vs global batch statistics — see :class:`_BNCore`.

    The core sits under the fixed child name ``BatchNorm_0`` so variable
    paths (``.../BatchNorm_0/{scale,bias}`` + batch_stats ``{mean,var}``)
    are stable across core implementations (checkpoints and torch
    ingestion address them)."""

    dtype: Any = jnp.bfloat16
    scale_init: Callable = nn.initializers.ones
    momentum: float = 0.9
    epsilon: float = 1e-5
    group_size: int = 0

    @nn.compact
    def __call__(self, x, train: bool = False, fold: bool = False):
        return _BNCore(
            group_size=self.group_size,
            momentum=self.momentum,
            epsilon=self.epsilon,
            dtype=self.dtype,
            scale_init=self.scale_init,
            name="BatchNorm_0",
        )(x, train=train, fold=fold)


class SqueezeExcite(nn.Module):
    """Squeeze-and-excitation gate: global mean → 1x1 reduce → act →
    1x1 expand → sigmoid. Reduction width is caller-chosen (RegNet-Y uses
    ratio×block-input, EfficientNet in_ch//4)."""

    se_width: int
    act: Callable = nn.relu
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        # jnp.mean of a bf16 tensor accumulates in f32 and casts back
        # (jax's half-type reduction upcast) — deliberate numerics, so
        # the scope declares it to the dtype lint (*_fp32 convention)
        with jax.named_scope("se_squeeze_fp32"):
            s = jnp.mean(x, axis=(1, 2), keepdims=True)
        s = nn.Conv(self.se_width, (1, 1), dtype=self.dtype,
                    param_dtype=jnp.float32)(s)
        s = self.act(s)
        s = nn.Conv(x.shape[-1], (1, 1), dtype=self.dtype,
                    param_dtype=jnp.float32)(s)
        return x * nn.sigmoid(s)


class Dense(nn.Module):
    """Linear head with torch-default init."""

    features: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        return nn.Dense(
            self.features,
            dtype=self.dtype,
            param_dtype=jnp.float32,
            kernel_init=dense_kernel_init,
        )(x)


def head_dtype(dtype):
    """Classifier-head / loss compute dtype: fp32 regardless of a
    low-precision compute dtype (bf16/f16 softmax is unstable), PROMOTED
    to fp64 when the activations already are — a hard ``jnp.float32``
    here would silently re-round f64 runs (the x64 CPU equivalence
    tests) at the loss boundary."""
    return jnp.promote_types(jnp.float32, dtype)


def global_avg_pool(x):
    """NHWC global average pooling (≙ AdaptiveAvgPool2d(1) + flatten)."""
    return jnp.mean(x, axis=(1, 2))


def max_pool_3x3_s2(x):
    """torch MaxPool2d(kernel=3, stride=2, padding=1) in NHWC."""
    return nn.max_pool(
        x, window_shape=(3, 3), strides=(2, 2), padding=[(1, 1), (1, 1)]
    )
