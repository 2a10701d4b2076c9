"""Fused pointwise conv + BN-affine + activation (the eval epilogue).

A 1×1/s1 conv is a matmul ``[B·H·W, Cin] × [Cin, Cout]``, and eval-mode
BatchNorm is a per-channel affine: ``y = act(x·W·a + c)`` with
``a = rsqrt(var+eps)·scale`` and ``c = bias − mean·a``. XLA computes the
chain as conv → elementwise — the conv output round-trips HBM (bf16)
before the affine re-reads it; this kernel rides the affine+activation
on the matmul tile while the fp32 accumulator is still VMEM-resident:
one HBM read of the activations, one write of the activated output,
nothing in between.

Scope is deliberately the shape where Pallas WINS: the PERF.md r5 conv
campaign measured a Pallas conv chain 34% behind XLA's conv emitter on
spatial convs, and the retired group-conv kernel lost e2e to forfeited
epilogue fusion at the custom-call boundary — so this kernel only takes
matmul-shaped convs (1×1, stride 1, ungrouped: ResNet/RegNet bottleneck
1×1s via layers.ConvBN, EfficientNet's expand/project/head convs) and
carries its epilogue INSIDE the call. Everything else falls back to the
XLA reference path with a ``kernel.fallback`` record.

Numerics vs the reference chain: the conv accumulator stays fp32 into
the affine (the unfused path rounds the conv output to the compute
dtype first), so outputs agree to compute-dtype rounding — the pinned
tolerance in tests/test_pallas_kernels.py, not bit-exactness.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Tile defaults: [blk_m, K]·[K, blk_n] with the fp32 accumulator and the
# per-channel affine vectors resident — ≈(blk_m+blk_n)·K·2B + blk_m·blk_n·4B,
# ~1.3 MiB at K=2048. Both snap down to the array bounds for small shapes.
BLK_M = 256
BLK_N = 128

# activation registry: code -> in-kernel fp32 implementation. Callables
# are matched by identity in act_code() — an activation outside this
# table is a fallback reason, never a silent misfusion.
_ACTS = {
    "id": lambda y: y,
    "relu": lambda y: jnp.maximum(y, 0.0),
    "silu": lambda y: y * jax.nn.sigmoid(y),
}


def act_code(fn) -> str | None:
    """Map a module-level activation callable to its kernel code, or
    None when the kernel has no implementation for it."""
    import flax.linen as nn

    if fn is None:
        return "id"
    if fn in (nn.relu, jax.nn.relu):
        return "relu"
    if fn in (nn.silu, jax.nn.silu, nn.swish, jax.nn.swish):
        return "silu"
    return None


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _mm_epilogue_kernel(x_ref, w_ref, a_ref, c_ref, o_ref, *, act):
    x = x_ref[...]
    w = w_ref[...]
    acc = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    y = acc * a_ref[0] + c_ref[0]
    o_ref[...] = _ACTS[act](y).astype(o_ref.dtype)


def conv1x1_bn_act(x, kernel, a, c, act: str = "id", *,
                   out_dtype=None, interpret: bool = False,
                   blk_m: int = BLK_M, blk_n: int = BLK_N):
    """``act((x ⊛ kernel) · a + c)`` for a pointwise conv, one fused pass.

    x: [..., Cin] (any leading dims — NHWC batches flatten to rows);
    kernel: [1, 1, Cin, Cout] (the nn.Conv param layout) or [Cin, Cout];
    a, c: [Cout] fp32 affine (BN folded by the caller);
    act: a key of the in-kernel activation registry.
    Returns [..., Cout] in ``out_dtype`` (default: x.dtype).
    """
    if act not in _ACTS:
        raise ValueError(f"conv epilogue: unknown act {act!r} ({list(_ACTS)})")
    if kernel.ndim == 4:
        kernel = kernel.reshape(kernel.shape[-2], kernel.shape[-1])
    cin, cout = kernel.shape
    lead = x.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    out_dtype = out_dtype or x.dtype

    x2 = x.reshape(m, cin)
    # pad every dim to its tile multiple (K to the 128-lane boundary);
    # zero K-padding is exact (0·w contributes nothing), M/N padding is
    # sliced back off
    if m >= blk_m:
        mp = _round_up(m, blk_m)
    else:
        blk_m = _round_up(m, 8)  # small inputs: one sublane-aligned block
        mp = blk_m
    kp = _round_up(cin, 128)
    if cout >= blk_n:
        np_ = _round_up(cout, blk_n)
    else:
        blk_n = _round_up(cout, 128)  # lane-aligned single block
        np_ = blk_n
    x2 = jnp.pad(x2, ((0, mp - m), (0, kp - cin)))
    w2 = jnp.pad(kernel, ((0, kp - cin), (0, np_ - cout)))
    a2 = jnp.pad(a.astype(jnp.float32), (0, np_ - cout)).reshape(1, np_)
    c2 = jnp.pad(c.astype(jnp.float32), (0, np_ - cout)).reshape(1, np_)

    out = pl.pallas_call(
        functools.partial(_mm_epilogue_kernel, act=act),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        grid=(mp // blk_m, np_ // blk_n),
        in_specs=[
            pl.BlockSpec((blk_m, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((kp, blk_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, blk_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, blk_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((blk_m, blk_n), lambda i, j: (i, j)),
        interpret=interpret,
        name="dtpu_conv_epilogue",
    )(x2, w2, a2, c2)
    return out[:m, :cout].reshape(*lead, cout)


def qualifies(kernel_size, strides, padding, groups, act_fn,
              train: bool) -> tuple[bool, str]:
    """(supported, reason) for one conv+BN+act site. The reason string
    names the disqualifier — it becomes the kernel.fallback record."""
    if train:
        return False, "training forward (BN batch stats need the raw conv output)"
    k = tuple(kernel_size)
    if k != (1, 1):
        return False, f"kernel {k} is not pointwise (1, 1)"
    s = strides if isinstance(strides, (tuple, list)) else (strides, strides)
    if tuple(s) != (1, 1):
        return False, f"stride {tuple(s)} != (1, 1)"
    if padding is not None and any(p != (0, 0) for p in map(tuple, padding)):
        return False, f"padding {padding} != zero"
    if groups != 1:
        return False, f"grouped conv (groups={groups})"
    if act_code(act_fn) is None:
        return False, f"activation {getattr(act_fn, '__name__', act_fn)!r} has no kernel"
    return True, ""


def pass_bytes(m: int, cin: int, cout: int, in_dtype, out_dtype) -> int:
    """DMA model of one fused pass: activations + weights read once,
    output written once, affine vectors negligible — the pallas arm of
    kernel_bench's roofline A/B (cost_analysis cannot price the fused
    TPU call; this is what its BlockSpecs transfer)."""
    isz = jnp.dtype(in_dtype).itemsize
    osz = jnp.dtype(out_dtype).itemsize
    return m * cin * isz + cin * cout * isz + 2 * cout * 4 + m * cout * osz
