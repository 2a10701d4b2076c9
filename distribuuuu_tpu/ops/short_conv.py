"""The gated short convolution of LFM2's ``conv`` layers (LiquidAI; HF
``Lfm2ShortConv``): everything of the mixer that is not a matmul.

    [B, C, u] = split3(x W_in)        g = B * u
    c_t = sum_{j=0..L-1} w_j * g_{t-(L-1)+j}      (g zero left of the sequence)
    y = C * c

``w`` is one filter of ``L`` taps a channel (``[H, L]``; ``L`` =
``conv_L_cache`` = 3), no bias, no activation: a depthwise causal convolution
between two elementwise gates. The arithmetic is float32 whatever the inputs'
dtype (a v5e's VPU has no bfloat16) and each result is rounded once, to the
inputs' dtype.

**Which path runs where** (:func:`_kernel_runs`: platform, program and shape,
no knob; a ``kernel.select`` or ``kernel.fallback`` record says which and
why). In a one-device TPU program where ``H`` is a multiple of the 128 lanes,
a sequence block divides ``S`` (``ops/pallas/short_conv.seq_block``), the
dtype is a 16- or 32-bit float and ``L <= 9``: ONE Pallas call each way,
``dtpu_short_conv_fwd`` and ``dtpu_short_conv_bwd`` (``ops/pallas/
short_conv.py``: LFM2's cell, 23.8 -> 86.5 % of the HBM's bandwidth on the
bytes a perfect fusion moves; PERF.md section 6, PR 44). Everywhere else (the
CPU, a program that may span devices such as the tensor-parallel one whose
filter is sharded over ``model``, odd shapes) the plain ``jax.numpy`` below,
which is also the kernel's reference in the tests: ``L`` shifted
multiply-adds and not a convolution call (a ``conv_general_dilated`` with
``feature_group_count = H`` is a kernel of its own between two elementwise
ones), inside ``gate_fp32``, which says the float32 is meant
(analysis/passes/dtype.py).

The backward is a rule of its own (``custom_vjp``) that keeps ``x W_in`` AS IT
CAME and the filter, nothing else, and computes ``g`` and the convolution
again from them: autodiff of the forward keeps float32 copies of B, C and u
between forward and backward (12 bytes a channel a token where the input has
6; compiled for the v5e, 0.94 GiB of temporaries against 0.50 for one layer
of 2 x 8192 tokens, and a quarter more cycles by XLA's own estimate; PERF.md
section 6, PR 41). Both paths keep exactly those residuals. In the
``jax.numpy`` path the shifts are taken on the INPUTS (``B`` and ``u`` moved,
then multiplied), so that no shifted product has to exist in memory; the
kernel shifts in VMEM.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from distribuuuu_tpu.ops import pallas as kernel_tier
from distribuuuu_tpu.ops.pallas import short_conv as kernel


def _shift(x, k: int, earlier: bool = False):
    """``x [..., S, H]`` read ``k`` positions back along S (``earlier``:
    ahead), zeros entering: ``out_t = x_{t-k}`` (``x_{t+k}``)."""
    if k == 0:
        return x
    lead = [(0, 0)] * (x.ndim - 2)
    if earlier:
        return jnp.pad(x[..., k:, :], (*lead, (0, k), (0, 0)))
    return jnp.pad(x[..., :-k, :], (*lead, (k, 0), (0, 0)))


def _f32(x):
    return x.astype(jnp.float32)


def _tap_inputs(b, u, back: int):
    """``g`` as tap ``L - 1 - back`` reads it: ``g_{t-back}``, float32."""
    return _f32(_shift(b, back)) * _f32(_shift(u, back))


def _conv(b, u, w):
    """``sum_j w_j g_{t-(L-1)+j}``: the last tap is the position's own."""
    taps = w.shape[-1]
    return sum(w[:, j] * _tap_inputs(b, u, taps - 1 - j) for j in range(taps))


def _kernel_runs(bcu, w, interpret) -> bool:
    """Whether ``ops/pallas/short_conv`` runs the op: decided on what is
    static in the call (platform, program, shape), no knob; says which ran,
    and why, in a ``kernel.select``/``kernel.fallback`` record."""
    S, H, taps = bcu.shape[-2], *w.shape
    reason = kernel.unsupported(S, H, taps, bcu.dtype)
    detail = {}
    if not reason:
        ts = kernel.seq_block(S, H, taps, bcu.dtype)
        tr, tl = kernel.chunks(ts, H, bcu.dtype)
        detail = dict(seq_block=ts, row_chunk=tr, lane_chunk=tl, taps=taps,
                      channels=H, tokens=math.prod(bcu.shape[:-1]))
    return kernel_tier.select(
        "short_conv", supported=not reason, reason=reason,
        forced=interpret is not None, **detail) == "pallas"


def _interpreted(interpret) -> bool:
    return kernel_tier.interpret_mode() if interpret is None else interpret


def forward_xla(bcu, w):
    """The forward in plain ``jax.numpy``: what runs off the TPU, and the
    kernel's reference."""
    with jax.named_scope("gate_fp32"):
        b, c, u = jnp.split(bcu, 3, axis=-1)
        return (_f32(c) * _conv(b, u, _f32(w))).astype(bcu.dtype)


def _forward(bcu, w, interpret):
    with jax.named_scope("short_conv_gate"):
        if _kernel_runs(bcu, w, interpret):
            return kernel.forward(bcu, w, interpret=_interpreted(interpret))
        return forward_xla(bcu, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def gated_short_conv(bcu, w, interpret=None):
    """``bcu [..., S, 3H]`` (``x W_in``, not yet split), ``w [H, L]`` ->
    ``[..., S, H]`` in ``bcu``'s dtype, under the scope ``short_conv_gate``,
    forward and backward. ``interpret`` True/False forces the kernel,
    interpreted or compiled (the tests)."""
    return _forward(bcu, w, interpret)


def _fwd(bcu, w, interpret):
    return _forward(bcu, w, interpret), (bcu, w)


def backward_xla(bcu, w, dy):
    """``(dbcu, dw float32)`` in plain ``jax.numpy``, as :func:`forward_xla`."""
    with jax.named_scope("gate_fp32"):
        b, c, u = jnp.split(bcu, 3, axis=-1)
        w32, taps = _f32(w), w.shape[-1]
        dc = _f32(dy) * _conv(b, u, w32)

        def dconv(ahead):  # dy * C at t + ahead
            return _f32(_shift(dy, ahead, True)) * _f32(_shift(c, ahead, True))

        # g_t feeds tap j of position t + (L - 1 - j)
        dg = sum(w32[:, j] * dconv(taps - 1 - j) for j in range(taps))
        over = tuple(range(dy.ndim - 1))  # batch and sequence
        dw = jnp.stack([
            (dconv(0) * _tap_inputs(b, u, taps - 1 - j)).sum(over)
            for j in range(taps)], axis=-1)
        dbcu = jnp.concatenate([dg * _f32(u), dc, dg * _f32(b)], axis=-1)
        return dbcu.astype(bcu.dtype), dw


def _bwd(interpret, residuals, dy):
    bcu, w = residuals
    with jax.named_scope("short_conv_gate"):
        if _kernel_runs(bcu, w, interpret):
            dbcu, dw = kernel.backward(
                bcu, w, dy, interpret=_interpreted(interpret))
        else:
            dbcu, dw = backward_xla(bcu, w, dy)
        return dbcu, dw.astype(w.dtype)


gated_short_conv.defvjp(_fwd, _bwd)
