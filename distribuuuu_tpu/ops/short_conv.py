"""The gated short convolution of LFM2's ``conv`` layers (LiquidAI; HF
``Lfm2ShortConv``): everything of the mixer that is not a matmul.

    [B, C, u] = split3(x W_in)        g = B * u
    c_t = sum_{j=0..L-1} w_j * g_{t-(L-1)+j}      (g zero left of the sequence)
    y = C * c

``w`` is one filter of ``L`` taps a channel (``[H, L]``; ``L`` =
``conv_L_cache`` = 3), no bias, no activation: a depthwise causal convolution
between two elementwise gates. Written as ``L`` shifted multiply-adds in
plain ``jax.numpy`` and not as a convolution call (a ``conv_general_dilated``
with ``feature_group_count = H`` is a kernel of its own between two
elementwise ones). The arithmetic is float32 whatever the inputs' dtype (a
v5e's VPU has no bfloat16) and each result is rounded once, to the inputs'
dtype.

The backward is a rule of its own (``custom_vjp``) that keeps ``x W_in`` AS IT
CAME and the filter, nothing else, and computes ``g`` and the convolution
again from them: autodiff of the forward keeps float32 copies of B, C and u
between forward and backward (12 bytes a channel a token where the input has
6; compiled for the v5e, 0.94 GiB of temporaries against 0.50 for one layer
of 2 x 8192 tokens, and a quarter more cycles by XLA's own estimate; PERF.md
section 6, PR 41). The shifts are taken on the INPUTS (``B`` and ``u`` moved,
then multiplied), so that no shifted product has to exist in memory.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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


def _forward(bcu, w):
    with jax.named_scope("short_conv_gate"), jax.named_scope("gate_fp32"):
        b, c, u = jnp.split(bcu, 3, axis=-1)
        return (_f32(c) * _conv(b, u, _f32(w))).astype(bcu.dtype)


@jax.custom_vjp
def gated_short_conv(bcu, w):
    """``bcu [..., S, 3H]`` (``x W_in``, not yet split), ``w [H, L]`` ->
    ``[..., S, H]`` in ``bcu``'s dtype, under the scope ``short_conv_gate``
    (and inside it ``gate_fp32``, which says the float32 is meant:
    analysis/passes/dtype.py), forward and backward."""
    return _forward(bcu, w)


def _fwd(bcu, w):
    return _forward(bcu, w), (bcu, w)


def _bwd(residuals, dy):
    bcu, w = residuals
    with jax.named_scope("short_conv_gate"), jax.named_scope("gate_fp32"):
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
        return dbcu.astype(bcu.dtype), dw.astype(w.dtype)


gated_short_conv.defvjp(_fwd, _bwd)
