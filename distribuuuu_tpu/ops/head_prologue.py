"""What lies between a q or k projection and the flash kernels where every
head has its own RMSNorm (``models/lfm2_moe.Attention``: LFM2, Trinity-Mini,
SDAR), as ONE op:

    x = heads(t)                          [B, S, n D] -> [B, n, S, D]
    x = x * rsqrt(mean_D(x^2) + eps) * scale
    x = x * cos + rotate_half(x) * sin    (where the layer has a rotary)

in float32 whatever ``t``'s dtype, rounded once to it.

**Which path runs where** (:func:`kernel_runs`: platform, program and shape,
no knob and no model's name; a ``kernel.select`` or ``kernel.fallback`` record
says which and why). In a one-device TPU program where the head dim is a
multiple of the 128 lanes, a row block divides ``S``
(``ops/pallas/head_prologue.row_block``) and the dtype is bfloat16 or
float32: ONE Pallas call each way, ``dtpu_head_prologue_fwd`` and
``dtpu_head_prologue_bwd`` (``ops/pallas/head_prologue.py``; PERF.md section
6, PR 51). Everywhere else (the CPU, a program that may span devices, LFM2's
heads of 64) the caller's own ``jax.numpy`` lines run, as they always did
(``models/lfm2_moe.HeadNorm.xla``), under plain autodiff; they are the
kernel's reference in the tests.

The kernel path is a rule of its own (``custom_vjp``) that keeps ``t`` AS THE
PROJECTION WROTE IT, the scale and the two tables, nothing else: the backward
call makes the normalised ``x`` again in VMEM. Under ``models/ouro.recomputed``
the second forward therefore runs the projection again, as it does today
(the norm's backward read its output then too), and never the forward call:
its one reader, the flash forward kernel, is not run again either.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from distribuuuu_tpu.ops import pallas as kernel_tier
from distribuuuu_tpu.ops.pallas import head_prologue as kernel


def rotary_tables(positions, head_dim: int, theta: float):
    """``(cos, sin±) [S, D]`` float32 of rotate-half rotary at ``positions
    [S]``: ``models/olmoe.rotary``'s angles, each half of the head carrying
    the same ones, and the sign of ``rotate_half([a, b]) = [-b, a]`` folded
    into the sine: ``x cos + roll(x, D / 2) sin±`` is ``rotary``'s result."""
    half = head_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.concatenate([cos, cos], axis=-1), jnp.concatenate([-sin, sin], axis=-1)


def kernel_runs(t, heads: int, rotary: bool, interpret=None) -> bool:
    """Whether ``ops/pallas/head_prologue`` runs the op on ``t [..., S, n
    D]``: decided on what is static in the call (platform, program, shape), no
    knob; says which ran, and why, in a ``kernel.select``/``kernel.fallback``
    record."""
    S, D = t.shape[-2], t.shape[-1] // heads
    reason = kernel.unsupported(S, heads, D, t.dtype, rotary)
    detail = {}
    if not reason:
        blk = kernel.row_block(S, heads, D, t.dtype, rotary)
        detail = dict(rows=t.size // (heads * D), heads=heads, head_dim=D,
                      rotary=rotary, row_block=blk,
                      row_chunk=kernel.row_chunk(blk, t.dtype))
    return kernel_tier.select(
        "head_prologue", supported=not reason, reason=reason,
        forced=interpret is not None, **detail) == "pallas"


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _prologue(t, scale, cos, sin, heads, eps, interpret):
    return kernel.forward(t, scale, cos, sin, heads=heads, eps=eps, interpret=interpret)


def _fwd(t, scale, cos, sin, heads, eps, interpret):
    return _prologue(t, scale, cos, sin, heads, eps, interpret), (t, scale, cos, sin)


def _bwd(heads, eps, interpret, residuals, dy):
    t, scale, cos, sin = residuals
    dt, dscale = kernel.backward(
        t, scale, dy, cos, sin, heads=heads, eps=eps, interpret=interpret)
    # the tables come from integer positions: nothing reads their cotangents
    return dt, dscale.astype(scale.dtype), None, None


_prologue.defvjp(_fwd, _bwd)


def head_prologue(t, scale, positions=None, *, heads: int, eps: float,
                  theta: float | None = None, interpret: bool | None = None):
    """The kernel path: ``t [..., S, n D]`` (``x W`` as the projection wrote
    it), ``scale [D]`` -> ``[..., n, S, D]`` in ``t``'s dtype, normed a head
    and, with a ``theta``, rotated at ``positions [S]`` (one a ROW). For a call
    :func:`kernel_runs` said yes to; ``interpret`` True/False forces the
    calls interpreted or compiled (the tests), None follows the platform."""
    if interpret is None:
        interpret = kernel_tier.interpret_mode()
    cos = sin = None
    if theta is not None:
        cos, sin = rotary_tables(positions, t.shape[-1] // heads, theta)
    return _prologue(t, scale, cos, sin, heads, eps, interpret)
