"""Mamba-2's recurrence as a chunked scan (state-space duality,
arXiv:2405.21060 section 6): the token mixer of a selective state-space layer
without its projections.

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t        y_t = S_t C_t + D_h x_t

``S`` is one ``[P, N]`` state a head (``P`` the head's width, ``N`` the state
size), ``A_h < 0`` one decay rate a head, ``dt_t > 0`` the step a token and a
head, ``B_t`` and ``C_t`` ``[N]`` vectors a token that the ``H / G`` heads of
a group share. Token by token that is S sequential steps of ``P N`` work; in
chunks of ``L`` tokens it is four matrix products and ONE short scan:

* inside a chunk the quadratic (attention-like) form, ``y_l += sum_{s<=l}
  (C_l . B_s) exp(cum_l - cum_s) dt_s x_s`` with ``cum`` the chunk's running
  sum of ``dt A``: a ``[L, L]`` product a group, masked and decayed a head;
* a chunk's own state, ``sum_s exp(cum_L - cum_s) dt_s x_s (x) B_s``;
* across the ``S / L`` chunks the recurrence on those states alone,
  ``carried_{c+1} = exp(cum_L) carried_c + state_c``:
  ``jax.lax.associative_scan`` over the chunk axis (``log2(S / L)`` levels of
  elementwise work on ``[H, P, N]``, no ``while`` in the program);
* what the carried state adds inside the next chunk, ``exp(cum_l) C_l .
  carried``.

Decays, running sums and every accumulation are float32; the operands of the
four products are ``x``'s dtype (bfloat16 in the timed program, as Mamba-2's
own kernels hold them) and accumulate in float32. A sequence the chunk does
not divide is PADDED with positions of ``dt = 0`` (the state passes them
unchanged, their rows are cut), not refused.

**Which path runs where** (:func:`_kernel_runs`: platform, program and shape,
no knob; a ``kernel.select`` record says which, with the chunking either way,
and a ``kernel.fallback`` record why the body ran). In a one-device TPU program
where the chunk is the 128 lanes, the state a multiple of them and a group's
heads fill whole lane tiles: ONE Pallas call each way, ``dtpu_ssd_fwd`` and
``dtpu_ssd_bwd`` (``ops/pallas/ssd.py``), that walk the chunks in order with
the state in VMEM, so that nothing ``[L, L]`` a head or ``[P, N]`` a chunk
reaches HBM but the state entering each chunk, which the backward reads
(PERF.md section 6, PR 54): the SAME recurrence in the same float32, the
sequential walk in place of the ``associative_scan``, rounded where the body
rounds. Everywhere else (the CPU, a program that may span devices, odd shapes)
the ``jax.numpy`` body below, whose backward is autodiff of it and which is
the kernel's reference in the tests (``tests/test_ssd_kernel.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from distribuuuu_tpu.ops import pallas as kernel_tier
from distribuuuu_tpu.ops.pallas import ssd as kernel

CHUNK = 128


def _combine(earlier, later):
    """Two stretches of chunks as one: (decay over it, state it leaves when
    entered with none)."""
    decay_e, state_e = earlier
    decay_l, state_l = later
    return decay_e * decay_l, decay_l[..., None, None] * state_e + state_l


def _kernel_runs(x, groups: int, state: int, chunk: int, interpret) -> bool:
    """Whether ``ops/pallas/ssd`` runs the scan: decided on what is static in
    the call (platform, program, shape), no knob; the ``kernel.select`` record
    says which ran, with the chunking either way, and a ``kernel.fallback``
    record why the body did."""
    _, seq, heads, width = x.shape
    reason = kernel.unsupported(chunk, heads, groups, state, width, x.dtype)
    return kernel_tier.select(
        "ssd", supported=not reason, reason=reason, forced=interpret is not None,
        work=dict(chunk=chunk, chunks_a_sequence=-(-seq // chunk), heads=heads,
                  groups=groups, state=state, head_dim=width)) == "pallas"


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, dt, a, b, c, d, chunk, interpret):
    """The kernel path over whole chunks: a rule of its own that keeps its
    inputs and the state entering every chunk, nothing ``[L, L]``."""
    return kernel.forward(x, dt, a, b, c, d, chunk=chunk, interpret=interpret)[:2]


def _scan_fwd(x, dt, a, b, c, d, chunk, interpret):
    y, last, entering = kernel.forward(
        x, dt, a, b, c, d, chunk=chunk, keep=True, interpret=interpret)
    return (y, last), (x, dt, a, b, c, d, entering)


def _scan_bwd(chunk, interpret, residuals, cotangents):
    *inputs, entering = residuals
    grads = kernel.backward(
        *inputs, entering, *cotangents, chunk=chunk, interpret=interpret)
    return tuple(g.astype(t.dtype) for g, t in zip(grads, inputs))


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd(x, dt, a, b, c, d=None, *, chunk: int = CHUNK,
        interpret: bool | None = None):
    """``(y [B, S, H, P] float32, the state after the last position [B, H, P,
    N] float32)`` of the recurrence in the module docstring, from zero state.

    ``x [B, S, H, P]``; ``dt [B, S, H]`` (after its softplus); ``a [H]``
    (negative); ``b``, ``c`` ``[B, S, G, N]``, head ``h`` on group ``h // (H /
    G)``; ``d [H]`` the skip, or None. The products run in ``x.dtype``.
    ``interpret`` True/False forces the kernel, interpreted or compiled (the
    tests)."""
    batch, seq, heads, width = x.shape
    groups, state = b.shape[-2:]
    if heads % groups:
        raise ValueError(f"{heads} heads on {groups} groups: not a whole number a group")
    if not _kernel_runs(x, groups, state, chunk, interpret):
        return _body(x, dt, a, b, c, d, chunk)
    pad = -seq % chunk

    def whole(t):  # positions of dt = 0 pass the state unchanged
        return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) if pad else t

    y, last = _scan(
        whole(x), whole(dt), a, whole(b.astype(x.dtype)), whole(c.astype(x.dtype)),
        jnp.zeros((heads,), jnp.float32) if d is None else d, chunk,
        kernel_tier.interpret_mode() if interpret is None else interpret)
    return y[:, :seq], last


def _body(x, dt, a, b, c, d, chunk: int):
    """:func:`ssd` in plain ``jax.numpy``: what runs off the TPU, and the
    kernel's reference."""
    batch, seq, heads, width = x.shape
    groups, state = b.shape[-2:]
    per = heads // groups
    chunks = -(-seq // chunk)
    f32, dtype = jnp.float32, x.dtype
    pad = chunks * chunk - seq

    def chunked(t, *tail):  # [B, S, ...] -> [B, chunks, L, *tail]
        if pad:
            t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return t.reshape(batch, chunks, chunk, *tail)

    xc = chunked(x, groups, per, width)
    dtc = chunked(dt.astype(f32), groups, per)
    bc, cc = chunked(b.astype(dtype), groups, state), chunked(c.astype(dtype), groups, state)
    # [B, chunks, G, R, L]: the running sum of dt A inside each chunk
    step = jnp.moveaxis(dtc, 2, -1)
    cum = jnp.cumsum(step * a.astype(f32).reshape(groups, per, 1), axis=-1)

    # inside a chunk: scores a group, decay and step a head, then the values
    scores = jnp.einsum("bzlgn,bzsgn->bzgls", cc, bc, preferred_element_type=f32)
    later = jnp.tril(jnp.ones((chunk, chunk), bool))
    # exp of the masked difference, never the mask of an overflowed exp
    decay = jnp.exp(jnp.where(later, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    mixed = scores[:, :, :, None] * decay * step[..., None, :]
    y = jnp.einsum("bzgrls,bzsgrp->bzlgrp", mixed.astype(dtype), xc,
                   preferred_element_type=f32)

    # each chunk's own state, and the recurrence on them alone
    to_end = jnp.moveaxis(jnp.exp(cum[..., -1:] - cum) * step, -1, 2)
    own = jnp.einsum(
        "bzlgrp,bzlgn->bzgrpn", (xc.astype(f32) * to_end[..., None]).astype(dtype),
        bc, preferred_element_type=f32)
    _, after = jax.lax.associative_scan(
        _combine, (jnp.exp(cum[..., -1]), own), axis=1)
    carried = jnp.concatenate([jnp.zeros_like(after[:, :1]), after[:, :-1]], axis=1)
    y = y + jnp.einsum(
        "bzlgn,bzgrpn->bzlgrp", cc, carried.astype(dtype),
        preferred_element_type=f32) * jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None]

    y = y.reshape(batch, chunks * chunk, heads, width)[:, :seq]
    if d is not None:
        y = y + d.astype(f32)[:, None] * x.astype(f32)
    return y, after[:, -1].reshape(batch, heads, width, state)
