"""The vocabulary head and its loss, a chunk of tokens at a time.

A decoder's logits are ``[B, S, V]``: at 16,384 tokens and 50,304 classes
3.3 GB a float32 copy, and loss, top-k and their backward each want one.
:func:`head_stats` never holds them: it walks the sequence in chunks of
``chunk`` positions, and for each computes the head's matmul, the
log-sum-exp, the label's logit and the label's rank among the logits, keeps
three numbers a token, and drops the chunk. The backward (a custom VJP)
walks the chunks again, recomputes each chunk's logits, takes the softmax
from the saved log-sum-exp, and adds the chunk's share to the head's
gradient. Forward and backward each hold one ``[B, chunk, V]`` block at a
time. Exact: the same loss, gradients and hits as on the full logits, up to
summation order.

The chunks are a Python loop, not a ``lax.map``: a while loop shows in a
device trace as one operation AND its body's operations, and every reader
that sums the operations under a scope would count the head twice.

The rank replaces ``lax.top_k`` over the vocabulary: a label is in the top
k exactly when fewer than k logits come before it in ``top_k``'s order
(larger first, ties to the lower index), which is one fused compare-and-
count instead of a 50,304-wide sort a token.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from distribuuuu_tpu.models.layers import head_dtype


def _logits(hidden, kernel):
    return jnp.einsum(
        "bcd,dv->bcv", hidden, kernel,
        preferred_element_type=head_dtype(hidden.dtype),
    )


def _chunks(x, chunk: int):
    """``[B, S, ...]`` cut along S into pieces of ``chunk`` (the last may be
    shorter); the batch dim is never reshaped, so a data-sharded batch stays
    where it is."""
    return [x[:, i:i + chunk] for i in range(0, x.shape[1], chunk)]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _head_stats(hidden, kernel, labels, chunk):
    return _forward(hidden, kernel, labels, chunk)[0]


def _forward(hidden, kernel, labels, chunk):
    # logits, log-sum-exp and the loss are float32 by design (a softmax over
    # the vocabulary in bfloat16 loses the loss): the scope says so to the
    # dtype lint
    with jax.named_scope("head_loss_fp32"):
        w = kernel.astype(hidden.dtype)
        classes = jnp.arange(kernel.shape[-1], dtype=labels.dtype)
        nll, rank, lse = [], [], []
        for h, y in zip(_chunks(hidden, chunk), _chunks(labels, chunk)):
            if nll:  # one chunk's logits at a time (see _backward)
                h, _ = jax.lax.optimization_barrier((h, nll[-1]))
            logits = _logits(h, w)
            label_logit = jnp.take_along_axis(logits, y[..., None], axis=-1)
            ahead = (logits > label_logit) | (
                (logits == label_logit) & (classes < y[..., None])
            )
            lse.append(jax.nn.logsumexp(logits, axis=-1))
            nll.append(lse[-1] - label_logit[..., 0])
            rank.append(ahead.sum(axis=-1, dtype=jnp.int32))
        nll, rank, lse = (jnp.concatenate(x, axis=1) for x in (nll, rank, lse))
    return (nll, rank), (hidden, kernel, labels, lse)


def _backward(chunk, residuals, cotangents):
    hidden, kernel, labels, lse = residuals
    g_nll, _ = cotangents  # the rank is integer: no cotangent
    with jax.named_scope("head_loss_fp32"):
        w = kernel.astype(hidden.dtype)
        classes = jnp.arange(kernel.shape[-1], dtype=labels.dtype)
        d_hidden, d_kernel = [], jnp.zeros(kernel.shape, head_dtype(hidden.dtype))
        for h, y, z, g in zip(*(_chunks(x, chunk) for x in (hidden, labels, lse, g_nll))):
            # one chunk after the other: without the barrier XLA computes
            # every chunk's [d, V] share first and adds them all at the end
            h, g, d_kernel = jax.lax.optimization_barrier((h, g, d_kernel))
            # d nll / d logits = softmax - one_hot(label), from the saved lse
            d_logits = jnp.exp(_logits(h, w) - z[..., None]) - (classes == y[..., None])
            d_logits = (d_logits * g[..., None]).astype(hidden.dtype)
            d_hidden.append(jnp.einsum("bcv,dv->bcd", d_logits, w))
            d_kernel = d_kernel + jnp.einsum(
                "bcd,bcv->dv", h, d_logits,
                preferred_element_type=d_kernel.dtype,
            )
    return (jnp.concatenate(d_hidden, axis=1).astype(hidden.dtype),
            d_kernel.astype(kernel.dtype), None)


_head_stats.defvjp(_forward, _backward)


def head_stats(hidden, kernel, labels, *, chunk: int):
    """Per-token ``(nll [B, S] float32, rank [B, S] int32)`` of the head
    ``hidden [B, S, d] @ kernel [d, V]`` against ``labels [B, S]``.

    ``rank`` is the label's position in ``lax.top_k``'s order (0 = the
    arg-max), so ``rank < k`` is the top-k hit. The matmuls run in
    ``hidden.dtype`` and accumulate in float32; logits, softmax and loss are
    float32; the logits' cotangent is rounded to ``hidden.dtype`` for the
    backward's two matmuls. ``chunk`` positions of every sequence are taken
    at a time (0, or at least S: one chunk)."""
    S = hidden.shape[1]
    return _head_stats(hidden, kernel, labels, S if chunk <= 0 else min(chunk, S))


def loss_and_accuracy(hidden, kernel, labels, *, topk, chunk: int):
    """``(mean cross-entropy, [top-k accuracy in percent for k in topk])``:
    what ``utils.metrics.cross_entropy`` and ``accuracy`` give on the full
    logits, from :func:`head_stats`."""
    nll, rank = head_stats(hidden, kernel, labels, chunk=chunk)
    return nll.mean(), [(rank < k).mean(dtype=jnp.float32) * 100.0 for k in topk]
