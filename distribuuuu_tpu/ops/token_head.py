"""The vocabulary head and its loss, a chunk of tokens at a time.

A decoder's logits are ``[B, S, V]``: at 16,384 tokens and 50,304 classes
3.3 GB a float32 copy, and loss, top-k and their backward each want one.
This module never holds them: it walks the sequence in chunks of ``chunk``
positions, and each chunk's logits exist once, as one ``[B, chunk, V]``
block that is dropped before the next chunk's is made.

:func:`head_stats` (evaluation) computes per chunk the head's matmul, the
log-sum-exp, the label's logit and the label's rank among the logits, and
keeps two numbers a token. The block is read once for them: the
log-sum-exp's sum and the rank's count are one two-output reduction.

:func:`weighted_loss` (training) is the one differentiable entry: a custom
VJP whose output is the weighted sum of the per-token losses, the weights
(``1/N`` a token for the mean) known in the forward. So its forward rule
takes, from the SAME copy of a chunk's logits that gave the loss, the
logits' cotangent ``(softmax - one_hot(label)) * weight`` and both
gradients: three vocabulary-wide matmuls a chunk (logits, dX, dW) and
nothing recomputed. Between forward and backward it holds the two
gradients, ``d_hidden [B, S, d]`` and ``d_kernel [d, V]`` in float32 (the
buffer the backward would allocate first anyway), and nothing else of the
head: no ``hidden``, ``kernel``, ``labels`` or log-sum-exp. The backward
rule is two multiplications by the scalar cotangent (the literal 1 in a
training step, which XLA folds away). Exact: the same loss, gradients and
hits as on the full logits, up to summation order.

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


def _chunks(x, chunk: int):
    """``[B, S, ...]`` cut along S into pieces of ``chunk`` (the last may be
    shorter); the batch dim is never reshaped, so a data-sharded batch stays
    where it is."""
    return [x[:, i:i + chunk] for i in range(0, x.shape[1], chunk)]


def _chunk_stats(h, w, y):
    """One chunk's ``(logits, lse, nll, rank)``: the only place a
    vocabulary-wide block is made."""
    logits = jnp.einsum(
        "bcd,dv->bcv", h, w, preferred_element_type=head_dtype(h.dtype)
    )
    classes = jnp.arange(w.shape[-1], dtype=y.dtype)
    label_logit = jnp.take_along_axis(logits, y[..., None], axis=-1)
    ahead = (logits > label_logit) | (
        (logits == label_logit) & (classes < y[..., None])
    )
    # the log-sum-exp's sum and the rank's count in ONE pass over the block
    # (a two-output reduce): apart they are two reads of it
    top = logits.max(axis=-1, keepdims=True)
    top = jnp.where(jnp.isfinite(top), top, 0)  # as jax.nn.logsumexp
    total, rank = jax.lax.reduce(
        (jnp.exp(logits - top), ahead.astype(jnp.int32)),
        (jnp.zeros((), logits.dtype), jnp.zeros((), jnp.int32)),
        lambda a, b: (a[0] + b[0], a[1] + b[1]), (logits.ndim - 1,),
    )
    lse = jnp.log(total) + top[..., 0]
    return logits, lse, lse - label_logit[..., 0], rank


def _stats(hidden, kernel, labels, chunk):
    # logits, log-sum-exp and the loss are float32 by design (a softmax over
    # the vocabulary in bfloat16 loses the loss): the scope says so to the
    # dtype lint
    with jax.named_scope("head_loss_fp32"):
        w = kernel.astype(hidden.dtype)
        nll, rank = [], []
        for h, y in zip(_chunks(hidden, chunk), _chunks(labels, chunk)):
            if nll:  # one chunk's logits at a time (see _with_gradients)
                h, _ = jax.lax.optimization_barrier((h, nll[-1]))
            _, _, n, r = _chunk_stats(h, w, y)
            nll.append(n)
            rank.append(r)
    return jnp.concatenate(nll, axis=1), jnp.concatenate(rank, axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _weighted(hidden, kernel, labels, weights, chunk):
    # undifferentiated, this is all that runs: no gradient is computed
    nll, rank = _stats(hidden, kernel, labels, chunk)
    return (nll * weights).sum(), nll, rank


def _with_gradients(hidden, kernel, labels, weights, chunk):
    with jax.named_scope("head_loss_fp32"):
        w = kernel.astype(hidden.dtype)
        nll, rank, d_hidden = [], [], []
        d_kernel = jnp.zeros(kernel.shape, head_dtype(hidden.dtype))
        for h, y, g in zip(*(_chunks(x, chunk) for x in (hidden, labels, weights))):
            # one chunk after the other: without the barrier XLA computes
            # every chunk's logits and [d, V] share first and adds them all
            # at the end
            h, g, d_kernel = jax.lax.optimization_barrier((h, g, d_kernel))
            logits, lse, n, r = _chunk_stats(h, w, y)
            nll.append(n)
            rank.append(r)
            # d (weight * nll) / d logits = (softmax - one_hot(label)) * weight,
            # rounded once for the two matmuls that read it
            d_logits = jnp.exp(logits - lse[..., None]) - jax.nn.one_hot(
                y, logits.shape[-1], dtype=logits.dtype
            )
            d_logits = (d_logits * g[..., None]).astype(hidden.dtype)
            d_hidden.append(jnp.einsum("bcv,dv->bcd", d_logits, w))
            d_kernel = d_kernel + jnp.einsum(
                "bcd,bcv->dv", h, d_logits,
                preferred_element_type=d_kernel.dtype,
            )
        nll, rank, d_hidden = (jnp.concatenate(x, axis=1) for x in (nll, rank, d_hidden))
    return ((nll * weights).sum(), nll, rank), (d_hidden, d_kernel.astype(kernel.dtype))


def _scale(chunk, gradients, cotangents):
    d_hidden, d_kernel = gradients
    g = cotangents[0]  # nll and rank are statistics (see weighted_loss)
    with jax.named_scope("head_loss_fp32"):
        return ((g * d_hidden).astype(d_hidden.dtype),
                (g * d_kernel).astype(d_kernel.dtype), None, None)


_weighted.defvjp(_with_gradients, _scale)


def _one_chunk(chunk: int, S: int) -> int:
    return S if chunk <= 0 else min(chunk, S)


def head_stats(hidden, kernel, labels, *, chunk: int):
    """Per-token ``(nll [B, S] float32, rank [B, S] int32)`` of the head
    ``hidden [B, S, d] @ kernel [d, V]`` against ``labels [B, S]``, for
    evaluation: statistics, which carry no gradient (the loss to
    differentiate is :func:`weighted_loss`).

    ``rank`` is the label's position in ``lax.top_k``'s order (0 = the
    arg-max), so ``rank < k`` is the top-k hit. The matmul runs in
    ``hidden.dtype`` and accumulates in float32; logits, softmax and loss are
    float32. ``chunk`` positions of every sequence are taken at a time (0,
    or at least S: one chunk)."""
    hidden, kernel = jax.lax.stop_gradient((hidden, kernel))
    return _stats(hidden, kernel, labels, _one_chunk(chunk, hidden.shape[1]))


def weighted_loss(hidden, kernel, labels, weights, *, chunk: int):
    """``(sum(weights * nll), (nll, rank))``: the head's loss as a fixed
    weighted sum of the per-token losses, and :func:`head_stats`' two
    statistics from the same walk.

    Differentiable in ``hidden`` and ``kernel`` through the scalar alone:
    ``weights [B, S]`` (float32) are constants of the loss, ``1/N`` for the
    mean, a 0/1 mask over N for a masked mean, and a per-token cotangent
    ``g`` of ``nll`` is ``weights=g``; ``nll`` and ``rank`` come back as
    statistics. The logits' cotangent is rounded to ``hidden.dtype``, weight
    applied, for the two matmuls that read it."""
    loss, nll, rank = _weighted(
        hidden, kernel, labels, weights, _one_chunk(chunk, hidden.shape[1])
    )
    return loss, (jax.lax.stop_gradient(nll), rank)


def loss_and_accuracy(hidden, kernel, labels, *, topk, chunk: int):
    """``(mean cross-entropy, [top-k accuracy in percent for k in topk])``:
    what ``utils.metrics.cross_entropy`` and ``accuracy`` give on the full
    logits, from :func:`weighted_loss`."""
    weights = jnp.full(labels.shape, 1.0 / labels.size, head_dtype(hidden.dtype))
    loss, (_, rank) = weighted_loss(hidden, kernel, labels, weights, chunk=chunk)
    return loss, [(rank < k).mean(dtype=jnp.float32) * 100.0 for k in topk]
