"""Fused decode attention over the paged KV cache.

The ledger's worst offender: ``gen_decode_*`` programs measured at
arithmetic intensity 0.56 vs the 3.9 ridge (PERF.md "LM decode
roofline") — per token, tiny flops against a full read of the cache.
The dense reference (lm/generate.CachedAttention's T=1 step) makes it
worse than it has to be: it CASTS the whole bf16 cache to fp32
(materializing a 2× copy), materializes the ``[B, H, 1, C]`` fp32
logits, and runs softmax as separate max/exp/sum/div passes over them —
tools/kernel_bench.py measures ~5× the unavoidable byte count on the
lowered program.

This kernel is that region fused: one program per (batch row, head)
reads its cache page block-by-block, runs the two matmuls and the
online softmax on VMEM-resident tiles (fp32 compute, exactly the
reference's precision), masks ``kpos > length`` in-register, and skips
key blocks entirely past the row's length — the flash block machinery
(ops/flash_attention.py) re-tiled for the T=1 ragged-lengths cache
shape. HBM sees one read of the live cache blocks and one [B, H, D]
write. Same math as the dense softmax up to fp32 summation order
(pinned tolerance: tests/test_pallas_kernels.py against real GPT
checkpoint logits).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distribuuuu_tpu.ops.flash_attention import _NEG_BIG

# default cache-block height (sublane dim; the lane dim is head_dim).
# KERNELS.DECODE_BLOCK overrides per run.
BLK_K = 128


def resolve_block(cache_len: int, blk: int) -> int | None:
    """The key-block height actually used for a cache tile: ``blk`` when
    it divides the tile, the whole tile when it fits inside one block,
    else None (unsupported — the caller's fallback/refusal carries both
    numbers)."""
    if cache_len <= blk:
        return cache_len
    if cache_len % blk == 0:
        return blk
    return None


def supported(t: int, cache_len: int, head_dim: int,
              blk: int) -> tuple[bool, str]:
    """(supported, reason) for one CachedAttention call site."""
    if t != 1:
        return False, f"T={t} new tokens (the kernel is the T=1 decode step)"
    if head_dim > 128:
        return False, f"head_dim {head_dim} > 128 (lane tiling)"
    if resolve_block(cache_len, blk) is None:
        return False, (
            f"KERNELS.DECODE_BLOCK={blk} does not divide the cache tile "
            f"{cache_len} ({cache_len} % {blk} = {cache_len % blk})"
        )
    return True, ""


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *, scale, blk_k):
    # len_ref is the scalar-prefetched [B] lengths vector (SMEM): the
    # row's length is a loop bound, and Mosaic reads loop bounds from
    # scalar memory, never from a VMEM tile
    q = q_ref[0, 0].astype(jnp.float32)  # [1, D]
    d = q.shape[1]
    c = k_ref.shape[2]
    nk = c // blk_k
    length = len_ref[pl.program_id(0)]

    def body(t, carry):
        m, l, acc = carry
        # t is a Python 0 for a one-block tile: a static slice, which
        # Mosaic takes at any height (a dynamic one must be provably
        # aligned to the dtype's sublane packing)
        start = t * blk_k if nk == 1 else pl.multiple_of(t * blk_k, blk_k)
        kb = k_ref[0, 0, pl.ds(start, blk_k), :].astype(jnp.float32)
        vb = v_ref[0, 0, pl.ds(start, blk_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [1, blk_k]
        kpos = start + jax.lax.broadcasted_iota(jnp.int32, (1, blk_k), 1)
        # the new token sits at absolute position ``length``: keys
        # 0..length inclusive are visible, stale tail positions masked
        s = jnp.where(kpos <= length, s, _NEG_BIG)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = corr * l + p.sum(axis=-1, keepdims=True)
        acc = acc * corr + jnp.dot(p, vb, preferred_element_type=jnp.float32)
        return m_new, l, acc

    # ragged block-skip: blocks starting past this row's length are fully
    # masked — never read them (the continuous-batching win: a short row
    # in a long tile reads only its own live blocks)
    m0 = jnp.full((1, 1), _NEG_BIG, jnp.float32)
    l0 = jnp.zeros((1, 1), jnp.float32)
    a0 = jnp.zeros((1, d), jnp.float32)
    if nk == 1:
        m, l, acc = body(0, (m0, l0, a0))
    else:
        nk_hi = jnp.minimum(nk, length // blk_k + 1)
        m, l, acc = jax.lax.fori_loop(0, nk_hi, body, (m0, l0, a0))
    o_ref[0, 0] = acc / jnp.maximum(l, 1e-30)


def decode_attention(q, cache_k, cache_v, lengths, *, scale: float,
                     blk_k: int = BLK_K, interpret: bool = False):
    """One fused decode-attention step.

    q: [B, H, D] (the single new token's queries); cache_k/cache_v:
    [B, H, C, D] paged KV (row b's positions 0..lengths[b] live, the new
    token's K/V already written at index lengths[b]); lengths: [B] int32.
    Returns fp32 [B, H, D] — identical contract to the dense reference's
    pre-projection output.
    """
    b, h, c, d = cache_k.shape
    blk = resolve_block(c, blk_k)
    if blk is None:
        raise ValueError(
            f"decode_attention: block {blk_k} does not divide cache {c}"
        )
    # q and the output ride as [B, H, 1, D] so their (1, D) blocks equal
    # the arrays' last two dims — Mosaic takes a block whose trailing
    # dims are (8, 128)-divisible or whole, and a (1, D) row of [H, D]
    # is neither
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, blk_k=blk),
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h),
            in_specs=[
                pl.BlockSpec((1, 1, 1, d), lambda i, j, lens: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, c, d), lambda i, j, lens: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, c, d), lambda i, j, lens: (i, j, 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, 1, d), lambda i, j, lens: (i, j, 0, 0)
            ),
        ),
        interpret=interpret,
        name="dtpu_decode_attn",
    )(lengths.astype(jnp.int32), q[:, :, None, :], cache_k, cache_v)
    return out[:, :, 0, :]


def pass_bytes(b: int, h: int, c: int, d: int, cache_dtype) -> int:
    """DMA model of one fused decode step: K+V cache pages read once in
    their STORED dtype (no fp32 copy), q read and out written once —
    kernel_bench's pallas arm for the gen_decode roofline A/B."""
    csz = jnp.dtype(cache_dtype).itemsize
    return 2 * b * h * c * d * csz + b * h * d * csz + b * h * d * 4 + b * 4
