"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no sequence-scaling mechanism at all — its only attention
runs on a fixed 196-token grid (ref: /root/reference/distribuuuu/models/
botnet.py:270-281, hard-asserted shape; SURVEY.md §5.7). This module is the
TPU-native capability the reference lacks: attention over sequences sharded
across the ``seq`` mesh axis, so context length scales with chips.

Two strategies, both built on XLA collectives riding ICI:

- **Ring attention** (Liu et al., arXiv:2310.01889): each device holds one
  query block and rotates K/V blocks around the ring with ``ppermute``,
  accumulating exact softmax attention with the online (flash) update. The
  K/V transfer for step ``i+1`` overlaps the block computation of step ``i``
  under XLA's latency-hiding scheduler. Exact — not an approximation.
- **Ulysses all-to-all** (arXiv:2309.14509): ``all_to_all`` re-shards
  sequence→heads, computes full attention locally on a head subset, and
  re-shards back. Cheaper at moderate sequence lengths; requires
  ``heads % seq_axis_size == 0``.

Both are pure functions of ``[B, H, S_shard, D]`` blocks designed to be
called inside ``shard_map`` (the mesh-axis name bound); ``ring_attention`` /
``ulysses_attention`` are the host-level wrappers that bind a mesh.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


_NEG_BIG = -0.7 * float(jnp.finfo(jnp.float32).max)  # safe additive -inf


def _block_update(q, k, v, m, l, o, scale, mask):
    """One online-softmax accumulation step over a K/V block.

    q: [B,H,Sq,D]; k,v: [B,H,Sk,D]; m,l: [B,H,Sq] running max / normalizer;
    o: [B,H,Sq,Dv] unnormalized accumulator; mask: [Sq,Sk] bool or None.

    The whole update is a deliberate f32 region (the ``_fp32`` scope is
    the dtype lint's self-declaration convention): the running
    (m, l, o) logsumexp state must accumulate in f32 across up to n
    rotations — bf16 would round the correction products once per hop.
    """
    with jax.named_scope("ring_softmax_fp32"):
        s = jnp.einsum(
            "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
        ) * scale
        if mask is not None:
            s = jnp.where(mask[None, None], s, _NEG_BIG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # exp of masked-out logits underflows to 0 via the _NEG_BIG shift
        p = jnp.exp(s - m_new[..., None])
        if mask is not None:
            p = jnp.where(mask[None, None], p, 0.0)
        corr = jnp.exp(m - m_new)
        l_new = corr * l + p.sum(axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, o_new


def _flash_state_update(q, kb, vb, m, l, o, scale, causal, interpret):
    """One online-softmax accumulation step computed by the Pallas flash
    kernel (r4). ``(o_b, lse_b)`` fully characterizes the block's softmax
    state as ``(m=lse_b, l=1, o_unnorm=o_b)``, which merges exactly with
    the running (m, l, o) — so ring attention's per-rotation updates get
    the kernel's VMEM tiling (no [Sq, Sk] logits materialized in HBM) and
    its fwd+bwd win. Gradients are exact: flash_attention_with_lse carries
    a vjp for BOTH outputs."""
    from distribuuuu_tpu.ops import flash_attention as fa

    # v upcast: the kernel writes o in v.dtype — bf16 v would round the
    # block output once per rotation before the f32 merge, a numerics
    # regression vs the all-f32 einsum path. f32 v keeps the accumulator
    # chain f32 end-to-end (scores still take the bf16-input MXU path);
    # the einsum ring pays full-f32 everywhere, so this still wins.
    o_b, lse_b = fa.flash_attention_with_lse(
        q, kb, vb.astype(jnp.float32), scale=scale, causal=causal,
        interpret=interpret,
    )
    m_new = jnp.maximum(m, lse_b)
    corr = jnp.exp(m - m_new)
    corr_b = jnp.exp(lse_b - m_new)
    l_new = corr * l + corr_b
    o_new = o * corr[..., None] + o_b * corr_b[..., None]
    return m_new, l_new, o_new


def _ring_flash_fits(q, k):
    """Whether the per-device shard can run the flash block path: head dim
    within lane tiling, equal q/k shards, and the whole-shard VMEM
    residency bound of the kernel (ops/flash_attention docstring)."""
    from distribuuuu_tpu.ops import flash_attention as fa

    d = q.shape[-1]
    L = q.shape[2]
    # itemsize 4: _flash_state_update hands v over in float32
    return d <= 128 and k.shape[2] == L and fa.fits_vmem(L, d, 4)


def ring_self_attention(
    q, k, v, *, axis_name: str = "seq", causal: bool = False,
    scale: float | None = None, impl: str = "auto",
):
    """Exact attention over a ring-sharded sequence. Call inside shard_map.

    q, k, v: [B, H, S_shard, D] — this device's sequence block; the global
    sequence is the concatenation of blocks in mesh-axis order. Returns
    [B, H, S_shard, Dv] in v.dtype.

    ``impl``: ``"einsum"`` — the original whole-block einsum update;
    ``"flash"`` — per-rotation block updates through the Pallas flash
    kernel (``_flash_state_update``; Pallas interpreter off-TPU — tests);
    ``"auto"`` — flash on TPU when the shard fits the kernel's bounds,
    einsum otherwise. In causal mode the flash path also SKIPS
    fully-masked source blocks via ``lax.cond`` (the einsum path computes
    and masks them), and the local block runs the kernel's causal
    block-skip — ring + causal flash composition (VERDICT r3 #4).
    """
    n = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scale = d ** -0.5 if scale is None else scale
    with jax.named_scope("ring_softmax_fp32"):
        qf = q.astype(jnp.float32)

    if impl not in ("auto", "einsum", "flash"):
        raise ValueError(f"ring impl must be auto|einsum|flash, got {impl!r}")
    use_flash = impl == "flash" or (
        impl == "auto"
        and jax.default_backend() == "tpu"
        and v.shape[-1] == d
        and _ring_flash_fits(q, k)
    )
    if use_flash and (v.shape[-1] != d or sk != sq):
        raise ValueError(
            f"ring flash path needs Dv == D and equal q/k shards, got "
            f"D={d} Dv={v.shape[-1]} Sq={sq} Sk={sk}"
        )

    m0 = jnp.full((b, h, sq), _NEG_BIG, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    o0 = jnp.zeros((b, h, sq, v.shape[-1]), jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]
    q_pos = my_idx * sq + jnp.arange(sq)

    def block_mask(src):
        if not causal:
            return None
        k_pos = src * sk + jnp.arange(sk)
        return q_pos[:, None] >= k_pos[None, :]

    # local block first (no rotation needed), then n-1 rotate-and-update
    # steps. The local block is the (only) diagonal one: under flash it is
    # the statically-causal kernel call.
    if use_flash:
        m, l, o = _flash_state_update(
            q, k, v, m0, l0, o0, scale, causal, None
        )
    else:
        with jax.named_scope("ring_softmax_fp32"):
            m, l, o = _block_update(qf, k.astype(jnp.float32), v, m0, l0,
                                    o0, scale, block_mask(my_idx))

    def step(carry, step_idx):
        m, l, o, kb, vb = carry
        # rotate K/V from the previous device; XLA's latency-hiding scheduler
        # overlaps the transfer with the previous iteration's compute
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        # after `step_idx` rotations this device holds block (my_idx - step_idx)
        src = (my_idx - step_idx) % n
        if use_flash:
            # rotated blocks are never diagonal (step_idx ∈ [1, n-1]):
            # under causal they are fully visible (src < my_idx) or fully
            # masked (src > my_idx) — skip the latter outright
            def upd(args):
                m, l, o = args
                return _flash_state_update(
                    q, kb, vb, m, l, o, scale, False, None
                )

            if causal:
                m, l, o = jax.lax.cond(
                    src < my_idx, upd, lambda args: args, (m, l, o)
                )
            else:
                m, l, o = upd((m, l, o))
        else:
            with jax.named_scope("ring_softmax_fp32"):
                m, l, o = _block_update(qf, kb.astype(jnp.float32), vb, m,
                                        l, o, scale, block_mask(src))
        return (m, l, o, kb, vb), None

    if n > 1:
        (m, l, o, _, _), _ = jax.lax.scan(
            step, (m, l, o, k, v), jnp.arange(1, n)
        )
    with jax.named_scope("ring_softmax_fp32"):
        out = o / jnp.maximum(l, 1e-30)[..., None]
        return out.astype(v.dtype)


def ulysses_self_attention(
    q, k, v, *, axis_name: str = "seq", causal: bool = False,
    scale: float | None = None,
):
    """All-to-all sequence parallelism. Call inside shard_map.

    Re-shards [B, H, S_shard, D] → [B, H/n, S_full, D] with one all_to_all,
    runs full (flash-style fp32-softmax) attention on the local head subset,
    and re-shards back. heads must divide by the axis size.
    """
    n = jax.lax.axis_size(axis_name)
    assert q.shape[1] % n == 0, (
        f"heads {q.shape[1]} not divisible by seq axis {n}"
    )
    # seq-sharded → head-sharded (gather full sequence, scatter heads)
    q, k, v = (
        jax.lax.all_to_all(t, axis_name, split_axis=1, concat_axis=2,
                           tiled=True)
        for t in (q, k, v)
    )
    d = q.shape[-1]
    scale = d ** -0.5 if scale is None else scale
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ) * scale
    if causal:
        sl = s.shape[-1]
        mask = jnp.tril(jnp.ones((sl, sl), bool))
        s = jnp.where(mask[None, None], s, _NEG_BIG)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhqk,bhkd->bhqd", w, v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(v.dtype)
    # head-sharded → seq-sharded
    return jax.lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)


def _spec(mesh: Mesh, data_axis: str | None, seq_axis: str):
    data = data_axis if data_axis and data_axis in mesh.axis_names else None
    return P(data, None, seq_axis, None)


def ring_attention(
    q, k, v, mesh: Mesh, *, seq_axis: str = "seq",
    data_axis: str | None = "data", causal: bool = False,
    scale: float | None = None, impl: str = "auto",
):
    """Host-level ring attention: q,k,v are global [B, H, S, D] arrays with S
    sharded over ``seq_axis`` (and B optionally over ``data_axis``).
    ``impl`` routes the per-rotation block updates (see
    :func:`ring_self_attention`): flash kernel on TPU by default."""
    spec = _spec(mesh, data_axis, seq_axis)
    fn = functools.partial(
        ring_self_attention, axis_name=seq_axis, causal=causal, scale=scale,
        impl=impl,
    )
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def ulysses_attention(
    q, k, v, mesh: Mesh, *, seq_axis: str = "seq",
    data_axis: str | None = "data", causal: bool = False,
    scale: float | None = None,
):
    """Host-level Ulysses attention over a ``seq``-sharded sequence."""
    spec = _spec(mesh, data_axis, seq_axis)
    fn = functools.partial(
        ulysses_self_attention, axis_name=seq_axis, causal=causal, scale=scale
    )
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


def blockwise_attention(q, k, v, *, chunk: int = 256, causal: bool = False,
                        scale: float | None = None, remat: bool = True,
                        window: int | None = None,
                        diffusion_block: int | None = None):
    """Single-device flash-style attention: exact softmax in O(L·chunk)
    memory instead of the dense path's O(L²) logits (Rabe & Staats,
    arXiv:2112.05682; the single-chip sibling of ring attention — same
    ``_block_update`` online-softmax math, ``lax.scan`` over local K/V
    chunks instead of ``ppermute`` hops around a mesh ring).

    This is what makes high-resolution ViT trainable on one chip: at
    L=4096 the dense attention materializes ~L²·H·B bf16 logits per layer
    (hundreds of MB) while this keeps only the running (m, l, o) state plus
    one [L, chunk] block. ``remat=True`` recomputes each chunk's block in
    the backward pass, so autodiff never stores the probabilities either.

    ``window`` (with ``causal``): query t reads the keys s with ``t - window
    < s <= t``, as ``ops/flash_attention.py``'s kernels; every chunk is
    still walked (the mask alone says it: this is the fallback, not the
    fast path). ``diffusion_block`` (with ``causal``): the block-diffusion
    mask over a noised and a clean copy of a sequence
    (``ops/flash_attention.diffusion_mask``), likewise by the mask alone.

    q, k: [B, H, L, D]; v: [B, H, L, Dv]. Returns [B, H, L, Dv] in v.dtype.
    """
    b, h, L, d = q.shape
    dv = v.shape[-1]
    scale = d ** -0.5 if scale is None else scale
    chunk = min(chunk, L)
    nc = -(-L // chunk)
    pad = nc * chunk - L
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    # [nc, B, H, chunk, D] so scan slices one K/V chunk per step
    ks = jnp.moveaxis(kp.reshape(b, h, nc, chunk, d), 2, 0)
    vs = jnp.moveaxis(vp.reshape(b, h, nc, chunk, dv), 2, 0)

    qf = q.astype(jnp.float32)
    q_pos = jnp.arange(L)
    m0 = jnp.full((b, h, L), _NEG_BIG, jnp.float32)
    l0 = jnp.zeros((b, h, L), jnp.float32)
    o0 = jnp.zeros((b, h, L, dv), jnp.float32)
    need_pad_mask = pad > 0

    def step(carry, inp):
        m, l, o = carry
        idx, kb, vb = inp
        k_pos = idx * chunk + jnp.arange(chunk)
        mask = None
        if diffusion_block is not None:
            from distribuuuu_tpu.ops import flash_attention as fa

            mask = fa.diffusion_mask(L, diffusion_block, k_pos)
        elif causal or need_pad_mask:
            mask = jnp.broadcast_to((k_pos < L)[None, :], (L, chunk))
            if causal:
                mask = mask & (q_pos[:, None] >= k_pos[None, :])
            if window is not None:
                mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
        m, l, o = _block_update(
            qf, kb.astype(jnp.float32), vb, m, l, o, scale, mask
        )
        return (m, l, o), None

    step_fn = jax.checkpoint(step) if remat else step
    (m, l, o), _ = jax.lax.scan(
        step_fn, (m0, l0, o0), (jnp.arange(nc), ks, vs)
    )
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(v.dtype)


def reference_attention(q, k, v, *, causal: bool = False,
                        scale: float | None = None):
    """Single-device exact attention — the numerics oracle for the tests."""
    d = q.shape[-1]
    scale = d ** -0.5 if scale is None else scale
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if causal:
        sl = s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((sl, sl), bool))[None, None], s,
                      _NEG_BIG)
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v.astype(jnp.float32)).astype(
        v.dtype
    )
