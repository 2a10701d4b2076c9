"""Pallas flash attention (ops/flash_attention.py) — VERDICT r1 item 4.

Correctness on the CPU mesh runs the kernels through the Pallas interpreter
(``interpret=True``) against the dense float32 reference — the forward AND
the fused backward (dq, dk, dv from one walk), including the padded (L not a
block multiple) case whose masked keys are the easy thing to get wrong, and
the split of every walk into masked and wholly kept tiles.

Speed (tools/flash_bench.py on a v5e, PERF.md) is hardware-gated and not
asserted here.
"""

import functools
import json
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distribuuuu_tpu.ops import flash_attention as fa
from distribuuuu_tpu.ops.ring_attention import reference_attention
from decoder_contract import forward_matmuls

BLK = dict(blk_q=256, blk_k=256)


@pytest.mark.parametrize(
    "B,H,L,D",
    [
        (2, 3, 512, 64),   # block multiple
        (1, 2, 300, 64),   # padded L (masked keys + padded q rows)
        (2, 2, 640, 32),   # L > blk, not a multiple; small head dim
        (1, 2, 512, 256),  # two lane tiles a head: latent attention's dim
    ],
)
def test_forward_matches_reference(B, H, L, D):
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, H, L, D)), jnp.float32)
        for _ in range(3)
    )
    out = fa.flash_attention(q, k, v, interpret=True, **BLK)
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def _grads(fn, q, k, v, w):
    return jax.grad(
        lambda q, k, v: jnp.sum(fn(q, k, v) * w), argnums=(0, 1, 2)
    )(q, k, v)


@pytest.mark.parametrize(
    "B,H,L,D,causal,blk_q,blk_k",
    [
        (1, 2, 512, 64, False, 256, 256),   # block multiple
        (1, 2, 300, 64, False, 128, 128),   # padded keys
        (1, 2, 512, 64, True, 256, 256),    # diagonal tiles and kept tiles
        (1, 2, 300, 64, True, 384, 128),    # causal and padding compose
        (2, 2, 512, 128, True, 256, 128),   # blk_q > blk_k; dq zeroed a head
        (2, 2, 512, 128, True, 128, 256),   # blk_q < blk_k
        (1, 2, 257, 128, True, 128, 384),   # a class token's padding: lp 384
        (1, 2, 257, 64, False, 384, 128),
        (2, 3, 384, 128, False, 128, 384),  # several heads, no mask at all
        (1, 2, 512, 256, True, 256, 256),   # head dim 256: models/glm_moe.py
        (1, 2, 300, 256, True, 128, 384),   # ... with padded keys, blk_q < blk_k
        (1, 2, 384, 256, False, 384, 128),  # ... and no mask at all
    ],
)
def test_gradients_match_reference(B, H, L, D, causal, blk_q, blk_k):
    """The fused backward (one walk: the scores, ``exp`` and ``dp`` once a
    tile; dq accumulated across the key blocks of each batch·head) against
    the dense float32 reference."""
    rng = np.random.default_rng(1 + causal)
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, H, L, D)), jnp.float32)
        for _ in range(3)
    )
    w = jnp.asarray(rng.standard_normal((D,)), jnp.float32)
    assert fa._resolve_blocks(L, blk_q, blk_k)[:2] == (blk_q, blk_k)
    gf = _grads(
        lambda q, k, v: fa.flash_attention(
            q, k, v, causal=causal, interpret=True, blk_q=blk_q, blk_k=blk_k),
        q, k, v, w,
    )
    gr = _grads(
        lambda q, k, v: reference_attention(q, k, v, causal=causal), q, k, v, w)
    for a, b, name in zip(gf, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, err_msg=name
        )


def test_a_head_dim_past_128_runs_in_whole_lane_tiles_only():
    """256 runs (the cases above); 192 is refused by every entry, and the
    residency bound halves with the dim: 8192 tokens fit at 256, 16,384 at
    128, neither at twice that."""
    q = jnp.zeros((1, 1, 256, 192), jnp.float32)
    for entry in (fa.flash_attention, fa.flash_attention_with_lse):
        with pytest.raises(ValueError, match="no multiple of the 128 lanes"):
            entry(q, q, q, interpret=True)
    assert fa.fits_vmem(8192, 256) and not fa.fits_vmem(16384, 256)
    assert fa.fits_vmem(16384, 128) and not fa.fits_vmem(32768, 128)


def test_cpu_fallback_is_blockwise():
    """Off-TPU the public entry point must run (and agree) without Pallas."""
    rng = np.random.default_rng(2)
    q, k, v = (
        jnp.asarray(rng.standard_normal((1, 2, 256, 32)), jnp.float32)
        for _ in range(3)
    )
    out = fa.flash_attention(q, k, v)  # backend is cpu in tests → fallback
    ref = reference_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize(
    "B,H,L,D",
    [
        (2, 2, 512, 64),   # block multiple: exercises the block-skip bounds
        (1, 2, 300, 32),   # padded L: causal ∧ pad masks compose
    ],
)
def test_causal_forward_matches_reference(B, H, L, D):
    """Causal in-kernel (r4): fully-masked K blocks are skipped by loop
    bound, diagonal blocks masked elementwise — must equal dense causal."""
    rng = np.random.default_rng(4)
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, H, L, D)), jnp.float32)
        for _ in range(3)
    )
    out = fa.flash_attention(q, k, v, causal=True, interpret=True, **BLK)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_causal_matches_blockwise_scan():
    """The causal kernel against the scan path it previously fell back to
    (the VERDICT r3 #4 'exactness test vs the causal blockwise path')."""
    from distribuuuu_tpu.ops.ring_attention import blockwise_attention

    rng = np.random.default_rng(6)
    q, k, v = (
        jnp.asarray(rng.standard_normal((1, 3, 384, 64)), jnp.float32)
        for _ in range(3)
    )
    out = fa.flash_attention(q, k, v, causal=True, interpret=True, **BLK)
    ref = blockwise_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("L,causal", [(256, False), (300, True)])
def test_with_lse_matches_and_differentiates(L, causal):
    """flash_attention_with_lse: the lse output equals the dense
    log-sum-exp, and a loss that consumes BOTH outputs gets exact
    gradients (the non-zero lse cotangent folds into the fused backward's
    delta — the property ring attention's flash block updates rely on),
    padded and causal too."""
    rng = np.random.default_rng(7)
    B, H, D = 1, 2, 32
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, H, L, D)), jnp.float32)
        for _ in range(3)
    )
    scale = D ** -0.5

    def scores(q, k):
        s = jnp.einsum(
            "bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32
        ) * scale
        return jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -1e30) if causal else s

    o, lse = fa.flash_attention_with_lse(
        q, k, v, causal=causal, interpret=True, **BLK)
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(jax.nn.logsumexp(scores(q, k), axis=-1)),
        atol=2e-5,
    )
    np.testing.assert_allclose(
        np.asarray(o), np.asarray(reference_attention(q, k, v, causal=causal)),
        atol=2e-5,
    )

    wo = jnp.asarray(rng.standard_normal((D,)), jnp.float32)

    def loss_flash(q, k, v):
        o, lse = fa.flash_attention_with_lse(
            q, k, v, causal=causal, interpret=True, **BLK
        )
        return jnp.sum(o * wo) + jnp.sum(jnp.sin(lse))

    def loss_ref(q, k, v):
        s = scores(q, k)
        o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        return jnp.sum(o * wo) + jnp.sum(jnp.sin(jax.nn.logsumexp(s, -1)))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-5, err_msg=name
        )


# ------------------------------------------- what a recomputed block keeps
_SHAPE = (1, 2, 256, 64)


def _loss(entry):
    """A block's use of the kernel: a projection into it (so that the
    recomputation has something of its own to run) and one out of it."""
    def loss(x, w):
        q = x * w
        out = entry(q, 0.5 * q, q + 1, causal=True, interpret=True, **BLK)
        return sum((t.astype(jnp.float32) ** 2).sum() for t in jax.tree.leaves(out))

    return loss


def _local_rule(with_lse, trapped):
    """A local copy of the forward rule that names the kernel's output and
    log-sum-exp and NOT its inputs: the parent's rule (PR 34's), or with
    ``trapped`` one that names only the residuals, its primal output the
    kernel's own, un-named ``o``."""
    args = (_SHAPE[-1] ** -0.5, True, 256, 256, True)

    def primal(o, lse):
        return (o, lse[:, 0].reshape(-1, *_SHAPE[1:3])) if with_lse else o

    @jax.custom_vjp
    def attend(q, k, v):
        return primal(*fa._flash_forward(q, k, v, *args)[:2])

    def fwd(q, k, v):
        o, lse, (qf, kf, vf) = fa._flash_forward(q, k, v, *args)
        kept, lse = (fa.checkpoint_name(t, name)
                     for t, name in zip((o, lse), fa.KEPT_UNDER_REMAT))
        return primal(o if trapped else kept, lse), (qf, kf, vf, lse, kept, q.shape)

    def bwd(res, g):
        g_o, g_lse = g if with_lse else (g, None)
        if with_lse:
            g_lse = g_lse.astype(jnp.float32).reshape(res[3].shape)
        return fa._flash_backward(res, g_o, *args, g_lse=g_lse)

    attend.defvjp(fwd, bwd)
    return lambda q, k, v, **kw: attend(q, k, v)


def _forward_calls(fn, *args) -> int:
    """``dtpu_flash_fwd`` calls in the gradient's jaxpr, forward and
    backward together."""
    text = str(jax.make_jaxpr(jax.grad(fn, argnums=(0, 1)))(*args))
    assert text.count("name=dtpu_flash_bwd") == 1
    return text.count("name=dtpu_flash_fwd")


@pytest.mark.parametrize("with_lse", [False, True], ids=["o", "o_and_lse"])
@pytest.mark.parametrize("arm,calls", [
    ("kept", 1), ("plain_checkpoint", 2), ("only_the_residual_named", 2)])
def test_a_checkpoint_that_keeps_the_names_runs_the_forward_kernel_once(
        arm, calls, with_lse):
    """Under ``save_only_these_names(*KEPT_UNDER_REMAT)`` the recomputation
    has no use for ``dtpu_flash_fwd``; under a plain ``jax.checkpoint`` it
    runs again; and it runs again too where the rule names its residual
    ``o`` and returns the un-named one as the primal output (the trap: the
    block's output projection reads THAT). The gradients are the same bits
    whatever is kept."""
    entry = fa.flash_attention_with_lse if with_lse else fa.flash_attention
    policy = jax.checkpoint_policies.save_only_these_names(*fa.KEPT_UNDER_REMAT)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal(_SHAPE), jnp.float32)
    w = jnp.asarray(1 + 0.1 * rng.standard_normal(_SHAPE[-1]), jnp.float32)
    loss = _loss(entry)
    wrapped = {
        "kept": jax.checkpoint(loss, policy=policy),
        "plain_checkpoint": jax.checkpoint(loss),
        "only_the_residual_named": jax.checkpoint(
            _loss(_local_rule(with_lse, trapped=True)), policy=policy),
    }[arm]
    assert _forward_calls(loss, x, w) == 1
    assert _forward_calls(wrapped, x, w) == calls
    for got, want in zip(jax.grad(wrapped, argnums=(0, 1))(x, w),
                         jax.grad(loss, argnums=(0, 1))(x, w)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_names_are_kept_through_a_data_ranks_shard_map():
    """A model on a mesh hands it over and every data rank runs the kernel
    under ``shard_map``: the policy sees the names inside it too, and the
    recomputation has no forward kernel there either."""
    from distribuuuu_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.build_mesh(data=8)
    policy = jax.checkpoint_policies.save_only_these_names(*fa.KEPT_UNDER_REMAT)
    loss = _loss(functools.partial(fa.flash_attention, mesh=mesh))
    x = jnp.asarray(
        np.random.default_rng(5).standard_normal((8, *_SHAPE[1:])), jnp.float32)
    w = jnp.ones(_SHAPE[-1], jnp.float32)
    assert "shard_map" in str(jax.make_jaxpr(loss)(x, w))
    assert _forward_calls(jax.checkpoint(loss, policy=policy), x, w) == 1
    assert _forward_calls(jax.checkpoint(loss), x, w) == 2
    for got, want in zip(
            jax.grad(jax.checkpoint(loss, policy=policy), argnums=(0, 1))(x, w),
            jax.grad(loss, argnums=(0, 1))(x, w)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


_DIM = 48  # a block's width: not H·d, so W_o's shape is not a projection's


def _block(entry):
    """A block's shape of work, ``projection → flash → W_o``, on ``x [B, L,
    _DIM]``: each of q, k and v its own matmul and head layout."""
    _, H, _, d = _SHAPE

    def loss(x, wq, wk, wv, wo):
        B, L, _ = x.shape

        def heads(w):
            return (x @ w).reshape(B, L, H, d).transpose(0, 2, 1, 3)

        out = entry(heads(wq), heads(wk), heads(wv), causal=True,
                    interpret=True, **BLK)
        o, *lse = jax.tree.leaves(out)
        y = o.transpose(0, 2, 1, 3).reshape(B, L, H * d) @ wo
        return (y ** 2).sum() + sum((t ** 2).sum() for t in lse)

    return loss


def _block_args(batch=1, seed=7):
    rng = np.random.default_rng(seed)
    _, H, L, d = _SHAPE

    def normal(*shape):
        return jnp.asarray(0.2 * rng.standard_normal(shape), jnp.float32)

    return (normal(batch, L, _DIM), *(normal(_DIM, H * d) for _ in range(3)),
            normal(H * d, _DIM))


def _projections(fn, *args) -> int:
    """The FORWARD matmuls of q, k and v (``x [B, L, _DIM] · w [_DIM, H·d]``)
    in the gradient's jaxpr, forward and backward together; the backward
    kernel is there once."""
    jaxpr = jax.make_jaxpr(jax.grad(fn, argnums=tuple(range(len(args)))))(*args)
    assert str(jaxpr).count("name=dtpu_flash_bwd") == 1
    return forward_matmuls(jaxpr.jaxpr, {args[1].shape})


def _same_bits(got, want):
    for a, b in zip(got, want, strict=True):
        assert float(jnp.abs(b).max()) > 0
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("with_lse", [False, True], ids=["o", "o_and_lse"])
@pytest.mark.parametrize("arm,projections", [
    ("kept", 3), ("plain_checkpoint", 6), ("only_o_and_lse_named", 6)])
def test_a_checkpoint_that_keeps_the_names_projects_q_k_and_v_once(
        arm, projections, with_lse):
    """Under ``save_only_these_names(*KEPT_UNDER_REMAT)`` the recomputation
    of a block ``projection → flash → W_o`` makes no q, k or v again (the
    backward kernel reads the kept ones, and a projection's own backward
    reads its input); under a plain ``jax.checkpoint`` each projection runs
    twice, and twice too under the policy where the rule names the kernel's
    output and log-sum-exp alone (the parent's rule). The gradients are the
    same bits whatever is kept."""
    entry = fa.flash_attention_with_lse if with_lse else fa.flash_attention
    policy = jax.checkpoint_policies.save_only_these_names(*fa.KEPT_UNDER_REMAT)
    args = _block_args()
    loss = _block(entry)
    wrapped = {
        "kept": jax.checkpoint(loss, policy=policy),
        "plain_checkpoint": jax.checkpoint(loss),
        "only_o_and_lse_named": jax.checkpoint(
            _block(_local_rule(with_lse, trapped=False)), policy=policy),
    }[arm]
    assert _projections(loss, *args) == 3
    assert _projections(wrapped, *args) == projections
    assert _forward_calls(wrapped, *args) == (2 if arm == "plain_checkpoint" else 1)
    everything = tuple(range(len(args)))
    _same_bits(jax.grad(wrapped, argnums=everything)(*args),
               jax.grad(jax.checkpoint(loss), argnums=everything)(*args))


def test_q_k_and_v_are_kept_through_a_data_ranks_shard_map():
    """The same block on a mesh, every data rank running the kernels on its
    own sequences under ``shard_map``: the policy sees the three names
    inside it, and no projection runs again."""
    from distribuuuu_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.build_mesh(data=8)
    policy = jax.checkpoint_policies.save_only_these_names(*fa.KEPT_UNDER_REMAT)
    loss = _block(functools.partial(fa.flash_attention, mesh=mesh))
    args = _block_args(batch=8)
    assert "shard_map" in str(jax.make_jaxpr(loss)(*args))
    assert _projections(loss, *args) == 3
    assert _projections(jax.checkpoint(loss, policy=policy), *args) == 3
    assert _projections(jax.checkpoint(loss), *args) == 6
    everything = tuple(range(len(args)))
    _same_bits(jax.grad(jax.checkpoint(loss, policy=policy), argnums=everything)(*args),
               jax.grad(jax.checkpoint(loss), argnums=everything)(*args))


@pytest.mark.parametrize("block", [False, True], ids=["elementwise", "projected"])
@pytest.mark.parametrize("with_lse", [False, True], ids=["o", "o_and_lse"])
def test_the_names_are_no_operation_where_no_checkpoint_asks(
        monkeypatch, with_lse, block):
    """A gradient through the kernels with no ``jax.checkpoint`` around them
    (``olmoe_1b_7b.train_seq4096``'s case): the lowered text holds nothing
    for the five names, and is the text of rules that name nothing."""
    entry = fa.flash_attention_with_lse if with_lse else fa.flash_attention
    if block:
        loss, args = _block(entry), [t.astype(jnp.bfloat16) for t in _block_args()]
    else:
        loss = _loss(entry)
        args = [jnp.ones(_SHAPE, jnp.bfloat16), jnp.ones(_SHAPE[-1], jnp.bfloat16)]

    def lowered():
        text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*args).as_text()
        return re.sub(r"_\d+\b", "", text)  # the counters in private functions' names

    named = lowered()
    assert "name=dtpu_flash_fwd" not in named  # lowered: no jaxpr syntax
    assert set(fa.KEPT_UNDER_REMAT) >= {"flash_o", "flash_lse", "flash_q", "flash_k", "flash_v"}
    assert not any(name in named for name in fa.KEPT_UNDER_REMAT)
    monkeypatch.setattr(fa, "checkpoint_name", lambda t, name: t)
    assert lowered() == named


def test_kept_bytes_are_the_five_arrays_where_the_kernel_runs(monkeypatch):
    """``kept_under_remat_bytes`` at the two cells' shapes (bf16 ``o``, the
    float32 ``lse`` and q, k and v at the padded length), and 0 wherever
    ``flash_attention`` itself takes the scan: off the TPU, in a program
    across devices with no ``shard_map`` a data rank, past the VMEM bound."""
    from distribuuuu_tpu.ops import pallas as tier
    from distribuuuu_tpu.parallel import mesh as mesh_lib

    ouro, glm = (1, 16, 4096, 128), (1, 20, 8192, 256)
    assert fa.kept_under_remat_bytes(ouro, 2) == 0  # the CPU: the scan path
    monkeypatch.setattr(tier, "interpret_mode", lambda: False)
    mesh = mesh_lib.build_mesh(data=8)
    assert jax.device_count() == 8 and fa.kept_under_remat_bytes(ouro, 2) == 0
    assert fa.kept_under_remat_bytes(ouro, 2, mesh) == 0  # 1 sequence, 8 ranks
    with tier.single_device_program():
        # o and lse, then q, k and v: 16.25 + 48 MiB and 80.6 + 240 MiB a call
        assert fa.kept_under_remat_bytes(ouro, 2) == 16 * 4096 * (128 * 2 + 4 + 3 * 128 * 2)
        assert fa.kept_under_remat_bytes(ouro, 2) * 32 == 2_155_872_256
        assert fa.kept_under_remat_bytes(glm, 2) == 20 * 8192 * (256 * 2 + 4 + 3 * 256 * 2)
        assert fa.kept_under_remat_bytes(glm, 2) * 6 == 2_017_198_080
        # o at its 300 rows; lse, q, k and v at the 384 the kernels take
        assert fa.kept_under_remat_bytes((1, 3, 300, 64), 4) == 3 * (
            300 * 64 * 4 + 384 * 4 + 3 * 384 * 64 * 4)
        assert fa.kept_under_remat_bytes((1, 1, 65536, 128), 2) == 0  # fits_vmem
    assert fa.kept_under_remat_bytes((8, *ouro[1:]), 2, mesh) == 8 * 16 * 4096 * (260 + 768)
    # the same answers as the routing itself
    for shape, m in ((ouro, None), ((8, *ouro[1:]), mesh), (ouro, mesh)):
        q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        text = str(jax.make_jaxpr(
            lambda q: fa.flash_attention(q, q, q, causal=True, mesh=m))(q))
        assert ("dtpu_flash_fwd" in text) == bool(fa.kept_under_remat_bytes(shape, 2, m))
    tier.reset_selection()


def _kept_keys(lp, L, causal):
    """What the mask means, a query row at a time: row i keeps the keys
    ``[0, end[i]]``."""
    return np.minimum(np.arange(lp), L - 1) if causal else np.full(lp, L - 1)


@pytest.mark.parametrize("causal", [False, True])
def test_the_kept_key_intervals_are_the_kernels_mask(causal):
    """``_kept_keys`` (the next test's yardstick) against ``_keep``, the
    expression both kernels mask with, score by score."""
    for L in (1, 127, 128, 129, 300, 384):
        lp = fa._round_up(L, 128)
        keep = np.asarray(fa._keep(
            jnp.arange(lp)[:, None], jnp.arange(lp)[None, :], L, causal))
        end = _kept_keys(lp, L, causal)
        assert (keep == (np.arange(lp)[None, :] <= end[:, None])).all(), L


@pytest.mark.parametrize("causal", [False, True])
def test_wholly_kept_tiles_are_exactly_those_the_mask_leaves_untouched(causal):
    """Every ``(L, blk_q, blk_k)`` the block-choosing function can return
    up to L = 8192 (each 128-multiple and its neighbours, d = 64 and 128),
    and some it cannot: the forward's walk (``_key_tiles``) and the
    backward's (``_first_query_tile``) visit exactly the tiles that keep a
    score, the tiles counted as wholly kept are exactly those that drop
    none, and ``tile_counts`` counts both."""
    shapes = {
        (L, *fa._resolve_blocks(L, *fa.choose_blocks(L, d, causal))[:2])
        for m in range(128, 8193, 128) for L in (m - 1, m, m + 1)
        for d in (64, 128) if L <= 8192
    } | {(300, 128, 384), (300, 384, 128), (1024, 256, 512), (1000, 512, 128),
         (257, 128, 128), (4097, 384, 128)}
    assert len(shapes) > 190
    for L, blk_q, blk_k in sorted(shapes):
        lp = fa._round_up(L, 128)
        nq, nk = lp // blk_q, lp // blk_k
        end = _kept_keys(lp, L, causal).reshape(nq, blk_q)
        first = np.arange(nk) * blk_k  # a key tile's first and last key
        last = first + blk_k - 1
        any_kept = first[None, :] <= end.max(axis=1)[:, None]  # [nq, nk]
        all_kept = last[None, :] <= end.min(axis=1)[:, None]
        assert any_kept[:, 0].all()  # every row sees key 0
        for j in range(nq):
            full, hi = fa._key_tiles(j, blk_q, blk_k, lp, L, causal)
            assert (any_kept[j] == (np.arange(nk) < hi)).all(), (L, blk_q, blk_k, j)
            assert (all_kept[j] == (np.arange(nk) < full)).all(), (L, blk_q, blk_k, j)
        for j in range(nk):
            lo = fa._first_query_tile(j, blk_q, blk_k, causal)
            assert (any_kept[:, j] == (np.arange(nq) >= lo)).all(), (L, blk_q, blk_k, j)
        assert fa.tile_counts(L, blk_q, blk_k, causal) == (
            any_kept.sum(), (any_kept & ~all_kept).sum())


def test_select_and_fallback_say_what_ran_with_which_tiles(tmp_path):
    """``kernel.select`` op ``flash_attn`` once a traced shape, with the
    blocks and tile counts; ``kernel.fallback`` with the reason where
    ``blockwise_attention`` runs instead."""
    from distribuuuu_tpu.ops import pallas as tier
    from distribuuuu_tpu.telemetry import schema, spans

    q = jax.ShapeDtypeStruct((1, 16, 4096, 128), jnp.bfloat16)
    long = jax.ShapeDtypeStruct((1, 1, 65536, 128), jnp.bfloat16)
    tier.reset_selection()
    path = spans.setup_telemetry(str(tmp_path), rank=0)
    try:
        for interpret, x in ((None, q), (True, q), (True, q), (False, long)):
            jax.eval_shape(lambda q: fa.flash_attention(
                q, q, q, causal=True, interpret=interpret), x)
    finally:
        spans.close_telemetry()
        tier.reset_selection()
    records = [json.loads(line) for line in open(path)]
    for record in records:
        if record.get("kind", "").startswith("kernel."):
            schema.validate_record(record)
    selected = [r for r in records if r.get("kind") == "kernel.select"]
    fell = [r for r in records if r.get("kind") == "kernel.fallback"]
    assert [(r["op"], r["impl"], r["requested"]) for r in selected] == [
        ("flash_attn", "xla", "auto"), ("flash_attn", "pallas", "pallas"),
        ("flash_attn", "xla", "pallas")]
    blk_q, blk_k, _ = fa._resolve_blocks(4096, *fa.choose_blocks(4096, 128, True))
    visited, crossed = fa.tile_counts(4096, blk_q, blk_k, True)
    detail = {k: selected[1][k] for k in (
        "L", "d", "causal", "blk_q", "blk_k", "tiles_visited", "tiles_crossed",
        "tiles_masked", "bwd_matmuls_a_tile")}
    assert detail == {
        "L": 4096, "d": 128, "causal": True, "blk_q": blk_q, "blk_k": blk_k,
        "tiles_visited": visited, "tiles_crossed": crossed,
        "tiles_masked": visited, "bwd_matmuls_a_tile": 5}
    assert crossed < visited < (4096 // blk_q) * (4096 // blk_k)
    assert "blk_q" not in selected[0]
    assert ["platform cpu" in r["reason"] for r in fell] == [True, False]
    assert "fits_vmem" in fell[1]["reason"]


def test_auto_resolution_threshold():
    """The 'auto' branch itself: flash at ≥1024 tokens with dropout 0,
    dense below / with dropout; explicit impls pass through."""
    from distribuuuu_tpu.models.vit import Attention

    assert Attention.resolve_impl("auto", 1024, 0.0) == "flash"
    assert Attention.resolve_impl("auto", 4096, 0.0) == "flash"
    assert Attention.resolve_impl("auto", 1023, 0.0) == "xla"
    assert Attention.resolve_impl("auto", 4096, 0.1) == "xla"  # no p-dropout
    assert Attention.resolve_impl("xla", 4096, 0.0) == "xla"
    assert Attention.resolve_impl("blockwise", 64, 0.0) == "blockwise"


@pytest.mark.slow
def test_vit_auto_resolves_by_length():
    """Through the real model: a ≥1024-token input drives the auto→flash
    branch (CPU fallback executes the blockwise math), a 64-token input
    the auto→xla branch; both produce finite logits."""
    from distribuuuu_tpu import models

    rng = np.random.default_rng(3)
    cases = [
        (128, 16, "auto"),   # 64 tokens  → xla
        (256, 8, "auto"),    # 1024 tokens → flash (threshold branch)
        (128, 16, "flash"),  # forced flash, short seq
    ]
    for size, patch, impl in cases:
        m = models.build_model(
            "vit_tiny", num_classes=10, dtype=jnp.float32, patch=patch,
            depth=1, dim=32, num_heads=2, attn_impl=impl,
        )
        x = jnp.asarray(
            rng.standard_normal((1, size, size, 3)), jnp.float32
        )
        vs = m.init(jax.random.key(0), x, train=False)
        logits = m.apply(vs, x, train=False)
        assert np.isfinite(np.asarray(logits)).all(), (size, patch, impl)


def test_trainer_accepts_flash_impl():
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.config import cfg

    cfg.MODEL.ARCH = "vit_tiny"
    cfg.DEVICE.ATTN_IMPL = "flash"
    model = trainer.build_model_from_cfg()
    assert model.attn_impl == "flash"
    cfg.DEVICE.ATTN_IMPL = "auto"
    model = trainer.build_model_from_cfg()
    assert model.attn_impl == "auto"
