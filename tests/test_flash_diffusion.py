"""The block-diffusion mask of ``ops/flash_attention.py``'s kernels (interpret
mode), of ``blockwise_attention`` and of the dense path ``models/olmoe._attend``
falls to, against the masked dense softmax written from the four rules:
values and all three gradients, with 4 query heads on 1 key/value head, for
blocks of 4 and 32 tokens (and 25, no power of two), at whole tiles and at a
length that pads each half; the walks' bounds against a brute count of the
tiles; what ``kernel.select`` says; and a call WITHOUT the keyword against
the jaxprs the parent of the mask's PR traced."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distribuuuu_tpu.models.olmoe import _attend
from distribuuuu_tpu.ops import flash_attention as fa
from distribuuuu_tpu.ops.ring_attention import blockwise_attention

HERE = os.path.dirname(os.path.abspath(__file__))
TILE = 128


def rules(seq, block):
    """``[2 seq, 2 seq]`` (query, key): a noised row reads the noised rows of
    its block and the clean rows of earlier blocks; a clean row the clean rows
    of its own and earlier blocks."""
    row = np.arange(2 * seq)
    clean, blk = row >= seq, row % seq // block
    q_clean, k_clean = clean[:, None], clean[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return np.where(q_clean, k_clean & (kb <= qb), np.where(k_clean, kb < qb, kb == qb))


def masked_softmax(q, k, v, seq, block):
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    scores = jnp.where(rules(seq, block), scores, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def tensors(seq, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(keys[0], (2, 4, 2 * seq, 16))
    k, v = (jax.random.normal(key, (2, 1, 2 * seq, 16)) for key in keys[1:3])
    return q, k, v, jax.random.normal(keys[3], q.shape)


def flash(block, tile=TILE):
    return lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, interpret=True, blk_q=tile, blk_k=tile,
        diffusion_block=block)


# whole tiles (a half of two tiles), a half that pads (200 -> 256 rows: the
# last tile of each half holds padded keys and rows), one tile a half, and
# tiles of 256 under a half of 384 (snapped to 128)
@pytest.mark.parametrize("block, seq, tile", [
    (4, 256, TILE), (32, 256, TILE), (4, 200, TILE), (25, 200, TILE),
    (32, 128, TILE), (32, 384, 256), (4, 100, TILE)])
def test_diffusion_flash_is_the_masked_softmax_forward_and_backward(block, seq, tile):
    q, k, v, weights = tensors(seq, block)
    want = masked_softmax(q, k, v, seq, block)
    np.testing.assert_allclose(flash(block, tile)(q, k, v), want, atol=2e-6)
    got = jax.grad(lambda *a: (flash(block, tile)(*a) * weights).sum(), (0, 1, 2))(q, k, v)
    wanted = jax.grad(
        lambda *a: (masked_softmax(*a, seq, block) * weights).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(got, wanted, strict=True):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=3e-5)
    # a plain causal call over the same rows is another function
    causal = fa.flash_attention(
        q, k, v, causal=True, interpret=True, blk_q=tile, blk_k=tile)
    assert float(jnp.abs(causal - want).max()) > 1e-2


@pytest.mark.parametrize("block", [4, 32])
def test_the_fallbacks_compute_the_same_mask(block):
    """``blockwise_attention`` (chunks of 64) and the dense softmax of
    ``_attend``, on K and V repeated as their callers hand them over; off the
    TPU the public entry takes the scan, mask and all."""
    seq = 160
    q, k, v, weights = tensors(seq, block)
    want = masked_softmax(q, k, v, seq, block)
    np.testing.assert_array_equal(fa.diffusion_mask(2 * seq, block), rules(seq, block))
    auto = fa.flash_attention(q, k, v, causal=True, diffusion_block=block)
    np.testing.assert_allclose(auto, want, atol=2e-6)
    k, v = (jnp.repeat(t, 4, axis=1) for t in (k, v))

    def scan(q, k, v):
        return blockwise_attention(
            q, k, v, causal=True, diffusion_block=block, chunk=64)

    def dense(q, k, v):
        return _attend(q, k, v, "xla", jnp.float32, None, None, block)

    for path in (scan, dense):
        np.testing.assert_allclose(path(q, k, v), want, atol=2e-6)
        got = jax.grad(lambda *a: (path(*a) * weights).sum(), (0, 1, 2))(q, k, v)
        wanted = jax.grad(lambda *a: (masked_softmax(
            *a, seq, block) * weights).sum(), (0, 1, 2))(q, k, v)
        for a, b in zip(got, wanted, strict=True):
            np.testing.assert_allclose(a, b, atol=3e-5)


def test_the_mask_takes_a_causal_call_of_whole_blocks():
    q, k, v, _ = tensors(128)
    for kw in (dict(causal=False), dict(causal=True, window=64)):
        with pytest.raises(ValueError, match="causal call without a window"):
            fa.flash_attention(q, k, v, diffusion_block=4, interpret=True, **kw)
    with pytest.raises(ValueError, match="whole number of blocks"):
        fa.flash_attention(q, k, v, causal=True, diffusion_block=48, interpret=True)


def test_the_walks_visit_exactly_the_tiles_the_mask_keeps():
    """The forward's two ranges of key tiles and the backward's two ranges of
    query tiles against the mask score by score, and ``tile_counts`` against
    a brute count, over lengths, tiles and block lengths."""
    cases = [(seq, bq, bk, block)
             for seq, bq, bk in ((256, 128, 128), (200, 128, 128), (512, 256, 128),
                                 (512, 128, 256), (1024, 512, 512), (384, 128, 384))
             for block in (1, 4, 8, 32, 128) if seq % block == 0]
    for seq, blk_q, blk_k, block in cases:
        half = fa._round_up(seq, 128)
        rows = np.arange(2 * half)
        keep = np.asarray(fa._diffusion_keep(
            rows[:, None], rows[None, :], half, seq, block))
        real = rows % half < seq
        assert (keep[np.ix_(real, real)] == rules(seq, block)).all()
        nq, nk = 2 * half // blk_q, 2 * half // blk_k
        tiles = keep.reshape(nq, blk_q, nk, blk_k)
        any_kept, all_kept = tiles.any((1, 3)), tiles.all((1, 3))

        def visited(ranges, n):
            at = np.arange(n)
            assert ranges[0][1] <= ranges[1][0] or ranges[1][0] >= ranges[1][1]
            return sum(((at >= lo) & (at < hi)) for lo, hi in ranges).astype(bool)

        case = (seq, blk_q, blk_k, block)
        for j in range(nq):
            walk = visited(fa._diffusion_key_tiles(j, blk_q, blk_k, half, block), nk)
            assert (any_kept[j] == walk).all(), (case, j)
        for j in range(nk):
            walk = visited(fa._diffusion_query_tiles(j, blk_q, blk_k, half, block), nq)
            # padded query rows (clean by their index) read every clean key
            assert (any_kept[:, j] == walk).all(), (case, j)
        if half == seq:
            assert fa.tile_counts(2 * seq, blk_q, blk_k, True, None, block) == (
                any_kept.sum(), (any_kept & ~all_kept).sum()), case
    # the cell's shape: the clean half's causal 136, the noised half's 16
    # diagonal tiles and its 136 clean ones, of a causal walk's 528
    assert fa.tile_counts(16384, 512, 512, True) == (528, 32)
    for block in (4, 32):
        assert fa.tile_counts(16384, 512, 512, True, None, block) == (288, 48)


def test_the_traced_walks_are_the_counted_walks():
    """The ranges with a traced program id (what the kernels run) equal the
    ranges with a Python int (what ``tile_counts`` counts)."""
    half, block = 1024, 4
    for fn in (fa._diffusion_key_tiles, fa._diffusion_query_tiles):
        for j in range(2 * half // 256):
            traced = jax.jit(lambda j, fn=fn: fn(j, 256, 256, half, block))(j)
            assert jax.tree.map(int, traced) == fn(j, 256, 256, half, block)


def test_select_says_the_mask_and_its_tiles(tmp_path):
    from distribuuuu_tpu.ops import pallas as tier
    from distribuuuu_tpu.telemetry import schema, spans

    q = jax.ShapeDtypeStruct((1, 32, 16384, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 4, 16384, 128), jnp.bfloat16)
    tier.reset_selection()
    path = spans.setup_telemetry(str(tmp_path), rank=0)
    try:
        for block in (4, None):
            jax.eval_shape(lambda q, k: fa.flash_attention(
                q, k, k, causal=True, interpret=True, diffusion_block=block), q, kv)
    finally:
        spans.close_telemetry()
        tier.reset_selection()
    records = [json.loads(line) for line in open(path)]
    chose = [r for r in records if r.get("kind") == "kernel.select"]
    for record in chose:
        schema.validate_record(record)
    masked, causal = chose
    assert (masked["mask"], masked["diffusion_block"], masked["tiles_visited"],
            masked["tiles_crossed"], masked["tiles_masked"], masked["kv_group"],
            masked["blk_q"], masked["blk_k"], masked["L"]) == (
                "block_diffusion", 4, 288, 48, 288, 8, 512, 512, 16384)
    assert "mask" not in causal and causal["tiles_visited"] == 528
    assert not [r for r in records if r.get("kind") == "kernel.fallback"]
    # the 16,384 rows fit the kernels' resident set as they stand
    assert fa.fits_vmem(16384, 128) and not fa.fits_vmem(16384, 256)


@pytest.mark.parametrize("name, keywords", [
    ("flash_causal_grouped", {}), ("flash_window_grouped", {"window": 100})])
def test_without_the_mask_a_call_traces_what_the_parent_traced(name, keywords):
    """The gradient's jaxpr of a grouped causal call (4 heads on 2, a padded
    length, the interpreted kernels), with and without a window, is,
    character for character, the one the parent of the mask's PR printed
    (``tests/data``, taken from its checkout with the installed jax): the
    keyword left out, or ``None``, adds nothing to the program of an accepted
    caller."""
    with open(os.path.join(HERE, "data", f"{name}.jaxpr.txt")) as f:
        parents = f.read()
    if f"jax {jax.__version__}\n" != parents.splitlines(keepends=True)[0]:
        pytest.skip("the parent's jaxpr was printed by another jax")
    q, k = jnp.zeros((1, 4, 300, 16)), jnp.zeros((1, 2, 300, 16))

    def loss(q, k, v, **kw):
        return fa.flash_attention(
            q, k, v, causal=True, interpret=True, blk_q=128, blk_k=128, **kw).sum()

    for none in ({}, {"diffusion_block": None}):
        text = str(jax.make_jaxpr(jax.grad(
            lambda *a: loss(*a, **keywords, **none), (0, 1, 2)))(q, k, k))
        assert text + "\n" == parents.split("\n", 1)[1]
    if not keywords:
        masked = str(jax.make_jaxpr(jax.grad(
            lambda *a: loss(*a, diffusion_block=4), (0, 1, 2)))(q[:, :, :296], k[:, :, :296], k[:, :, :296]))
        assert masked != text and "diffusion" not in text
