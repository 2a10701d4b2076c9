"""The sliding window of ``ops/flash_attention.py``'s kernels (interpret mode),
of ``blockwise_attention`` and of the dense path ``models/olmoe._attend``
falls to, against the masked dense softmax written out: values and all three
gradients, with 4 query heads on 1 key/value head, for windows below, at and
above a block, no multiple of a block, and reaching every key; the walks'
bounds against a brute count of the tiles; what ``kernel.select`` says; and
``window=None`` against the jaxpr the parent of the window's PR traced."""

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
BLOCK = 128


def masked_softmax(q, k, v, window):
    """Query t reads the keys s with ``t - window < s <= t``: the mask as a
    comparison of positions, K and V repeated to q's heads."""
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    t = jnp.arange(q.shape[2])[:, None]
    s = jnp.arange(q.shape[2])[None, :]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    scores = jnp.where((s <= t) & (t - s < window), scores, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def tensors(length, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(keys[0], (2, 4, length, 16))
    k, v = (jax.random.normal(key, (2, 1, length, 16)) for key in keys[1:3])
    return q, k, v, jax.random.normal(keys[3], q.shape)


def flash(window):
    return lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, interpret=True, blk_q=BLOCK, blk_k=BLOCK, window=window)


# below a block (a row's first visited tile may be wholly masked for it), at
# one, one past it, no multiple of it, two blocks, and every key
WINDOWS = [1, 50, 128, 129, 200, 256, 5000]


# every window at a length the kernels pad (300 -> 384), three of them (under
# a block, off its multiples, every key) at whole blocks too
@pytest.mark.parametrize("window, length", [
    *((w, 300) for w in WINDOWS), (50, 384), (129, 384), (5000, 384)])
def test_windowed_flash_is_the_masked_softmax_forward_and_backward(window, length):
    q, k, v, weights = tensors(length, window)
    want = masked_softmax(q, k, v, window)
    np.testing.assert_allclose(flash(window)(q, k, v), want, atol=2e-6)
    got = jax.grad(lambda *a: (flash(window)(*a) * weights).sum(), (0, 1, 2))(q, k, v)
    wanted = jax.grad(
        lambda *a: (masked_softmax(*a, window) * weights).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(got, wanted, strict=True):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=3e-5)
    if window >= length:  # the window reaches every key: the causal call
        np.testing.assert_array_equal(flash(window)(q, k, v), flash(None)(q, k, v))
    else:
        assert float(jnp.abs(flash(None)(q, k, v) - want).max()) > 1e-2


@pytest.mark.parametrize("window", WINDOWS)
def test_the_fallbacks_compute_the_same_window(window):
    """``blockwise_attention`` (chunks of 64 that no window here is a
    multiple of but 128 and 256) and the dense softmax of ``_attend``, on K
    and V repeated as their callers hand them over."""
    q, k, v, weights = tensors(300, window)
    want = masked_softmax(q, k, v, window)
    k, v = (jnp.repeat(t, 4, axis=1) for t in (k, v))

    def scan(q, k, v):
        return blockwise_attention(q, k, v, causal=True, window=window, chunk=64)

    def dense(q, k, v):
        return _attend(q, k, v, "xla", jnp.float32, None, window)

    for path in (scan, dense):
        np.testing.assert_allclose(path(q, k, v), want, atol=2e-6)
        got = jax.grad(lambda *a: (path(*a) * weights).sum(), (0, 1, 2))(q, k, v)
        wanted = jax.grad(
            lambda *a: (masked_softmax(*a, window) * weights).sum(), (0, 1, 2))(q, k, v)
        for a, b in zip(got, wanted, strict=True):
            np.testing.assert_allclose(a, b, atol=3e-5)
    # off the TPU the public entry takes the scan, window and all
    auto = fa.flash_attention(q[:, :, :, :], k[:, :1], v[:, :1], causal=True, window=window)
    np.testing.assert_allclose(auto, want, atol=2e-6)


def test_a_window_takes_a_causal_call_and_a_key():
    q, k, v, _ = tensors(256)
    with pytest.raises(ValueError, match="causal call"):
        fa.flash_attention(q, k, v, window=64, interpret=True)
    with pytest.raises(ValueError, match="own key"):
        fa.flash_attention(q, k, v, causal=True, window=0, interpret=True)


def test_the_walks_visit_exactly_the_tiles_the_window_keeps():
    """The forward's walk ``[lo, hi)`` and the backward's ``[first, last)``
    against the mask score by score, and ``tile_counts`` against a brute
    count, over lengths, blocks and windows (below, at and across blocks)."""
    cases = [(L, bq, bk, w)
             for L, bq, bk in ((384, 128, 128), (300, 128, 128), (1024, 256, 512),
                               (1024, 512, 256), (1000, 512, 128), (2048, 512, 512))
             for w in (1, 100, 128, 129, 300, 512, 513, 1000)]
    for L, blk_q, blk_k, window in cases:
        lp = fa._round_up(L, 128)
        nq, nk = lp // blk_q, lp // blk_k
        keep = np.asarray(fa._keep(
            jnp.arange(lp)[:, None], jnp.arange(lp)[None, :], L, True, window))
        tiles = keep.reshape(nq, blk_q, nk, blk_k)
        any_kept, all_kept = tiles.any((1, 3)), tiles.all((1, 3))
        for j in range(nq):
            _, hi = fa._key_tiles(j, blk_q, blk_k, lp, L, True)
            lo, _ = fa._window_key_tiles(j, blk_q, blk_k, window)
            visited = (np.arange(nk) >= lo) & (np.arange(nk) < hi)
            # a visited tile may keep nothing only through the padding
            assert (any_kept[j] <= visited).all(), (L, blk_q, blk_k, window, j)
            real = np.arange(nk) * blk_k < L
            assert (any_kept[j][real] == visited[real]).all(), (L, blk_q, blk_k, window, j)
        for j in range(nk):
            first = fa._first_query_tile(j, blk_q, blk_k, True)
            last = fa._last_query_tile(j, blk_q, blk_k, lp, window)
            visited = (np.arange(nq) >= first) & (np.arange(nq) < last)
            assert (any_kept[:, j] <= visited).all(), (L, blk_q, blk_k, window, j)
            if (j + 1) * blk_k <= L:
                # rows past the sequence's end keep what the mask says too
                assert (any_kept[:, j] == visited).all(), (L, blk_q, blk_k, window, j)
        if lp == L:
            assert fa.tile_counts(L, blk_q, blk_k, True, window) == (
                any_kept.sum(), (any_kept & ~all_kept).sum()), (L, blk_q, blk_k, window)
    # the cell's shape: 70 of causal's 136 tiles, two crossed a row of blocks
    assert fa.tile_counts(8192, 512, 512, True) == (136, 16)
    assert fa.tile_counts(8192, 512, 512, True, 2048) == (70, 16 + 12)
    assert fa.tile_counts(8192, 512, 512, True, 8192) == (136, 16)


def test_select_says_the_window_and_its_tiles(tmp_path):
    from distribuuuu_tpu.ops import pallas as tier
    from distribuuuu_tpu.telemetry import schema, spans

    q = jax.ShapeDtypeStruct((2, 32, 8192, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, 4, 8192, 128), jnp.bfloat16)
    tier.reset_selection()
    path = spans.setup_telemetry(str(tmp_path), rank=0)
    try:
        for window in (2048, None, 8192):
            jax.eval_shape(lambda q, k: fa.flash_attention(
                q, k, k, causal=True, interpret=True, window=window), q, kv)
    finally:
        spans.close_telemetry()
        tier.reset_selection()
    records = [json.loads(line) for line in open(path)]
    chose = [r for r in records if r.get("kind") == "kernel.select"]
    for record in chose:
        schema.validate_record(record)
    # a window that reaches every key is the causal call: one record for both
    assert len(chose) == 2
    windowed, causal = chose
    assert (windowed["window"], windowed["tiles_visited"], windowed["tiles_crossed"],
            windowed["kv_group"], windowed["blk_q"], windowed["blk_k"]) == (
                2048, 70, 28, 8, 512, 512)
    assert "window" not in causal and causal["tiles_visited"] == 136


def test_without_a_window_the_causal_call_traces_what_the_parent_traced():
    """The gradient's jaxpr of a grouped causal call (4 heads on 2, a padded
    length, the interpreted kernels) is, character for character, the one
    the parent of the window's PR printed (``tests/data``, taken from its
    checkout with the installed jax): ``window=None`` adds nothing to the
    program of an accepted caller."""
    with open(os.path.join(HERE, "data", "flash_causal_grouped.jaxpr.txt")) as f:
        parents = f.read()
    if f"jax {jax.__version__}\n" != parents.splitlines(keepends=True)[0]:
        pytest.skip("the parent's jaxpr was printed by another jax")
    q, k = jnp.zeros((1, 4, 300, 16)), jnp.zeros((1, 2, 300, 16))

    def loss(q, k, v, **kw):
        return fa.flash_attention(
            q, k, v, causal=True, interpret=True, blk_q=128, blk_k=128, **kw).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, k))
    assert text + "\n" == parents.split("\n", 1)[1]
    windowed = str(jax.make_jaxpr(jax.grad(
        lambda *a: loss(*a, window=100), (0, 1, 2)))(q, k, k))
    assert windowed != text
