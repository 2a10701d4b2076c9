"""LFM2-24B-A2B's blocks on the normal path, against the benchmark's plain
reference (``benchmark/reference/lfm2_moe.py``), at a size the CPU runs:
hidden 64, 4 query heads on 2 key/value heads of 16, a gated short
convolution of 3 taps, a dense MLP of 160 in the 2 leading layers, then 8
experts of 32 with 2 a token, 6 layers by the pattern conv, conv, attention,
conv, vocab 512 with the head tied to the embedding; two chips share each
layer unless a test says otherwise. The contracts it answers are
``tests/decoder_contract.py``'s; below them, what only LFM2 has: the layer
pattern, the tied head, the short convolution, the grouped flash kernels."""

import functools

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_contract as contract
from decoder_contract import program_loss, variables
from distribuuuu_tpu import models
from distribuuuu_tpu.models import glm_moe, lfm2_moe
from distribuuuu_tpu.ops import flash_attention as fa
from distribuuuu_tpu.ops import moe as moe_ops
from distribuuuu_tpu.ops.short_conv import gated_short_conv

ROW = contract.ROWS["lfm2"]


def build(**kw):
    return contract.build(ROW, **kw)


class TestLFM2(contract.Decoder, contract.ThroughLower, contract.Recomputes,
               contract.RecomputesNothingInItsCell, contract.HoldsAShare):
    row = ROW

    def shapes_of_its_own(self, full, model, state, hidden):
        assert len(full.layer_kinds) == 40
        assert full.layer_kinds.count("full_attention") == 10
        assert full.layer_kinds[:4] == ("conv", "conv", "full_attention", "conv")
        assert hidden[1]["aux"].shape == (4,)
        params = state["params"]
        assert params["Block_0"]["short_conv"]["filter"].shape == (64, 3)
        assert params["Block_0"]["short_conv"]["in_proj"]["kernel"].shape == (64, 192)
        attn = params["Block_2"]["attn"]
        assert attn["q_proj"]["kernel"].shape == (64, 64)
        assert attn["k_proj"]["kernel"].shape == attn["v_proj"]["kernel"].shape == (64, 32)
        assert attn["q_norm"]["scale"].shape == attn["k_norm"]["scale"].shape == (16,)
        assert "shared" not in params["Block_2"]["moe"]  # no shared expert
        # ONE matrix is embedding and head
        assert "head" not in params
        assert jax.eval_shape(model.head_kernel, params).shape == (64, 256)
        with pytest.raises(ValueError, match="inside the list"):
            build(first_layer=4, depth=6).layer_kinds

    def declared_of_its_own(self, arch, model):
        assert model.layer_kinds == ("conv", "full_attention", "conv", "conv", "conv")

    def step_of_its_own(self, ran, want):
        assert ran.model.recompute is ran.overrides["recompute"]

    def test_the_tied_heads_gradient_has_two_sources(self, small):
        """The embedding's gradient is the lookup's plus the head's: each
        alone is another matrix, and their sum is the gradient of the tied
        loss."""
        model, params, biases, tokens, labels = small

        def loss(table, head):
            tied = {**params, "tok_embed": {"embedding": table}}
            outputs = model.apply(variables(tied, biases), tokens, hidden_only=True)
            return model.head_loss(outputs, head.T, labels, topk=(1, 5))[0]

        table = params["tok_embed"]["embedding"]
        lookup, head = jax.grad(loss, argnums=(0, 1))(table, table)
        whole = jax.grad(lambda p: program_loss(model, p, biases, tokens, labels)[0])(
            params)["tok_embed"]["embedding"]
        np.testing.assert_allclose(whole, lookup + head, atol=1e-7)
        assert float(jnp.abs(lookup).max()) > 0 and float(jnp.abs(head).max()) > 0
        # rows no token drew get a gradient from the head alone
        unseen = np.setdiff1d(np.arange(256), np.asarray(tokens))
        assert not float(jnp.abs(lookup[unseen]).max())
        assert float(jnp.abs(whole[unseen]).max()) > 0


@pytest.mark.parametrize("dense", [0, 1, 2])
@pytest.mark.parametrize("first", [0, 1])
def test_the_layer_pattern_says_which_block_is_which(dense, first):
    """Block i's mixer is ``layer_types[first_layer + i]``'s and its FFN the
    dense MLP while published layer ``first_layer + i`` lies under
    ``num_dense_layers``."""
    model = build(first_layer=first, depth=5, dense_layers=dense)
    kinds = ("conv", "conv", "full_attention", "conv") * 2
    assert model.layer_kinds == kinds[first:first + 5]
    assert model.dense_here == max(0, dense - first)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), model.dummy_input()))["params"]
    for i, kind in enumerate(model.layer_kinds):
        block = shapes[f"Block_{i}"]
        assert ("short_conv" in block) == (kind == "conv")
        assert ("attn" in block) == (kind == "full_attention")
        assert ("mlp" in block) == (first + i < dense)
        assert ("moe" in block) == (first + i >= dense)
    # the cell's stage: layers 1..5 of the published 40
    stage = models.build_model("lfm2_24b_a2b", first_layer=1, depth=5)
    assert stage.layer_kinds == ("conv", "full_attention", "conv", "conv", "conv")
    assert stage.dense_here == 1


def _explicit_conv(bcu, w):
    """``c_t = sum_j w_j g_{t-(L-1)+j}`` position by position."""
    b, c, u = np.split(np.asarray(bcu, np.float64), 3, axis=-1)
    g, w = b * u, np.asarray(w, np.float64)
    taps, out = w.shape[1], np.zeros_like(g)
    for t in range(g.shape[1]):
        for j in range(taps):
            src = t - (taps - 1) + j
            if src >= 0:
                out[:, t] += w[:, j] * g[:, src]
    return c * out


# the jax.numpy path at a shape no kernel takes, and the two Pallas calls
# (ops/pallas/short_conv.py) through the interpreter: (S, H, interpret)
SHORT_CONV_PATHS = {"jax_numpy": (9, 8, None), "kernel": (16, 128, True)}


@pytest.mark.parametrize("path", list(SHORT_CONV_PATHS))
@pytest.mark.parametrize("taps", [3, 4])
def test_short_conv_is_the_explicit_sum_value_and_gradient(taps, path):
    """``ops/short_conv`` against the sum written out, the first L - 1
    positions (which read zeros left of the sequence) included; its gradient
    against a central difference of that sum; on either path."""
    S, H, interpret = SHORT_CONV_PATHS[path]
    gated = functools.partial(gated_short_conv, interpret=interpret)
    keys = jax.random.split(jax.random.key(taps), 3)
    bcu = jax.random.normal(keys[0], (2, S, 3 * H))
    w = jax.random.normal(keys[1], (H, taps))
    want = _explicit_conv(bcu, w)
    got = gated(bcu, w)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(got[:, :taps - 1], want[:, :taps - 1], atol=1e-5)
    # position 0 reads the last tap alone
    b, c, u = jnp.split(bcu, 3, axis=-1)
    np.testing.assert_allclose(got[:, 0], c[:, 0] * w[:, -1] * b[:, 0] * u[:, 0], atol=1e-5)
    weights = np.asarray(jax.random.normal(keys[2], want.shape), np.float64)
    d_bcu, d_w = jax.grad(
        lambda bcu, w: (gated(bcu, w) * weights).sum(), argnums=(0, 1))(bcu, w)

    def central(of, x, index, step=1e-3):
        hi, lo = np.array(x, np.float64), np.array(x, np.float64)
        hi[index] += step
        lo[index] -= step
        return ((of(hi) - of(lo)) * weights).sum() / (2 * step)

    # B at the first position, C, u, the last element, B near the end
    for index in [(0, 0, 0), (1, 0, H + 1), (0, 1, 2 * H + 1), (1, S - 1, 3 * H - 1),
                  (0, S - 2, 3)]:
        np.testing.assert_allclose(
            d_bcu[index], central(lambda x: _explicit_conv(x, w), bcu, index), rtol=2e-3)
    for index in [(0, 0), (3, taps - 1), (7, 1)]:
        np.testing.assert_allclose(
            d_w[index], central(lambda x: _explicit_conv(bcu, x), w, index), rtol=2e-3)
    # in bfloat16 the arithmetic is float32 and the result rounded once
    low = gated(bcu.astype(jnp.bfloat16), w)
    assert low.dtype == jnp.bfloat16
    exact = _explicit_conv(bcu.astype(jnp.bfloat16).astype(jnp.float32), w)
    np.testing.assert_allclose(low.astype(jnp.float32), exact, rtol=2 ** -7, atol=1e-6)


@pytest.mark.parametrize("length", [200, 256])
@pytest.mark.parametrize("kv_heads", [1, 2])
def test_grouped_flash_is_repeated_kv_flash_forward_and_backward(kv_heads, length):
    """4 query heads on 1 and on 2 key/value heads through the interpreted
    kernels: output and all three gradients equal the call on K and V
    repeated to 4 heads (dK and dV summed over the group), at a length the
    128 lanes divide and at one they pad."""
    group = 4 // kv_heads
    keys = jax.random.split(jax.random.key(kv_heads), 4)
    q = jax.random.normal(keys[0], (2, 4, length, 16))
    k, v = (jax.random.normal(key, (2, kv_heads, length, 16)) for key in keys[1:3])
    weights = jax.random.normal(keys[3], q.shape)

    def attend(q, k, v):
        return fa.flash_attention(
            q, k, v, causal=True, interpret=True, blk_q=128, blk_k=128)

    def grouped(q, k, v):
        return (attend(q, k, v) * weights).sum()

    def repeated(q, k, v):
        return (attend(
            q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)) * weights).sum()

    np.testing.assert_allclose(attend(q, k, v), attend(
        q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)), atol=1e-6)
    got = jax.grad(grouped, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(repeated, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want, strict=True):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=2e-5)
    # head h reads key/value head h // group, not h % kv_heads
    if kv_heads == 2:
        wrong = attend(q, jnp.tile(k, (1, group, 1, 1)), jnp.tile(v, (1, group, 1, 1)))
        assert float(jnp.abs(wrong - attend(q, k, v)).max()) > 1e-2
    with pytest.raises(ValueError, match="their heads divide"):
        fa.flash_attention(q[:, :3], k[:, :1].repeat(2, 1), v[:, :1].repeat(2, 1),
                           causal=True, interpret=True)


def test_an_equal_head_call_keeps_the_index_maps_it_had():
    """With one query head a key/value head the K/V block specs ARE the q
    specs (the parent's index maps: no division enters the program); a
    grouped call's send query program i to key/value head i // group."""
    blocked, whole, _, _, kv_whole, kv_blocked = fa._specs(256, 16, 128, 1)
    assert kv_whole is whole and kv_blocked is blocked
    *_, kv_whole, kv_blocked = fa._specs(256, 16, 128, 4)
    # b * H_q + h with H_q = 8: programs 8..11 are batch 1's heads 0..3
    assert [kv_whole().index_map(i, 1) for i in (0, 3, 4, 9, 15)] == [
        (0, 0, 0), (0, 0, 0), (1, 0, 0), (2, 0, 0), (3, 0, 0)]
    assert kv_blocked().index_map(13, 1) == (3, 1, 0)
    assert fa._kv_group(jnp.zeros((1, 8, 4, 2)), *[jnp.zeros((1, 2, 4, 2))] * 2) == 4


def test_the_weights_are_normalised_over_the_chosen_plus_the_given_epsilon():
    scores = jnp.asarray([[0.9, 0.5, 0.4, 0.1]])
    bias = jnp.zeros((4,))
    for eps in (1e-20, 1e-6, 0.1):
        weights, indices = moe_ops.top_k_biased(scores, bias, 2, 1.0, eps)
        np.testing.assert_array_equal(indices, [[0, 1]])
        np.testing.assert_allclose(
            weights, [[0.9 / (1.4 + eps), 0.5 / (1.4 + eps)]], rtol=1e-6)
    # GLM's default is the parent's literal
    np.testing.assert_array_equal(
        moe_ops.top_k_biased(scores, bias, 2, 1.8)[0],
        moe_ops.top_k_biased(scores, bias, 2, 1.8, 1e-20)[0])
    assert glm_moe.Mixture.norm_eps == 1e-20
    assert lfm2_moe.LFM2MoE.route_norm_eps == 1e-6


def test_the_model_says_its_layer_kinds_and_the_grouped_flash_its_group_once_a_shape(
        tmp_path):
    from unittest import mock

    from distribuuuu_tpu.ops import pallas as kernel_tier
    from distribuuuu_tpu.telemetry import schema, spans

    kernel_tier.reset_selection()
    spans.setup_telemetry(str(tmp_path), 0)
    try:
        model = build().clone(first_layer=1, depth=3, seq_len=24, share_rank=1)
        state = flax.linen.meta.unbox(
            model.init(jax.random.key(0), jnp.full((3, 24), 256, jnp.int32)))
        for _ in range(2):
            model.apply(state, jnp.full((3, 24), 300, jnp.int32), hidden_only=True)
        q = jnp.zeros((1, 4, 256, 16))
        fa.flash_attention(q, q[:, :2], q[:, :2], causal=True, interpret=True)
    finally:
        spans.close_telemetry()
    plans = contract.records(tmp_path, "share.plan")
    assert len(plans) == 1
    schema.check_fields("share.plan", plans[0])
    assert {k: plans[0][k] for k in (
        "share_chips", "share_rank", "experts_held", "experts_total", "vocab_held",
        "vocab_total", "layer_kinds", "dense_layers",
    )} == {"share_chips": 2, "share_rank": 1, "experts_held": 4, "experts_total": 8,
           "vocab_held": 256, "vocab_total": 512,
           "layer_kinds": ["conv", "full_attention", "conv"], "dense_layers": 1}
    assert "every block of either kind" in plans[0]["recomputed"]
    # the scan path names nothing; a block keeps its float32 input and its
    # mixer's output (float32 here), not its FFN's: no norm follows it
    assert plans[0]["kept_branch_bytes"] == 3 * 3 * 24 * 64 * 4
    assert plans[0]["kept_bytes"] == 2 * 3 * 3 * 24 * 64 * 4
    chose = [r for r in contract.records(tmp_path, "kernel.select")
             if r["op"] == "flash_attn" and r["impl"] == "pallas"]
    assert chose and (chose[-1]["kv_group"], chose[-1]["kv_heads"]) == (2, 2)
    assert {"blk_q", "blk_k", "tiles_visited"} <= set(chose[-1])
    # what a recomputed block keeps of a grouped call: k and v at their own heads
    equal = fa.kept_under_remat_bytes((2, 32, 8192, 64), 2)
    grouped = fa.kept_under_remat_bytes((2, 32, 8192, 64), 2, kv_heads=8)
    assert equal == grouped  # 0 here: the CPU takes the scan path
    with mock.patch.object(kernel_tier, "interpret_mode", lambda: False), \
            mock.patch.object(kernel_tier, "compiled_across_devices", lambda: False):
        equal = fa.kept_under_remat_bytes((2, 32, 8192, 64), 2)
        grouped = fa.kept_under_remat_bytes((2, 32, 8192, 64), 2, kv_heads=8)
    assert equal - grouped == 2 * 2 * 24 * 8192 * 64 * 2
