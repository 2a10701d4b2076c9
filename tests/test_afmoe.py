"""Trinity-Mini's blocks on the normal path, against the benchmark's plain
reference (``benchmark/reference/afmoe.py``), at a size the CPU runs: hidden
64, 4 query heads on 1 key/value head of 32 (4 x 32 is not the width, as
published), a window of 24, a gated attention output, four norms a block, a
dense MLP of 160 in the 2 leading layers, then 8 experts of 32 with 2 a
token and a shared one, 6 layers by the pattern sliding, sliding, sliding,
full, vocab 512 with an untied head; two chips share each layer unless a
test says otherwise. The contracts it answers are
``tests/decoder_contract.py``'s; below them, what only Trinity-Mini has: the
window, rotary in the window layers alone, the gate, the scaled embedding."""

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_contract as contract
from decoder_contract import FULL, SLIDING, seeded, variables
from distribuuuu_tpu import models
from distribuuuu_tpu.models import afmoe, lfm2_moe

ROW = contract.ROWS["afmoe"]


def build(**kw):
    return contract.build(ROW, **kw)


class TestTrinityMini(contract.Decoder, contract.ThroughLower, contract.Recomputes,
                      contract.HoldsAShare):
    row = ROW

    def shapes_of_its_own(self, full, model, state, hidden):
        assert len(full.layer_kinds) == 32
        assert full.layer_kinds.count(FULL) == 8
        assert full.layer_kinds[:4] == (SLIDING, SLIDING, SLIDING, FULL)
        assert hidden[1]["aux"].shape == (4,)
        params = state["params"]
        attn = params["Block_3"]["attn"]
        # 4 heads of 32: wider than the model, as 32 heads of 128 are than 2048
        assert attn["q_proj"]["kernel"].shape == attn["gate_proj"]["kernel"].shape == (64, 128)
        assert attn["k_proj"]["kernel"].shape == attn["v_proj"]["kernel"].shape == (64, 32)
        assert attn["o_proj"]["kernel"].shape == (128, 64)
        assert attn["q_norm"]["scale"].shape == attn["k_norm"]["scale"].shape == (32,)
        assert set(params["Block_3"]) == {"attn", "moe", *afmoe.NORMS}
        assert set(params["Block_0"]) == {"attn", "mlp", *afmoe.NORMS}
        assert "shared" in params["Block_3"]["moe"]
        assert params["head"].shape == (64, 256)  # untied
        assert model.head_kernel(params) is params["head"]
        with pytest.raises(ValueError, match="inside the list"):
            build(first_layer=4, depth=6).layer_kinds
        with pytest.raises(ValueError, match="whose words are"):
            build(layer_types=("conv", FULL), depth=2).layer_kinds

    def loss_of_its_own(self, model, loss, aux, want):
        assert {SLIDING, FULL} <= set(model.layer_kinds)

    def declared_of_its_own(self, arch, model):
        assert model.layer_kinds == (SLIDING, SLIDING, FULL, SLIDING, SLIDING)
        assert model.sliding_window == {"trinity_mini": 2048, "afmoe_tiny": 24}[arch]

    def step_of_its_own(self, ran, want):
        # every block recomputed, as the cell runs them; the loss cases hold
        # the model that keeps everything against the same reference
        assert ran.model.recompute


@pytest.mark.parametrize("dense", [0, 1, 2])
@pytest.mark.parametrize("first", [0, 1])
def test_the_layer_pattern_says_which_block_is_which(dense, first):
    """Block i's FFN is the dense MLP while published layer ``first_layer +
    i`` lies under ``num_dense_layers``, every mixer is attention with a
    gate, and the model says each one's kind."""
    model = build(first_layer=first, depth=5, dense_layers=dense)
    kinds = (SLIDING, SLIDING, SLIDING, FULL, SLIDING, SLIDING)
    assert model.layer_kinds == kinds[first:first + 5]
    assert model.dense_here == max(0, dense - first)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), model.dummy_input()))["params"]
    for i in range(5):
        block = shapes[f"Block_{i}"]
        assert "gate_proj" in block["attn"]
        assert ("mlp" in block) == (first + i < dense)
        assert ("moe" in block) == (first + i >= dense)
    # the cell's stage: layers 1..5 of the published 32
    stage = models.build_model("trinity_mini", first_layer=1, depth=5)
    assert stage.layer_kinds == (SLIDING, SLIDING, FULL, SLIDING, SLIDING)
    assert stage.dense_here == 1


def _one_mixer(kind, **kw):
    """(module, variables, x): one attention mixer of the tiny model."""
    model = build()
    mixer = lfm2_moe.Attention(
        model.dim, model.num_heads, model.kv_heads, model.norm_eps, model.rope_theta,
        jnp.float32, head_dim=model.head_dim, gated=True,
        **({"window": model.sliding_window} if kind == SLIDING else {"rotary": False}),
        **kw)
    x = jax.random.normal(jax.random.key(3), (1, 60, model.dim))
    return mixer, mixer.init(jax.random.key(4), x, jnp.arange(60)), x


def test_rotary_is_in_the_window_layers_and_in_no_full_layer():
    """Moving every position by the same offset changes nothing (rotary is
    relative); handing the positions over in another ORDER moves a sliding
    layer's output and leaves a full layer's, which reads no position, bit
    for bit."""
    shuffled = jax.random.permutation(jax.random.key(5), 60)
    for kind, moves in ((SLIDING, True), (FULL, False)):
        mixer, state, x = _one_mixer(kind)
        base = mixer.apply(state, x, jnp.arange(60))
        np.testing.assert_allclose(
            mixer.apply(state, x, jnp.arange(60) + 7), base, atol=1e-5)
        moved = float(jnp.abs(mixer.apply(state, x, shuffled) - base).max())
        assert (moved > 1e-3) is moves, (kind, moved)
        if not moves:
            assert moved == 0.0


def test_the_window_is_in_the_sliding_layers_alone():
    """A sliding layer's output at position t does not move when a token
    more than the window behind it changes; a full layer's does."""
    window = build().sliding_window
    for kind, reaches in ((SLIDING, False), (FULL, True)):
        mixer, state, x = _one_mixer(kind)
        base = mixer.apply(state, x, jnp.arange(60))
        far = mixer.apply(state, x.at[0, 5].add(1.0), jnp.arange(60))
        delta = jnp.abs(far - base)[0].max(-1)
        assert float(delta[5 + window - 1]) > 1e-5  # the last row that sees it
        assert (float(delta[5 + window:].max()) > 1e-6) is reaches, kind
        assert not float(delta[:5].max())  # causal either way


def test_the_gate_multiplies_the_heads_and_takes_a_gradient():
    """``out = (heads * sigmoid(x W_g)) W_o``: a gate projection of zeros
    halves the ungated mixer's output, the gate's gradient is the product
    rule's against a central difference, and it is nowhere zero."""
    mixer, state, x = _one_mixer(SLIDING)
    params = state["params"]
    ungated = lfm2_moe.Attention(
        mixer.dim, mixer.num_heads, mixer.kv_heads, mixer.eps, mixer.rope_theta,
        jnp.float32, head_dim=mixer.head_dim, window=mixer.window)
    plain = {k: v for k, v in params.items() if k != "gate_proj"}
    zero_gate = {**params, "gate_proj": {"kernel": jnp.zeros_like(
        params["gate_proj"]["kernel"])}}
    np.testing.assert_allclose(
        mixer.apply({"params": zero_gate}, x, jnp.arange(60)),
        0.5 * ungated.apply({"params": plain}, x, jnp.arange(60)), atol=1e-6)
    weights = jax.random.normal(jax.random.key(6), x.shape)

    def total(gate):
        p = {**params, "gate_proj": {"kernel": gate}}
        return (mixer.apply({"params": p}, x, jnp.arange(60)) * weights).sum()

    gate = params["gate_proj"]["kernel"]
    grad = jax.grad(total)(gate)
    assert float(jnp.abs(grad).min()) > 0
    for index in [(0, 0), (17, 45), (63, 127)]:
        step = jnp.zeros_like(gate).at[index].set(1e-2)
        central = (total(gate + step) - total(gate - step)) / 2e-2
        np.testing.assert_allclose(grad[index], central, rtol=2e-2)


def test_the_embedding_is_scaled_by_the_root_of_the_width():
    model = build(depth=3)
    params, biases, tokens, _ = seeded(model, seq=16)
    scaled = {**params, "tok_embed": {"embedding": params["tok_embed"]["embedding"] * 8.0}}
    np.testing.assert_allclose(
        jax.jit(model.apply)(variables(params, biases), tokens),
        jax.jit(model.clone(mup=False).apply)(variables(scaled, biases), tokens),
        atol=1e-5)


def test_the_model_says_its_layer_kinds_and_the_windowed_flash_its_window_once_a_shape(
        tmp_path):
    from unittest import mock

    from distribuuuu_tpu.models.ouro import kept_plan
    from distribuuuu_tpu.ops import flash_attention as fa
    from distribuuuu_tpu.ops import pallas as kernel_tier
    from distribuuuu_tpu.telemetry import schema, spans

    kernel_tier.reset_selection()
    spans.setup_telemetry(str(tmp_path), 0)
    try:
        model = build().clone(first_layer=1, depth=3, seq_len=24, share_rank=1)
        state = flax.linen.meta.unbox(
            jax.jit(model.init)(jax.random.key(0), jnp.full((3, 24), 256, jnp.int32)))
        for _ in range(2):  # traced twice: the plan is said once a shape
            jax.eval_shape(lambda v, t: model.apply(v, t, hidden_only=True),
                           state, jnp.full((3, 24), 300, jnp.int32))
        q = jnp.zeros((1, 4, 256, 32))
        fa.flash_attention(q, q[:, :1], q[:, :1], causal=True, interpret=True, window=24)
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
           "layer_kinds": [SLIDING, SLIDING, FULL], "dense_layers": 1}
    assert "every block of either kind" in plans[0]["recomputed"]
    # the scan path names nothing; a block keeps its float32 input and both
    # its branches' outputs (float32 here): a norm follows each
    assert plans[0]["kept_branch_bytes"] == 2 * 3 * 3 * 24 * 64 * 4
    assert plans[0]["kept_bytes"] == 3 * 3 * 3 * 24 * 64 * 4
    chose = [r for r in contract.records(tmp_path, "kernel.select")
             if r["op"] == "flash_attn" and r["impl"] == "pallas"]
    assert chose and (chose[-1]["window"], chose[-1]["kv_group"]) == (24, 4)
    assert {"blk_q", "blk_k", "tiles_visited", "tiles_crossed"} <= set(chose[-1])
    # what a recomputed block keeps where the kernels run: all five blocks'
    # flash residuals, q at 32 heads of 128 and k, v at their own 4
    cell = build().clone(
        num_heads=32, kv_heads=4, head_dim=128, dim=2048, dtype=jnp.bfloat16)
    with mock.patch.object(kernel_tier, "interpret_mode", lambda: False), \
            mock.patch.object(kernel_tier, "compiled_across_devices", lambda: False):
        one = fa.kept_under_remat_bytes((2, 32, 8192, 128), 2, kv_heads=4)
        kept = kept_plan(
            cell, 5, 2, 8192, cell.attn_head_dim, "x", branches=10, flash_blocks=5)
    assert kept["kept_flash_bytes"] == 5 * one
    assert kept["kept_branch_bytes"] == 10 * 2 * 8192 * 2048 * 2  # 0.625 GiB
    assert kept["kept_bytes"] == 5 * 2 * 8192 * 2048 * (4 + 2 * 2) + 5 * one
    assert one == 2 * (32 * (8192 * 128 * 2 + 8192 * 4 + 8192 * 128 * 2)
                       + 2 * 4 * 8192 * 128 * 2)
