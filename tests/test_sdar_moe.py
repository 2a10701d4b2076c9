"""SDAR-30B-A3B-Chat's blocks and its block-diffusion objective on the normal
path, against the benchmark's plain reference
(``benchmark/reference/sdar_moe.py``), at a size the CPU runs: hidden 64, 4
query heads on 1 key/value head of 32 (4 x 32 is not the width, as
published), 8 experts of 32 with 2 a token by a softmax router renormalised
over its choices, no shared expert, no bias, 4 layers, blocks of 4 tokens,
vocab 512 with an untied head; two chips share each layer unless a test says
otherwise. The contracts it answers are ``tests/decoder_contract.py``'s;
below them, what only SDAR has: the noised and the clean copy and what may
read what, positions that repeat, a loss over the masked positions alone, a
router without a bias in the held mixture."""

import json
import os

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_contract as contract
from decoder_contract import sdar_step_key, seeded, stream_key
from distribuuuu_tpu import models
from distribuuuu_tpu.models import glm_moe, lfm2_moe, sdar_moe
from distribuuuu_tpu.ops import moe as moe_ops

ROW = contract.ROWS["sdar"]
REFERENCE = ROW.reference.module


def build(**kw):
    return contract.build(ROW, **kw)


class TestSDAR(contract.Decoder, contract.ThroughLower, contract.Recomputes,
               contract.KeepsTheFlashKernels, contract.ComputesInBfloat16,
               contract.HoldsAShare):
    row = ROW

    def shapes_of_its_own(self, full, model, state, hidden):
        assert full.layer_kinds == (sdar_moe.KIND,) * 48 and full.dense_here == 0
        states, stats, noise = hidden
        assert stats["aux"].shape == (4,) and "bias_abs_max" not in stats
        assert {k: v.shape for k, v in noise.items()} == {
            "masked": (2, 40), "level": (2, 40), "labels": (2, 40)}
        params = state["params"]
        attn = params["Block_3"]["attn"]
        assert set(attn) == {"q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm"}
        assert attn["q_proj"]["kernel"].shape == (64, 128)
        assert attn["k_proj"]["kernel"].shape == attn["v_proj"]["kernel"].shape == (64, 32)
        assert attn["q_norm"]["scale"].shape == attn["k_norm"]["scale"].shape == (32,)
        # every layer a mixture with no shared expert, and no state beside
        # the parameters: this router has no bias
        assert all(set(params[f"Block_{i}"]) == {
            "attn", "moe", "input_norm", "post_attention_norm"} for i in range(4))
        assert set(params["Block_0"]["moe"]) == {"router", "w_gate", "w_up", "w_down"}
        assert "batch_stats" not in state
        assert params["head"].shape == (64, 256)  # untied
        # the mask's id: the published one where the chip's rows hold it,
        # else the last row it holds
        assert (model.mask_token, build(share_rank=1).mask_token) == (255, 511)
        assert build(mask_id=7).mask_token == 7 and build(mask_id=300).mask_token == 255
        assert models.build_model(
            "sdar_30b_a3b", share_chips=8).mask_token == 18991
        assert models.build_model(
            "sdar_30b_a3b", mask_id=151669).mask_token == 151669
        with pytest.raises(ValueError, match="whole number of blocks of 4"):
            jax.eval_shape(model.apply, state, jax.ShapeDtypeStruct((1, 42), jnp.int32))

    def loss_of_its_own(self, model, loss, aux, want):
        # about half the positions are masked, and the program's draws are the
        # reference's own
        assert 0.35 < float(aux.extra["diffusion_masked_share"]) < 0.65
        noise = aux.outputs[2]
        np.testing.assert_array_equal(noise["masked"], want["masked"])
        np.testing.assert_array_equal(noise["level"], want["level"])
        assert float(noise["level"].min()) > model.noise_eps
        assert float(noise["level"].max()) <= 1.0

    def declared_of_its_own(self, arch, model):
        assert model.layer_kinds == (sdar_moe.KIND,) * 3

    def step_of_its_own(self, ran, want):
        assert ran.model.recompute and ran.model.noise_streams == ("diffusion",)
        # hits over the masked positions of a model that knows nothing yet
        assert 0 <= float(ran.metrics["top1"]) <= float(ran.metrics["topk"]) < 20

    def run_once(self, model, params):
        """``o_proj``, whose output the block keeps. (``q_proj`` and
        ``k_proj`` run again whatever is kept: the backward of the per-head
        norm behind each reads the projection's output, as in LFM2's and
        Trinity-Mini's blocks; ``v_proj`` has ``k_proj``'s shape.)"""
        attn = params["Block_0"]["attn"]
        shapes = {name: attn[f"{name}_proj"]["kernel"].shape for name in "qkvo"}
        assert shapes["k"] == shapes["v"] and len(set(shapes.values())) == 3
        return [({shapes["o"]}, 1, 0)]

    def bfloat16_of_its_own(self, model16, params, tokens, labels, got, want, arch,
                            monkeypatch):
        assert abs(float(got.extra["moe_aux"]) - float(want["load_balance"])) < 5e-3
        assert float(got.extra["diffusion_masked_share"]) == float(want["masked_share"])


def _applied(model, params, tokens, key=None, **kw):
    key = sdar_step_key() if key is None else key
    return model.apply({"params": params}, tokens, train=True,
                       rngs={sdar_moe.NOISE_STREAM: key}, **kw)


@pytest.mark.parametrize("block", [4, 20])
def test_the_logits_are_the_references_under_the_steps_key(block):
    """The noised half's logits against the reference's on its own draws."""
    model = build(block_length=block)
    params, _, tokens, _ = seeded(model, batch=2, seq=100)
    want = ROW.reference.logits(params, tokens, architecture=ROW.architecture(model))
    np.testing.assert_allclose(_applied(model, params, tokens), want, atol=2e-5)


def test_nothing_leaks_from_a_blocks_answer_into_its_logits():
    """Changing the clean token of a MASKED position of block b (the noised
    copy stays what it was) moves no logit of a noised row in the blocks up to
    b, its own among them, and moves those of the later blocks: a noised row
    reads the clean past and never its own block's answer."""
    model = build(depth=2)
    params, _, tokens, _ = seeded(model, batch=1, seq=40)
    masked = np.asarray(_applied(model, params, tokens, hidden_only=True)[2]["masked"])[0]
    position = int(np.flatnonzero(masked & (np.arange(40) // 4 == 4))[0])  # in block 4
    base = _applied(model, params, tokens)
    other = tokens.at[0, position].set((tokens[0, position] + 1) % 255)
    moved = np.abs(np.asarray(_applied(model, params, other) - base))[0].max(-1)
    assert not moved[:20].max()  # blocks 0..4, bit for bit
    assert moved[20:].min() > 1e-7  # every later row reads block 4's clean copy


def _mixer(block=4):
    model = build()
    mixer = lfm2_moe.Attention(
        model.dim, model.num_heads, model.kv_heads, model.norm_eps, model.rope_theta,
        jnp.float32, head_dim=model.head_dim, diffusion_block=block)
    x = jax.random.normal(jax.random.key(3), (1, 80, model.dim))
    positions = jnp.tile(jnp.arange(40), 2)
    return mixer, mixer.init(jax.random.key(4), x, positions), x, positions


def test_no_clean_row_reads_a_noised_row_and_a_noised_row_reads_its_block_alone():
    """One mixer over ``[noised ; clean]``: changing a noised row changes no
    clean row's state and, of the noised rows, those of its own block alone;
    changing a clean row of block b changes the clean rows from block b on
    and the noised rows from block b + 1 on."""
    mixer, state, x, positions = _mixer()
    base = mixer.apply(state, x, positions)

    def moved(row):
        out = mixer.apply(state, x.at[0, row].add(1.0), positions)
        return np.asarray(jnp.abs(out - base))[0].max(-1) > 0

    noised = moved(9)  # noised row 9: block 2
    assert not noised[40:].any()
    assert noised[:40].nonzero()[0].tolist() == [8, 9, 10, 11]
    clean = moved(40 + 9)  # clean row 9
    assert clean[:40].nonzero()[0].tolist() == list(range(12, 40))
    assert clean[40:].nonzero()[0].tolist() == list(range(8, 40))


def test_positions_repeat(monkeypatch):
    """Every mixer is handed ``0..S-1`` twice: row i of either half carries
    position i, and ``0..2S-1`` would give other logits."""
    seen = []

    class Spy(lfm2_moe.Attention):
        def __call__(self, x, positions):
            seen.append(np.asarray(positions))
            return super().__call__(x, positions)

    monkeypatch.setattr(sdar_moe, "Attention", Spy)
    model = build(depth=2, recompute=False)
    params, _, tokens, _ = seeded(model, batch=1, seq=40)
    del seen[:]  # the initialisation's
    logits = _applied(model, params, tokens)
    assert len(seen) == 2
    for positions in seen:
        np.testing.assert_array_equal(positions, np.tile(np.arange(40), 2))
    mixer, state, x, positions = _mixer()
    apart = mixer.apply(state, x, jnp.arange(80)) - mixer.apply(state, x, positions)
    assert float(jnp.abs(apart[0, :40]).max()) > 1e-4 and logits.shape == (1, 40, 256)


def test_the_loss_is_over_the_masked_positions_alone_each_against_its_own_token():
    """With nothing masked the cross-entropy is 0 and moves no weight; with
    everything masked at level 1 it is the plain mean over every position of
    the position's OWN token's loss; the batch's ``label`` is never read."""
    model = build(depth=1)
    params, _, tokens, labels = seeded(model, batch=2, seq=40)
    states, stats, noise = _applied(model, params, tokens, hidden_only=True)
    kernel = model.head_kernel(params)

    def ce(masked, level, labels=labels):
        drawn = {**noise, "masked": masked, "level": level}
        return model.head_loss((states, stats, drawn), kernel, labels, topk=(1,))

    nothing = jnp.zeros((2, 40), bool)
    loss, hits, extra = ce(nothing, noise["level"])
    assert float(extra["ce"]) == 0.0 and float(extra["diffusion_masked_share"]) == 0.0
    assert float(loss) == pytest.approx(model.aux_weight * float(extra["moe_aux"]))
    assert not float(hits[0])
    grad = jax.grad(lambda k: model.head_loss(
        (states, stats, {**noise, "masked": nothing}), k, labels, topk=(1,))[0])(kernel)
    assert not float(jnp.abs(grad).max())
    _, _, everything = ce(~nothing, jnp.ones((2, 40)))
    logp = jax.nn.log_softmax(states @ kernel, axis=-1)
    own = -jnp.take_along_axis(logp, tokens[..., None], -1)[..., 0].mean()
    np.testing.assert_allclose(everything["ce"], own, rtol=1e-6)
    _, _, same = ce(~nothing, jnp.ones((2, 40)), labels=labels * 0)
    assert float(same["ce"]) == float(everything["ce"])
    # a position of a block at level t weighs 1 / t
    level = jnp.full((2, 40), 0.25)
    np.testing.assert_allclose(ce(~nothing, level)[2]["ce"], 4 * own, rtol=1e-6)


def test_the_draws_follow_the_stated_rule_and_evaluation_draws_from_a_fixed_key():
    """The model's sown draws are the reference's own from the key its stream
    yields; one level a block; a call without the stream draws from
    ``jax.random.key(0)`` and so gives the same states every time."""
    model = build(depth=1)
    params, _, tokens, _ = seeded(model, batch=2, seq=40)
    arch = ROW.architecture(model)
    for key in (sdar_step_key(), jax.random.key(11)):
        _, sown = model.apply(
            {"params": params}, tokens, train=True, hidden_only=True,
            rngs={sdar_moe.NOISE_STREAM: key}, mutable=["diffusion_noise"])
        masked, level = REFERENCE.noise(stream_key(key), 2, 40, arch)
        np.testing.assert_array_equal(sown["diffusion_noise"]["masked"][0], masked)
        np.testing.assert_array_equal(sown["diffusion_noise"]["level"][0], level)
        blocks = np.asarray(level).reshape(2, 10, 4)
        assert (blocks == blocks[..., :1]).all() and len(np.unique(blocks)) == 20
    fixed = [model.apply({"params": params}, tokens, hidden_only=True) for _ in range(2)]
    np.testing.assert_array_equal(fixed[0][0], fixed[1][0])
    masked, _ = REFERENCE.noise(jax.random.key(0), 2, 40, arch)
    np.testing.assert_array_equal(fixed[0][2]["masked"], masked)


@pytest.mark.parametrize("renormalise", [True, False])
def test_renormalise_sums_the_chosen_weights_to_one_and_the_default_stays(renormalise):
    """``norm_topk_prob`` true: the chosen probabilities over their sum; the
    default (false, OLMoE's) the probabilities as they are, as it was."""
    x = jax.random.normal(jax.random.key(0), (50, 16))
    router = jax.random.normal(jax.random.key(1), (16, 12))
    probs = jax.nn.softmax(x @ router, axis=-1)
    top, experts = jax.lax.top_k(probs, 3)
    got = moe_ops.softmax_route(x, router, 3, **(
        {"renormalise": True} if renormalise else {}))
    np.testing.assert_allclose(got[0], probs, rtol=1e-6)
    np.testing.assert_array_equal(got[2], experts)
    np.testing.assert_allclose(
        got[1], top / top.sum(-1, keepdims=True) if renormalise else top, rtol=1e-6)
    total = np.asarray(got[1].sum(-1))
    assert (np.abs(total - 1) < 1e-6).all() if renormalise else (total < 0.99).any()


def test_the_mixture_carries_a_bias_only_where_its_router_has_one():
    """``Mixture`` with the router handed over holds no ``router_bias`` and
    reports none; without one it is the sigmoid router with its state, as
    GLM's, LFM2's and Trinity-Mini's cells build it."""
    x = jax.random.normal(jax.random.key(0), (2, 24, 64))

    def mixture(**kw):
        return glm_moe.Mixture(64, 32, 8, 2, 0, 1.0, 0.001, (0, 4), jnp.float32, **kw)

    softmax = mixture(route=ROW.mixture["keywords"]["route"])
    state = softmax.init(jax.random.key(1), x)
    assert set(state) == {"params", "moe_route"}
    _, stats = softmax.apply({"params": state["params"]}, x)
    assert set(stats) == {"aux", "load_max_over_mean", "held_row_share"}
    state = mixture().init(jax.random.key(1), x)
    assert set(state) == {"params", "batch_stats", "moe_route"}
    assert set(mixture().apply(state, x)[1]) == {
        "aux", "load_max_over_mean", "held_row_share", "bias_abs_max"}


def test_the_accepted_cells_parameter_trees_are_the_parents():
    """OLMoE's, GLM's, LFM2's and Trinity-Mini's cells build, leaf for leaf
    (path, shape, dtype; the routers' biases in ``batch_stats`` too), the
    trees the parent of SDAR's PR built (``tests/data/decoder_trees.json``,
    taken from its checkout): a mixture that takes its router as a function
    changed none of them."""
    cells = {
        "olmoe_1b_7b": dict(depth=1),
        "glm_4_7_flash": dict(depth=5, share_chips=8),
        "lfm2_24b_a2b": dict(first_layer=1, depth=5, share_chips=8, recompute=False),
        "trinity_mini": dict(first_layer=1, depth=5, share_chips=8),
    }
    with open(os.path.join(contract.REPO, "tests", "data", "decoder_trees.json")) as f:
        parents = json.load(f)
    for arch, kw in cells.items():
        model = models.build_model(arch, **kw)
        state = flax.linen.meta.unbox(jax.eval_shape(
            lambda model=model: model.init(jax.random.key(0), model.dummy_input())))
        tree = {c: {jax.tree_util.keystr(path): [list(leaf.shape), str(leaf.dtype)]
                    for path, leaf in jax.tree_util.tree_leaves_with_path(state[c])}
                for c in ("params", "batch_stats") if c in state}
        assert tree == parents[arch], arch
