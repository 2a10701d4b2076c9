"""Trinity-Mini's blocks on the normal path, against the benchmark's plain
reference (``benchmark/reference/afmoe.py``), at a size the CPU runs: hidden
64, 4 query heads on 1 key/value head of 32 (4 x 32 is not the width, as
published), a window of 24, a gated attention output, four norms a block, a
dense MLP of 160 in the 2 leading layers, then 8 experts of 32 with 2 a
token and a shared one, 6 layers by the pattern sliding, sliding, sliding,
full, vocab 512 with an untied head; two chips share each layer unless a
test says otherwise."""

import importlib.util
import os

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distribuuuu_tpu import models
from distribuuuu_tpu.models import afmoe, glm_moe, lfm2_moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "afmoe_reference", os.path.join(REPO, "benchmark", "reference", "afmoe.py")
)
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

VOCAB, CHUNK = 512, 48
SLIDING, FULL = "sliding_attention", "full_attention"


def build(**kw):
    return models.build_model("afmoe_tiny", num_classes=VOCAB, dtype=jnp.float32, **kw)


def architecture(model) -> dict:
    first, count = model.held
    return {
        "layer_types": list(model.layer_kinds), "num_dense_layers": model.dense_here,
        "hidden_size": model.dim, "num_attention_heads": model.num_heads,
        "num_key_value_heads": model.kv_heads, "head_dim": model.head_dim,
        "sliding_window": model.sliding_window,
        "intermediate_size": model.mlp_hidden,
        "moe_intermediate_size": model.expert_hidden,
        "num_experts": model.num_experts, "num_experts_per_tok": model.top_k,
        "num_shared_experts": model.shared_experts,
        "route_scale": model.routed_scale, "route_norm_eps": 1e-20,
        "mup_enabled": model.mup, "rms_norm_eps": model.norm_eps,
        "rope_theta": model.rope_theta, "vocab_size": model.vocab_size,
        "share_chips": model.share_chips, "share_rank": model.share_rank,
        "experts_held": count, "vocab_held": model.vocab_held,
        "bias_update_rate": model.bias_rate, "balance_loss_weight": model.aux_weight,
    }


def seeded(model, batch=2, seq=100, seed=0):
    """(params, biases, tokens, labels): weights from the program's
    initialiser with the norm scales moved off 1, so that a dropped scale
    would show, biases off 0, and ids from the rows of the vocabulary the
    rank holds."""
    k_init, k_tok, k_scale, k_bias = jax.random.split(jax.random.key(seed), 4)
    variables = flax.linen.meta.unbox(
        jax.jit(model.init)(k_init, model.dummy_input()))
    flat, tree = jax.tree_util.tree_flatten_with_path(variables["params"])
    keys = jax.random.split(k_scale, len(flat))
    flat = [
        leaf * (1 + 0.2 * jax.random.normal(key, leaf.shape))
        if "scale" in jax.tree_util.keystr(path) else leaf
        for (path, leaf), key in zip(flat, keys)]
    biases = jax.tree.map(
        lambda b: 0.02 * jax.random.normal(k_bias, b.shape), variables["batch_stats"])
    ids = model.share_rank * model.vocab_held + jax.random.randint(
        k_tok, (batch, seq + 1), 0, model.vocab_held, jnp.int32)
    return jax.tree.unflatten(tree, flat), biases, ids[:, :-1], ids[:, 1:]


def program_loss(model, params, biases, tokens, labels):
    """(loss, (step metrics, the biases the step leaves, what ``hidden_only``
    returned)): the two calls the step's ``loss_fn`` makes."""
    outputs, mutated = model.apply(
        {"params": params, "batch_stats": biases}, tokens, train=True,
        hidden_only=True, mutable=["batch_stats"])
    loss, _hits, extra = model.head_loss(
        outputs, model.head_kernel(params), labels, topk=(1, 5))
    return loss, (extra, mutated["batch_stats"], outputs)


def mixture_biases(model, biases):
    """``[mixtures, E]`` in the reference's order."""
    names = [f"Block_{i}" for i in range(model.dense_here, len(model.layer_kinds))]
    return jnp.stack([biases[n]["moe"]["router_bias"] for n in names])


def assert_trees_close(got, want, tolerance):
    flat = jax.tree_util.tree_leaves_with_path(got)
    for (path, g), w in zip(flat, jax.tree.leaves(want), strict=True):
        norm = float(jnp.linalg.norm(w))
        assert norm > 0, jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(g - w)) <= tolerance * norm, jax.tree_util.keystr(path)


def test_registry_and_shapes():
    assert {"trinity_mini", "afmoe_tiny"} <= set(models.available_models())
    full = models.build_model("trinity_mini")
    assert (full.dim, len(full.layer_kinds), full.num_heads, full.kv_heads,
            full.head_dim, full.sliding_window, full.num_experts, full.top_k,
            full.shared_experts, full.vocab_size, full.share_chips, full.dense_here
            ) == (2048, 32, 32, 4, 128, 2048, 128, 8, 1, 200192, 1, 2)
    assert full.layer_kinds.count(FULL) == 8
    assert full.layer_kinds[:4] == (SLIDING, SLIDING, SLIDING, FULL)
    assert (full.routed_scale, full.rope_theta, full.norm_eps) == (2.826, 1e4, 1e-5)
    model = build()
    assert (model.held, model.vocab_held) == ((0, 4), 256)
    assert build(share_rank=1).held == (4, 4)
    # shapes alone: nothing here is compiled or run
    variables = jax.eval_shape(lambda: flax.linen.meta.unbox(
        model.init(jax.random.key(0), model.dummy_input())))
    params, tokens = variables["params"], jax.ShapeDtypeStruct((2, 40), jnp.int32)
    logits = jax.eval_shape(model.apply, variables, tokens)
    assert logits.shape == (2, 40, 256) and logits.dtype == jnp.float32
    states, stats = jax.eval_shape(
        lambda v, t: model.apply(v, t, hidden_only=True), variables, tokens)
    assert states.shape == (2, 40, 64) and stats["aux"].shape == (4,)
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
    with pytest.raises(ValueError, match="exceeds the context"):
        jax.eval_shape(model.apply, variables, jax.ShapeDtypeStruct((1, 129), jnp.int32))
    with pytest.raises(ValueError, match="LM.SHARE_CHIPS=3"):
        jax.eval_shape(build(share_chips=3).init, jax.random.key(0),
                       jax.ShapeDtypeStruct((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="inside the list"):
        build(first_layer=4, depth=6).layer_kinds
    with pytest.raises(ValueError, match="whose words are"):
        build(layer_types=("conv", FULL), depth=2).layer_kinds


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


@pytest.mark.parametrize("recompute, dense", [
    (True, 0), (True, 1), (True, 2), (False, 1)],
    ids=["recomputed-0", "recomputed-1", "recomputed-2", "kept-1"])
def test_logits_loss_every_gradient_and_the_bias_equal_the_reference(recompute, dense):
    """Logits, the loss and its terms, the share of the choices on held
    experts, the gradient on every leaf, and the biases one step leaves, with
    0, 1 and 2 leading dense layers under the pattern sliding x 3, full (100
    positions: four windows long; the head in chunks of 48),
    for either of the two chips that share the layers, with every block
    recomputed as the cell runs them and, once, with none. Each side is one
    compiled function: what the CPU would otherwise compile operation by
    operation is most of this file's time."""
    rank = dense % 2
    model = build(share_rank=rank, recompute=recompute, dense_layers=dense, depth=4)
    assert {SLIDING, FULL} <= set(model.layer_kinds)
    params, biases, tokens, labels = seeded(model, seed=dense)
    arch = architecture(model)

    @jax.jit
    def program(p):
        logits = model.apply({"params": p, "batch_stats": biases}, tokens)
        return logits, jax.value_and_grad(
            lambda p: program_loss(model, p, biases, tokens, labels), has_aux=True)(p)

    @jax.jit
    def plain(p):
        def total(p):
            terms = reference.loss(p, biases, tokens, labels, architecture=arch)
            return terms["loss"], terms

        return reference.logits(p, biases, tokens, architecture=arch), jax.value_and_grad(
            total, has_aux=True)(p)

    logits, ((loss, (extra, after, _)), grads) = program(params)
    want_logits, ((_, want), want_grads) = plain(params)
    np.testing.assert_allclose(logits, want_logits, atol=2e-5)
    np.testing.assert_allclose(loss, want["loss"], rtol=1e-6)
    for got, term in (("ce", "ce"), ("moe_aux", "load_balance"),
                      ("moe_held_row_share", "held_row_share")):
        np.testing.assert_allclose(extra[got], want[term], rtol=2e-6, err_msg=got)
    assert float(extra["moe_dropped"]) == 0.0
    assert 0.3 < float(extra["moe_held_row_share"]) < 0.7
    assert_trees_close(grads, want_grads, 2e-5)
    np.testing.assert_array_equal(
        mixture_biases(model, after),
        reference.bias_after(mixture_biases(model, biases), want["counts"], 0.001))
    np.testing.assert_allclose(
        extra["router_bias_abs_max"], jnp.abs(mixture_biases(model, after)).max())


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
        mixer, variables, x = _one_mixer(kind)
        base = mixer.apply(variables, x, jnp.arange(60))
        np.testing.assert_allclose(
            mixer.apply(variables, x, jnp.arange(60) + 7), base, atol=1e-5)
        moved = float(jnp.abs(mixer.apply(variables, x, shuffled) - base).max())
        assert (moved > 1e-3) is moves, (kind, moved)
        if not moves:
            assert moved == 0.0


def test_the_window_is_in_the_sliding_layers_alone():
    """A sliding layer's output at position t does not move when a token
    more than the window behind it changes; a full layer's does."""
    window = build().sliding_window
    for kind, reaches in ((SLIDING, False), (FULL, True)):
        mixer, variables, x = _one_mixer(kind)
        base = mixer.apply(variables, x, jnp.arange(60))
        far = mixer.apply(variables, x.at[0, 5].add(1.0), jnp.arange(60))
        delta = jnp.abs(far - base)[0].max(-1)
        assert float(delta[5 + window - 1]) > 1e-5  # the last row that sees it
        assert (float(delta[5 + window:].max()) > 1e-6) is reaches, kind
        assert not float(delta[:5].max())  # causal either way


def test_the_gate_multiplies_the_heads_and_takes_a_gradient():
    """``out = (heads * sigmoid(x W_g)) W_o``: a gate projection of zeros
    halves the ungated mixer's output, the gate's gradient is the product
    rule's against a central difference, and it is nowhere zero."""
    mixer, variables, x = _one_mixer(SLIDING)
    params = variables["params"]
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
    variables = {"params": params, "batch_stats": biases}
    scaled = {**params, "tok_embed": {"embedding": params["tok_embed"]["embedding"] * 8.0}}
    np.testing.assert_allclose(
        jax.jit(model.apply)(variables, tokens),
        jax.jit(model.clone(mup=False).apply)(
            {"params": scaled, "batch_stats": biases}, tokens),
        atol=1e-5)


@pytest.mark.parametrize("chips", [2, 4])
def test_the_shares_of_a_layer_add_up_to_the_whole_layer(chips):
    """The guide's share test: with 16 experts split over 2 and over 4 ranks,
    the ranks' partial mixture outputs, the shared expert (which every chip
    computes alike) counted ONCE, add up to what the UNCUT reference gives
    for the whole layer."""
    E, k, d, f = 16, 4, 64, 32
    whole = glm_moe.Mixture(d, f, E, k, 1, 2.826, 0.001, (0, E), jnp.float32)
    x = jax.random.normal(jax.random.key(0), (2, 24, d))
    variables = flax.linen.meta.unbox(whole.init(jax.random.key(1), x))
    bias = 0.05 * jax.random.normal(jax.random.key(2), (E,))
    p = variables["params"]
    assert set(p) == {"router", "w_gate", "w_up", "w_down", "shared"}
    arch = {"num_experts_per_tok": k, "route_scale": 2.826, "route_norm_eps": 1e-20,
            "share_rank": 0, "experts_held": E}
    with jax.default_matmul_precision("highest"):
        want = reference._mixture(x, p, bias, arch)[0]
        shared = reference._mlp(x, p["shared"])
    parts, count = [], E // chips
    for rank in range(chips):
        held = slice(rank * count, (rank + 1) * count)
        mine = {**p, **{n: p[n][held] for n in ("w_gate", "w_up", "w_down")}}
        out, stats = glm_moe.Mixture(
            d, f, E, k, 1, 2.826, 0.001, (rank * count, count), jnp.float32,
        ).apply({"params": mine, "batch_stats": {"router_bias": bias}}, x)
        parts.append(out)
        assert 0 < float(stats["held_row_share"]) < 1
        with jax.default_matmul_precision("highest"):  # the reference's share
            np.testing.assert_allclose(out, reference._mixture(
                x, mine, bias, arch, held=(rank * count, count))[0], atol=3e-6)
    np.testing.assert_allclose(sum(parts) - (chips - 1) * shared, want, atol=5e-6)
    # counted every time it is not the layer, and no share alone is
    assert float(jnp.abs(sum(parts) - want).max()) > 1e-3
    assert float(jnp.abs(parts[0] - want).max()) > 1e-3
