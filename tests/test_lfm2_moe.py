"""LFM2-24B-A2B's blocks on the normal path, against the benchmark's plain
reference (``benchmark/reference/lfm2_moe.py``), at a size the CPU runs:
hidden 64, 4 query heads on 2 key/value heads of 16, a gated short
convolution of 3 taps, a dense MLP of 160 in the 2 leading layers, then 8
experts of 32 with 2 a token, 6 layers by the pattern conv, conv, attention,
conv, vocab 512 with the head tied to the embedding; two chips share each
layer unless a test says otherwise."""

import functools
import importlib.util
import os

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distribuuuu_tpu import models
from distribuuuu_tpu.models import glm_moe, lfm2_moe
from distribuuuu_tpu.ops import flash_attention as fa
from distribuuuu_tpu.ops import moe as moe_ops
from distribuuuu_tpu.ops.short_conv import gated_short_conv

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "lfm2_moe_reference", os.path.join(REPO, "benchmark", "reference", "lfm2_moe.py")
)
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

VOCAB, CHUNK = 512, 48


def build(**kw):
    return models.build_model("lfm2_moe_tiny", num_classes=VOCAB, dtype=jnp.float32, **kw)


def architecture(model) -> dict:
    first, count = model.held
    return {
        "layer_types": list(model.layer_kinds), "num_dense_layers": model.dense_here,
        "hidden_size": model.dim, "num_attention_heads": model.num_heads,
        "num_key_value_heads": model.kv_heads, "conv_L_cache": model.conv_taps,
        "intermediate_size": model.mlp_hidden,
        "moe_intermediate_size": model.expert_hidden,
        "num_experts": model.num_experts, "num_experts_per_tok": model.top_k,
        "routed_scaling_factor": model.routed_scale,
        "route_norm_eps": model.route_norm_eps, "norm_eps": model.norm_eps,
        "rope_theta": model.rope_theta, "vocab_size": model.vocab_size,
        "share_chips": model.share_chips, "share_rank": model.share_rank,
        "experts_held": count, "vocab_held": model.vocab_held,
        "bias_update_rate": model.bias_rate, "balance_loss_weight": model.aux_weight,
    }


def seeded(model, batch=2, seq=100, seed=0):
    """(params, biases, tokens, labels): weights from the program's
    initialiser with the norm scales moved off 1 and the filters made large,
    so that a dropped scale or a shifted tap would show, biases off 0, and
    ids from the rows of the vocabulary the rank holds."""
    k_init, k_tok, k_scale, k_bias = jax.random.split(jax.random.key(seed), 4)
    variables = flax.linen.meta.unbox(model.init(k_init, model.dummy_input()))
    flat, tree = jax.tree_util.tree_flatten_with_path(variables["params"])
    keys = jax.random.split(k_scale, len(flat))

    def moved(path, leaf, key):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return leaf * (1 + 0.2 * jax.random.normal(key, leaf.shape))
        return jax.random.normal(key, leaf.shape) if "filter" in name else leaf

    flat = [moved(path, leaf, k) for (path, leaf), k in zip(flat, keys)]
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
    assert {"lfm2_24b_a2b", "lfm2_moe_tiny"} <= set(models.available_models())
    full = models.build_model("lfm2_24b_a2b")
    assert (full.dim, len(full.layer_kinds), full.num_heads, full.kv_heads,
            full.num_experts, full.top_k, full.vocab_size, full.share_chips,
            full.dense_here, full.conv_taps) == (2048, 40, 32, 8, 64, 4, 65536, 1, 2, 3)
    assert full.layer_kinds.count("full_attention") == 10
    assert full.layer_kinds[:4] == ("conv", "conv", "full_attention", "conv")
    model = build()
    assert (model.held, model.vocab_held) == ((0, 4), 256)
    assert build(share_rank=1).held == (4, 4)
    params, biases, tokens, _ = seeded(model, seq=40)
    logits = model.apply({"params": params, "batch_stats": biases}, tokens)
    assert logits.shape == (2, 40, 256) and logits.dtype == jnp.float32
    states, stats = model.apply(
        {"params": params, "batch_stats": biases}, tokens, hidden_only=True)
    assert states.shape == (2, 40, 64) and stats["aux"].shape == (4,)
    assert params["Block_0"]["short_conv"]["filter"].shape == (64, 3)
    assert params["Block_0"]["short_conv"]["in_proj"]["kernel"].shape == (64, 192)
    attn = params["Block_2"]["attn"]
    assert attn["q_proj"]["kernel"].shape == (64, 64)
    assert attn["k_proj"]["kernel"].shape == attn["v_proj"]["kernel"].shape == (64, 32)
    assert attn["q_norm"]["scale"].shape == attn["k_norm"]["scale"].shape == (16,)
    assert "shared" not in params["Block_2"]["moe"]  # no shared expert
    # ONE matrix is embedding and head
    assert "head" not in params
    assert model.head_kernel(params).shape == (64, 256)
    with pytest.raises(ValueError, match="exceeds the context"):
        model.apply({"params": params, "batch_stats": biases},
                    jnp.zeros((1, 129), jnp.int32))
    with pytest.raises(ValueError, match="LM.SHARE_CHIPS=3"):
        build(share_chips=3).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="inside the list"):
        build(first_layer=4, depth=6).layer_kinds


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


@pytest.mark.parametrize("recompute", [True, False], ids=["recomputed", "kept"])
@pytest.mark.parametrize("rank", [0, 1])
def test_logits_loss_every_gradient_and_the_bias_equal_the_reference(rank, recompute):
    """Logits, the loss and its terms, the share of the choices on held
    experts, the gradient on every leaf (the embedding's from both of its
    sources), and the biases one step leaves, for either of the two chips
    that share the layers (the head in chunks of 48 of 100 positions), with
    every block recomputed and with none."""
    model = build(share_rank=rank, recompute=recompute)
    params, biases, tokens, labels = seeded(model, seed=rank)
    arch = architecture(model)
    np.testing.assert_allclose(
        model.apply({"params": params, "batch_stats": biases}, tokens),
        reference.logits(params, biases, tokens, architecture=arch), atol=2e-5)
    (loss, (extra, after, _)), grads = jax.value_and_grad(
        lambda p: program_loss(model, p, biases, tokens, labels), has_aux=True)(params)

    def plain(p):
        terms = reference.loss(p, biases, tokens, labels, architecture=arch)
        return terms["loss"], terms

    (_, want), want_grads = jax.value_and_grad(plain, has_aux=True)(params)
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


def test_the_tied_heads_gradient_has_two_sources():
    """The embedding's gradient is the lookup's plus the head's: each alone
    is another matrix, and their sum is the gradient of the tied loss."""
    model = build()
    params, biases, tokens, labels = seeded(model, seq=40)

    def loss(table, head):
        tied = {**params, "tok_embed": {"embedding": table}}
        outputs = model.apply(
            {"params": tied, "batch_stats": biases}, tokens, hidden_only=True)
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


@pytest.mark.parametrize("chips", [2, 4])
def test_the_shares_of_a_layer_add_up_to_the_whole_layer(chips):
    """The guide's share test: with 8 experts split over 2 and over 4 ranks,
    the ranks' partial mixture outputs (there is no shared expert to count
    once) add up to what the UNCUT reference gives for the whole layer."""
    E, k, d, f = 8, 2, 64, 32
    whole = glm_moe.Mixture(
        d, f, E, k, 0, 1.0, 0.001, (0, E), jnp.float32, norm_eps=1e-6)
    x = jax.random.normal(jax.random.key(0), (2, 24, d))
    variables = flax.linen.meta.unbox(whole.init(jax.random.key(1), x))
    bias = 0.05 * jax.random.normal(jax.random.key(2), (E,))
    p = variables["params"]
    assert set(p) == {"router", "w_gate", "w_up", "w_down"}
    arch = {"num_experts_per_tok": k, "routed_scaling_factor": 1.0,
            "route_norm_eps": 1e-6, "share_rank": 0, "experts_held": E}
    with jax.default_matmul_precision("highest"):
        want = reference._mixture(x, p, bias, arch)[0]
    parts, count = [], E // chips
    for rank in range(chips):
        held = slice(rank * count, (rank + 1) * count)
        mine = {**p, **{n: p[n][held] for n in ("w_gate", "w_up", "w_down")}}
        out, stats = glm_moe.Mixture(
            d, f, E, k, 0, 1.0, 0.001, (rank * count, count), jnp.float32,
            norm_eps=1e-6,
        ).apply({"params": mine, "batch_stats": {"router_bias": bias}}, x)
        parts.append(out)
        assert 0 < float(stats["held_row_share"]) < 1
        with jax.default_matmul_precision("highest"):  # the reference's share
            np.testing.assert_allclose(out, reference._mixture(
                x, mine, bias, arch, held=(rank * count, count))[0], atol=2e-6)
    np.testing.assert_allclose(sum(parts), want, atol=2e-6)
    assert float(jnp.abs(parts[0] - want).max()) > 1e-3  # no share is the layer


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
