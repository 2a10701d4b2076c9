"""GLM-4.7-Flash's blocks on the normal path, against the benchmark's plain
reference (``benchmark/reference/glm_moe.py``), at a size the CPU runs:
hidden 64, 4 heads of latent attention (score dim 24 + 8, value dim 32), a
dense MLP of 160, then 8 experts of 32 with 2 a token and a shared one, 1 + 2
layers and the MTP module, vocab 512; two chips share each layer unless a
test says otherwise."""

import functools
import importlib.util
import os

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distribuuuu_tpu import models
from distribuuuu_tpu.models import glm_moe
from distribuuuu_tpu.ops import moe as moe_ops
from test_ouro import forward_matmuls

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "glm_moe_reference", os.path.join(REPO, "benchmark", "reference", "glm_moe.py")
)
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

VOCAB, CHUNK = 512, 48


def build(**kw):
    return models.build_model("glm_moe_tiny", num_classes=VOCAB, dtype=jnp.float32, **kw)


def architecture(model) -> dict:
    first, count = model.held
    return {
        "layers": model.depth, "first_k_dense_replace": model.dense_layers,
        "num_nextn_predict_layers": model.mtp_layers, "hidden_size": model.dim,
        "num_attention_heads": model.num_heads, "q_lora_rank": model.q_lora_rank,
        "kv_lora_rank": model.kv_lora_rank,
        "qk_nope_head_dim": model.qk_nope_head_dim,
        "qk_rope_head_dim": model.qk_rope_head_dim, "v_head_dim": model.v_head_dim,
        "intermediate_size": model.mlp_hidden,
        "moe_intermediate_size": model.expert_hidden,
        "n_routed_experts": model.num_experts, "num_experts_per_tok": model.top_k,
        "n_shared_experts": model.shared_experts,
        "routed_scaling_factor": model.routed_scale,
        "rms_norm_eps": model.rms_norm_eps, "rope_theta": model.rope_theta,
        "vocab_size": model.vocab_size, "share_chips": model.share_chips,
        "share_rank": model.share_rank, "experts_held": count,
        "vocab_held": model.vocab_held, "bias_update_rate": model.bias_rate,
        "mtp_loss_weight": model.mtp_weight, "balance_loss_weight": model.aux_weight,
    }


def seeded(model, batch=2, seq=100, seed=0):
    """(params, biases, tokens, labels): weights from the program's
    initialiser with the norm scales moved off 1, so that a dropped or
    misplaced scale would show, biases off 0, so that a router that ignored
    them would, and ids from the rows of the vocabulary the rank holds."""
    k_init, k_tok, k_scale, k_bias = jax.random.split(jax.random.key(seed), 4)
    variables = flax.linen.meta.unbox(model.init(k_init, model.dummy_input()))
    flat, tree = jax.tree_util.tree_flatten_with_path(variables["params"])
    keys = jax.random.split(k_scale, len(flat))
    flat = [
        leaf * (1 + 0.2 * jax.random.normal(k, leaf.shape))
        if "scale" in jax.tree_util.keystr(path) else leaf
        for (path, leaf), k in zip(flat, keys)
    ]
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
    """``[mixtures, E]`` in the reference's order: the trunk's, the MTP's."""
    names = [f"Block_{i}" for i in range(model.dense_layers, model.depth)]
    return jnp.stack([biases[n]["moe"]["router_bias"] for n in names + ["mtp_block"]])


def assert_trees_close(got, want, tolerance):
    flat = jax.tree_util.tree_leaves_with_path(got)
    for (path, g), w in zip(flat, jax.tree.leaves(want), strict=True):
        norm = float(jnp.linalg.norm(w))
        assert norm > 0, jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(g - w)) <= tolerance * norm, jax.tree_util.keystr(path)


def test_registry_and_shapes():
    assert {"glm_4_7_flash", "glm_moe_tiny"} <= set(models.available_models())
    full = models.build_model("glm_4_7_flash")
    assert (full.dim, full.depth, full.num_heads, full.num_experts, full.top_k,
            full.vocab_size, full.share_chips) == (2048, 47, 20, 64, 4, 154880, 1)
    assert (full.qk_nope_head_dim + full.qk_rope_head_dim, full.v_head_dim) == (256, 256)
    model = build()
    assert (model.held, model.vocab_held) == ((0, 4), 256)
    assert build(share_rank=1).held == (4, 4)
    params, biases, tokens, _ = seeded(model, seq=40)
    logits = model.apply({"params": params, "batch_stats": biases}, tokens)
    assert logits.shape == (2, 40, 256) and logits.dtype == jnp.float32
    states, stats = model.apply(
        {"params": params, "batch_stats": biases}, tokens, hidden_only=True)
    assert states.shape == (2, 2, 40, 64) and stats["aux"].shape == (3,)
    # layer 0 is dense, the later ones and the MTP module mixtures of the held
    assert set(params["Block_0"]) == {"attn", "attn_norm", "mlp", "mlp_norm"}
    for name in ("Block_1", "Block_2", "mtp_block"):
        assert params[name]["moe"]["w_gate"].shape == (4, 64, 32)
        assert params[name]["moe"]["router"].shape == (64, 8)
        assert biases[name]["moe"]["router_bias"].shape == (8,)
    assert params["tok_embed"]["embedding"].shape == (256, 64)
    assert params["head"].shape == (64, 256)  # ONE embedding, ONE head
    with pytest.raises(ValueError, match="exceeds the context"):
        model.apply({"params": params, "batch_stats": biases},
                    jnp.zeros((1, 129), jnp.int32))
    with pytest.raises(ValueError, match="LM.SHARE_CHIPS=3"):
        build(share_chips=3).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))


@pytest.mark.parametrize("rank", [0, 1])
def test_loss_terms_every_gradient_and_the_bias_equal_the_reference(rank):
    """Both losses, the balancing term, the share of the choices on held
    experts, the gradient on every leaf, and the biases one step leaves, for
    either of the two chips that share the layers (the head in chunks of 48
    of 100 positions)."""
    model = build(share_rank=rank)
    params, biases, tokens, labels = seeded(model, seed=rank)
    (loss, (extra, after, _)), grads = jax.value_and_grad(
        lambda p: program_loss(model, p, biases, tokens, labels), has_aux=True)(params)
    arch = architecture(model)

    def plain(p):
        terms = reference.loss(p, biases, tokens, labels, architecture=arch)
        return terms["loss"], terms

    (_, want), want_grads = jax.value_and_grad(plain, has_aux=True)(params)
    np.testing.assert_allclose(loss, want["loss"], rtol=1e-6)
    for got, term in (("ce", "ce"), ("ce_mtp", "ce_mtp"), ("moe_aux", "load_balance"),
                      ("moe_held_row_share", "held_row_share")):
        np.testing.assert_allclose(extra[got], want[term], rtol=2e-6, err_msg=got)
    assert float(extra["moe_dropped"]) == 0.0
    assert 0.3 < float(extra["moe_held_row_share"]) < 0.7
    assert_trees_close(grads, want_grads, 2e-5)
    np.testing.assert_array_equal(
        mixture_biases(model, after),
        reference.bias_after(mixture_biases(model, biases), want["counts"], 0.001))
    np.testing.assert_allclose(  # of the biases the step leaves
        extra["router_bias_abs_max"], jnp.abs(mixture_biases(model, after)).max())


def test_the_shares_of_a_layer_add_up_to_the_whole_layer():
    """The guide's share test: the routed parts that the chips of a group
    give, with the shared expert (which every chip computes alike) counted
    once, add up to what the UNCUT reference gives for the whole layer."""
    E, k, d, f, chips = 8, 2, 64, 32, 4
    whole = glm_moe.Mixture(d, f, E, k, 1, 1.8, 0.001, (0, E), jnp.float32)
    x = jax.random.normal(jax.random.key(0), (2, 24, d))
    variables = flax.linen.meta.unbox(whole.init(jax.random.key(1), x))
    variables["batch_stats"]["router_bias"] = 0.05 * jax.random.normal(
        jax.random.key(2), (E,))
    p = variables["params"]
    arch = {"num_experts_per_tok": k, "routed_scaling_factor": 1.8,
            "share_rank": 0, "experts_held": E}
    with jax.default_matmul_precision("highest"):
        want = reference._mixture(x, p, variables["batch_stats"]["router_bias"], arch)[0]
        shared = reference._mlp(x, p["shared"])
    parts = []
    for rank in range(chips):
        count = E // chips
        held = slice(rank * count, (rank + 1) * count)
        mine = {**p, **{n: p[n][held] for n in ("w_gate", "w_up", "w_down")}}
        out, stats = glm_moe.Mixture(
            d, f, E, k, 1, 1.8, 0.001, (rank * count, count), jnp.float32,
        ).apply({"params": mine, "batch_stats": variables["batch_stats"]}, x)
        parts.append(out - shared)  # this chip's routed part
        assert 0 < float(stats["held_row_share"]) < 1
    np.testing.assert_allclose(sum(parts) + shared, want, atol=2e-6)
    # and no single share is the layer
    assert float(jnp.abs(parts[0] + shared - want).max()) > 1e-3


def _dense_part(params, x, weights, indices, first):
    """A loop over the held experts, each on every token, masked."""
    out = jnp.zeros_like(x)
    for j in range(params["w_gate"].shape[0]):
        y = (jax.nn.silu(x @ params["w_gate"][j]) * (x @ params["w_up"][j])
             ) @ params["w_down"][j]
        out = out + y * jnp.where(indices == first + j, weights, 0).sum(-1)[:, None]
    return out


@pytest.mark.parametrize("interpret", [None, True], ids=["ragged_dot", "kernels"])
@pytest.mark.parametrize("where", ["none", "all", "mixed"])
def test_held_routing_at_both_extremes_of_the_buffer(where, interpret):
    """No (token, slot) on a held expert, every one on them (the buffer's
    bound: all T * k rows live), and a mix: the part and its gradients are a
    masked dense loop's, finite, with nothing dropped; through
    ``lax.ragged_dot`` and through the interpreted kernels."""
    T, k, d, f, held, total, first = 512, 2, 128, 128, 2, 8, 2
    keys = jax.random.split(jax.random.key(3), 6)
    params = {
        "w_gate": 0.1 * jax.random.normal(keys[0], (held, d, f)),
        "w_up": 0.1 * jax.random.normal(keys[1], (held, d, f)),
        "w_down": 0.1 * jax.random.normal(keys[2], (held, f, d)),
    }
    x = jax.random.normal(keys[3], (T, d))
    weights = jax.random.uniform(keys[4], (T, k))
    lo, hi = {"none": (4, 8), "all": (2, 4), "mixed": (0, 8)}[where]
    indices = jax.random.randint(keys[5], (T, k), lo, hi, jnp.int32)

    def part(params, x, weights):
        return moe_ops.sorted_experts(
            params, x, weights, indices, held=(first, total), interpret=interpret)

    def total_of(fn):
        return lambda *a: jnp.sum(fn(*a) * jnp.cos(jnp.arange(d)))

    want = _dense_part(params, x, weights, indices, first)
    got = jax.jit(part)(params, x, weights)
    np.testing.assert_allclose(got, want, atol=2e-4)
    grads = jax.jit(jax.grad(total_of(part), argnums=(0, 1, 2)))(params, x, weights)
    wants = jax.grad(
        total_of(lambda p, x, w: _dense_part(p, x, w, indices, first)),
        argnums=(0, 1, 2))(params, x, weights)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(wants), strict=True):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(a, b, atol=3e-4)
    if where == "none":
        assert not float(jnp.abs(got).max())
        assert not any(float(jnp.abs(g).max()) for g in jax.tree.leaves(grads))


def test_all_experts_held_is_the_unsharded_function():
    """``held=None`` and ``held=(0, E)`` trace one program, without the
    masks a partial share adds."""
    T, k, E = 64, 2, 4
    keys = jax.random.split(jax.random.key(4), 5)
    params = {n: 0.1 * jax.random.normal(key, shape) for n, key, shape in (
        ("w_gate", keys[0], (E, 16, 8)), ("w_up", keys[1], (E, 16, 8)),
        ("w_down", keys[2], (E, 8, 16)))}
    x = jax.random.normal(keys[3], (T, 16))
    indices = jax.random.randint(keys[4], (T, k), 0, E, jnp.int32)
    weights = jnp.full((T, k), 0.5)
    texts = [
        str(jax.make_jaxpr(lambda: moe_ops.sorted_experts(
            params, x, weights, indices, held=held))())
        for held in (None, (0, E))
    ]
    assert texts[0] == texts[1]
    partial = str(jax.make_jaxpr(lambda: moe_ops.sorted_experts(
        {n: w[:2] for n, w in params.items()}, x, weights, indices, held=(0, E)))())
    assert partial.count("select_n") > texts[0].count("select_n")  # the masks


def test_the_sigmoid_router_chooses_by_the_bias_and_weighs_without_it():
    scores = jnp.asarray([[0.9, 0.5, 0.4, 0.1], [0.2, 0.3, 0.6, 0.7]])
    bias = jnp.asarray([-0.6, 0.0, 0.0, 0.35])
    weights, indices = moe_ops.top_k_biased(scores, bias, 2, scale=1.8)
    # token 0: 0.9 - 0.6 falls behind 0.5 and 0.1 + 0.35; token 1 keeps its top
    np.testing.assert_array_equal(indices, [[1, 3], [3, 2]])
    np.testing.assert_allclose(weights, [[1.8 * 0.5 / 0.6, 1.8 * 0.1 / 0.6],
                                         [1.8 * 0.7 / 1.3, 1.8 * 0.6 / 1.3]], rtol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.8, rtol=1e-6)
    # the rule: under the mean up, over it down, at it still
    np.testing.assert_allclose(
        moe_ops.bias_after(bias, jnp.asarray([4, 0, 2, 2]), 0.001),
        bias + jnp.asarray([-0.001, 0.001, 0.0, 0.0]))
    # no gradient reaches the bias, through the choice or through the weights
    grad = jax.grad(lambda b: moe_ops.top_k_biased(scores, b, 2, 1.8)[0].sum())(bias)
    assert not float(jnp.abs(grad).max())


def test_the_bias_takes_no_gradient_and_sits_in_no_optimizer_leaf():
    model = build()
    params, biases, tokens, labels = seeded(model, seq=40)
    grad = jax.grad(
        lambda b: program_loss(model, params, b, tokens, labels)[0])(biases)
    assert all(not float(jnp.abs(g).max()) for g in jax.tree.leaves(grad))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(params)]
    assert not any("bias" in p for p in paths) and len(paths) == 67
    # evaluation routes by the bias and leaves it where it is
    _, mutated = model.apply(
        {"params": params, "batch_stats": biases}, tokens, train=False,
        hidden_only=True, mutable=["batch_stats"])
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool((a == b).all()), mutated["batch_stats"], biases))


def test_mtp_reads_the_token_after_next_and_not_its_last_position():
    """``ce_mtp`` at position t is the head on the MTP state against
    ``label[t + 1]``, a mean over the S - 1 positions that have one; the
    last position carries no weight and no gradient; one walk gives ``ce +
    mtp_weight * ce_mtp``."""
    model = build()
    params, biases, tokens, labels = seeded(model, batch=3, seq=50)
    _, (extra, _, (states, stats)) = program_loss(model, params, biases, tokens, labels)
    logp = jax.nn.log_softmax(states.astype(jnp.float32) @ params["head"], axis=-1)
    nll = -jnp.take_along_axis(
        logp[:, 1, :-1], labels[:, 1:, None], axis=-1)[..., 0]
    np.testing.assert_allclose(extra["ce_mtp"], nll.mean(), rtol=1e-6)
    main = -jnp.take_along_axis(logp[:, 0], labels[..., None], axis=-1)[..., 0]
    np.testing.assert_allclose(extra["ce"], main.mean(), rtol=1e-6)

    def walk(s):
        return model.head_loss((s, stats), params["head"], labels, topk=(1, 5))[0]

    np.testing.assert_allclose(
        walk(states), extra["ce"] + 0.3 * extra["ce_mtp"] + 1e-4 * extra["moe_aux"],
        rtol=1e-6)
    d_states = jax.grad(walk)(states)
    assert not float(jnp.abs(d_states[:, 1, -1]).max())
    assert float(jnp.abs(d_states[:, 1, -2]).max()) > 0
    assert float(jnp.abs(d_states[:, 0, -1]).max()) > 0
    # the MTP module's input: the embedding of the input one to the left
    moved = tokens.at[:, 1:].set((tokens[:, 1:] + 1) % 256)
    _, (_, _, (other, _)) = program_loss(model, params, biases, moved, labels)
    assert float(jnp.abs(other[:, 1, 0] - states[:, 1, 0]).max()) > 0  # reads x_1
    np.testing.assert_array_equal(other[:, 0, 0], states[:, 0, 0])  # the trunk does not


def test_the_recomputing_step_equals_the_step_that_keeps_everything():
    model = build()
    params, biases, tokens, labels = seeded(model, seq=40)

    def run(m):
        return jax.value_and_grad(
            lambda p: program_loss(m, p, biases, tokens, labels)[0])(params)

    (a, ga), (b, gb) = run(model), run(model.clone(recompute=False))
    np.testing.assert_allclose(a, b, rtol=1e-6)
    assert_trees_close(ga, gb, 1e-5)


def test_what_the_recomputed_blocks_keep_of_the_flash_kernel_changes_no_bit(monkeypatch):
    """With the kernels run (the interpreter, forced, where ``auto`` runs
    them compiled on the chip) a recomputed block keeps what the backward
    kernel reads, the forward kernel's output and log-sum-exp and its q, k
    and v, and the attention branch's output: a block, the MTP module's
    too, runs the forward kernel, the two projections out of the latents
    (``q_b_proj``, ``kv_b_proj``) and ``o_proj`` once, where a plain
    ``nn.remat`` (the policy keeping nothing) runs them
    twice, and the loss and every gradient leaf are that step's bit for
    bit: what is kept is what was recomputed. Against the step that
    recomputes nothing the loss is the same bits and the gradients are as
    near as they were before anything was kept (jax sums a value's several
    cotangents in another order under a checkpoint)."""
    from distribuuuu_tpu.ops import flash_attention as fa

    monkeypatch.setattr(
        fa, "flash_attention", functools.partial(fa.flash_attention, interpret=True))
    model = build(attn_impl="flash")
    params, biases, tokens, labels = seeded(model, batch=1, seq=40)
    blocks = model.depth + model.mtp_layers
    attn = params["Block_0"]["attn"]
    out_of_the_latents = {
        attn["q_b_proj"]["kernel"].shape, attn["kv_b_proj"]["kernel"].shape}
    others = {leaf.shape for path, leaf in jax.tree_util.tree_leaves_with_path(params)
              if "_b_proj" not in jax.tree_util.keystr(path)}
    assert len(out_of_the_latents) == 2 and not out_of_the_latents & others
    # W_o's shape is the MTP module's ``mtp_proj``'s too, which no block holds
    w_o = {attn["o_proj"]["kernel"].shape}
    assert [jax.tree_util.keystr(path)[-30:] for path, leaf in
            jax.tree_util.tree_leaves_with_path(params)
            if leaf.shape in w_o].count("['mtp_proj']['kernel']") == 1

    def run(variant, forward_calls, projections, outputs):
        def loss(p):
            return program_loss(variant, p, biases, tokens, labels)[0]

        traced = jax.jit(jax.value_and_grad(loss)).trace(params)
        text = str(traced.jaxpr)
        assert text.count("name=dtpu_flash_fwd") == forward_calls
        assert text.count("name=dtpu_flash_bwd") == blocks
        assert forward_matmuls(traced.jaxpr.jaxpr, out_of_the_latents) == projections
        assert forward_matmuls(traced.jaxpr.jaxpr, w_o) == outputs + 1
        return traced.lower().compile()(params)

    kept = run(model, blocks, 2 * blocks, blocks)
    nothing_recomputed = run(model.clone(recompute=False), blocks, 2 * blocks, blocks)
    monkeypatch.setattr(
        jax.checkpoint_policies, "save_only_these_names",
        lambda *names: jax.checkpoint_policies.nothing_saveable)
    plain = run(model, 2 * blocks, 4 * blocks, 2 * blocks)
    assert float(kept[0]) == float(plain[0]) == float(nothing_recomputed[0])
    flat = jax.tree_util.tree_leaves_with_path(kept[1])
    for (path, got), want in zip(flat, jax.tree.leaves(plain[1]), strict=True):
        assert float(jnp.abs(want).max()) > 0, jax.tree_util.keystr(path)
        np.testing.assert_array_equal(got, want, err_msg=jax.tree_util.keystr(path))
    assert_trees_close(kept[1], nothing_recomputed[1], 1e-5)


@pytest.mark.parametrize("engaged", [True, False], ids=["kernel", "scan"])
def test_the_plan_says_what_the_cells_blocks_keep(tmp_path, monkeypatch, engaged):
    """``share.plan`` at ``glm_4_7_flash.train_seq8192``'s shape (1 + 4
    layers and the MTP module, 1 x 8192 tokens, 20 heads of 256): six
    float32 inputs of 64 MiB, the six attention branches' outputs of 32 MiB
    (bfloat16; the FFN branches' are read by nothing) and, where the flash
    kernel runs, 6 x (80 MiB of output + 0.625 MiB of log-sum-exp + 3 x 80
    MiB of q, k and v); nothing of the kernel's on the scan path."""
    import json

    from distribuuuu_tpu.ops import pallas as tier
    from distribuuuu_tpu.telemetry import schema, spans

    if engaged:  # what the tier answers on one chip
        monkeypatch.setattr(tier, "interpret_mode", lambda: False)
        monkeypatch.setattr(tier, "compiled_across_devices", lambda: False)
    model = models.build_model(
        "glm_4_7_flash", num_classes=154880, depth=5, share_chips=8)
    glm_moe._planned.clear()
    path = spans.setup_telemetry(str(tmp_path), rank=0)
    try:
        for _ in range(2):  # once a shape
            glm_moe._say_plan(model, 1, 8192)
    finally:
        spans.close_telemetry()
        glm_moe._planned.clear()
    plans = [r for r in map(json.loads, open(path)) if r.get("kind") == "share.plan"]
    assert len(plans) == 1
    plan = plans[0]
    schema.validate_record(plan)
    assert (plan["experts_held"], plan["vocab_held"]) == (8, 19360)
    assert plan["kept_flash_bytes"] == (2_017_198_080 if engaged else 0)
    assert plan["kept_branch_bytes"] == 6 * 8192 * 2048 * 2
    assert plan["kept_bytes"] == 6 * 8192 * 2048 * (4 + 2) + plan["kept_flash_bytes"]
    said = ("every block, the MTP module's too, from its float32 input, the outputs "
            "of its branches that are read again (whose last matmuls run once)")
    assert plan["recomputed"] == said + (
        " and the flash kernel's output, log-sum-exp, q, k and v" if engaged else "")


def test_bfloat16_program_stays_near_the_reference_because_its_float32_parts_do():
    model = build().clone(dtype=jnp.bfloat16)
    params, biases, tokens, labels = seeded(model, seq=64)
    _, (extra, _, _) = program_loss(model, params, biases, tokens, labels)
    want = reference.loss(params, biases, tokens, labels,
                          architecture=architecture(model))
    assert abs(float(extra["ce"]) - float(want["ce"])) < 5e-3
    assert abs(float(extra["ce_mtp"]) - float(want["ce_mtp"])) < 5e-3
