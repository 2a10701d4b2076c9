"""GLM-4.7-Flash's blocks on the normal path, against the benchmark's plain
reference (``benchmark/reference/glm_moe.py``), at a size the CPU runs:
hidden 64, 4 heads of latent attention (score dim 24 + 8, value dim 32), a
dense MLP of 160, then 8 experts of 32 with 2 a token and a shared one, 1 + 2
layers and the MTP module, vocab 512; two chips share each layer unless a
test says otherwise. The contracts it answers are
``tests/decoder_contract.py``'s; below them, what only GLM has: the held
experts' routing, the sigmoid router and its bias, the MTP module."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_contract as contract
from decoder_contract import program_loss, seeded, variables
from distribuuuu_tpu.ops import moe as moe_ops

ROW = contract.ROWS["glm"]


def build(**kw):
    return contract.build(ROW, **kw)


class TestGLM(contract.Decoder, contract.ThroughLower, contract.Recomputes,
              contract.KeepsTheFlashKernels, contract.ComputesInBfloat16,
              contract.HoldsAShare):
    row = ROW

    def shapes_of_its_own(self, full, model, state, hidden):
        assert (full.qk_nope_head_dim + full.qk_rope_head_dim, full.v_head_dim) == (256, 256)
        assert hidden[1]["aux"].shape == (3,)
        params, biases = state["params"], state["batch_stats"]
        # layer 0 is dense, the later ones and the MTP module mixtures of the held
        assert set(params["Block_0"]) == {"attn", "attn_norm", "mlp", "mlp_norm"}
        for name in ("Block_1", "Block_2", "mtp_block"):
            assert params[name]["moe"]["w_gate"].shape == (4, 64, 32)
            assert params[name]["moe"]["router"].shape == (64, 8)
            assert biases[name]["moe"]["router_bias"].shape == (8,)
        assert params["tok_embed"]["embedding"].shape == (256, 64)
        assert params["head"].shape == (64, 256)  # ONE embedding, ONE head

    def declared_of_its_own(self, arch, model):
        assert not hasattr(model, "moe_axis")

    def run_once(self, model, params):
        """The two projections out of the latents (``q_b_proj``,
        ``kv_b_proj``) and ``o_proj``, the MTP module's block's too."""
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
        return [(out_of_the_latents, 2, 0), (w_o, 1, 1)]

    def test_the_bias_takes_no_gradient_and_sits_in_no_optimizer_leaf(self, small):
        model, params, biases, tokens, labels = small
        grad = jax.grad(
            lambda b: program_loss(model, params, b, tokens, labels)[0])(biases)
        assert all(not float(jnp.abs(g).max()) for g in jax.tree.leaves(grad))
        paths = [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_leaves_with_path(params)]
        assert not any("bias" in p for p in paths) and len(paths) == 67
        # evaluation routes by the bias and leaves it where it is
        _, mutated = model.apply(
            variables(params, biases), tokens, train=False,
            hidden_only=True, mutable=["batch_stats"])
        assert jax.tree.all(jax.tree.map(
            lambda a, b: bool((a == b).all()), mutated["batch_stats"], biases))


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


def test_mtp_reads_the_token_after_next_and_not_its_last_position():
    """``ce_mtp`` at position t is the head on the MTP state against
    ``label[t + 1]``, a mean over the S - 1 positions that have one; the
    last position carries no weight and no gradient; one walk gives ``ce +
    mtp_weight * ce_mtp``."""
    model = build()
    params, biases, tokens, labels = seeded(model, batch=3, seq=50)
    _, aux = program_loss(model, params, biases, tokens, labels)
    extra, (states, stats) = aux.extra, aux.outputs
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
    other = program_loss(model, params, biases, moved, labels)[1].outputs[0]
    assert float(jnp.abs(other[:, 1, 0] - states[:, 1, 0]).max()) > 0  # reads x_1
    np.testing.assert_array_equal(other[:, 0, 0], states[:, 0, 0])  # the trunk does not


def test_the_share_says_its_plan_and_the_experts_their_bound_once_a_shape(tmp_path):
    import flax

    from distribuuuu_tpu.ops import pallas as kernel_tier
    from distribuuuu_tpu.telemetry import schema, spans

    kernel_tier.reset_selection()
    spans.setup_telemetry(str(tmp_path), 0)
    try:
        model = build().clone(depth=2, seq_len=24, share_rank=1)
        state = flax.linen.meta.unbox(
            model.init(jax.random.key(0), jnp.full((3, 24), 256, jnp.int32)))
        for _ in range(2):
            model.apply(state, jnp.full((3, 24), 300, jnp.int32), hidden_only=True)
    finally:
        spans.close_telemetry()
    plans = contract.records(tmp_path, "share.plan")
    assert len(plans) == 1
    schema.check_fields("share.plan", plans[0])
    assert {k: plans[0][k] for k in (
        "share_chips", "share_rank", "experts_held", "experts_total", "vocab_held",
        "vocab_total",
    )} == {"share_chips": 2, "share_rank": 1, "experts_held": 4, "experts_total": 8,
           "vocab_held": 256, "vocab_total": 512}
    assert "every block" in plans[0]["recomputed"]
    # 2 blocks and the MTP module's, 3 x 24 tokens, float32 here: an input
    # and the attention's output a block
    assert plans[0]["kept_branch_bytes"] == 3 * 3 * 24 * 64 * 4
    assert plans[0]["kept_bytes"] == 2 * 3 * 3 * 24 * 64 * 4
    # the experts' record: a share's fields beside the tiles
    chose = [r for r in contract.records(tmp_path, "kernel.select") if r["op"] == "moe_gmm"]
    assert chose and chose[-1]["impl"] == "xla"  # the CPU: ragged_dot
    reasons = [r["reason"] for r in contract.records(tmp_path, "kernel.fallback")
               if r["op"] == "moe_gmm"]
    assert reasons and all("rows an expert" in r or "128 lanes" in r or "platform" in r
                           for r in reasons)
