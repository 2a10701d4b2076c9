"""Ouro on the normal path, against the benchmark's plain reference
(``benchmark/reference/ouro.py``), at a size the CPU runs: hidden 64, 4 heads
of 16, an MLP of 176, 3 layers run 4 times, vocab 512, 128 tokens and a
length that is no multiple of the loss chunk. The contracts it answers are
``tests/decoder_contract.py``'s; below them, what only Ouro has: the passes,
the exit gate and its distribution, the shared stack's gradient."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_contract as contract
from decoder_contract import assert_trees_close, program_loss, seeded
from distribuuuu_tpu import models
from distribuuuu_tpu.models import ouro
from distribuuuu_tpu.models.olmoe import RMSNorm

ROW = contract.ROWS["ouro"]
BETA = 0.05
reference = ROW.reference


def build(**kw):
    return contract.build(ROW, **kw)


def value_and_grad(model, params, tokens, labels):
    (loss, aux), grads = jax.value_and_grad(
        lambda p: program_loss(model, p, None, tokens, labels), has_aux=True)(params)
    return loss, aux, grads


class TestOuro(contract.Decoder, contract.ThroughLower, contract.Recomputes,
               contract.KeepsTheFlashKernels, contract.ComputesInBfloat16):
    row = ROW

    def shapes_of_its_own(self, full, model, state, hidden):
        _, gates = hidden
        assert gates.shape == (2, 4, 40) and gates.dtype == jnp.float32

        def parameters(model):  # the passes share them: one pass traces them all
            shapes = jax.eval_shape(lambda: model.clone(passes=1).init(
                jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
            return sum(x.size for x in jax.tree.leaves(shapes["params"]))

        assert parameters(full) == 2_667_974_657
        # the one-chip depth of benchmark/configs/ouro_2_6b.json
        assert parameters(full.clone(depth=8)) == 612_438_017
        assert parameters(build()) == parameters(build(passes=1))

    def loss_of_its_own(self, model, loss, aux, want):
        np.testing.assert_allclose(
            [aux.extra[f"ce_pass_{t}"] for t in range(4)], want["ce_pass"], rtol=1e-5)
        np.testing.assert_allclose(
            loss, aux.extra["ce"] - BETA * aux.extra["exit_entropy"], rtol=1e-6)

    def declared_of_its_own(self, arch, model):
        assert not hasattr(model, "moe_axis")

    def step_of_its_own(self, ran, want):
        assert ran.model.exit_beta == BETA and ran.model.passes == 4
        np.testing.assert_allclose(
            [ran.metrics[f"ce_pass_{t}"] for t in range(4)], want["ce_pass"], rtol=1e-5)
        # a fresh gate is at 1/2: 1 x 1/2 + 2 x 1/4 + 3 x 1/8 + 4 x 1/8
        assert float(ran.metrics["exit_step_mean"]) == pytest.approx(1.875, abs=0.05)
        # it trained: every leaf moved
        for a, b in zip(jax.tree.leaves(ran.params), jax.tree.leaves(ran.params_after)):
            assert not np.array_equal(a, b)

    def run_once(self, model, params):
        """The three projections and ``W_o`` (a block's four ``[dim, dim]``
        matmuls) and the MLP's ``down_proj``."""
        assert model.mlp_hidden != model.dim  # q, k, v and W_o alone are [dim, dim]
        return [({(model.dim, model.dim)}, 4, 0), ({(model.mlp_hidden, model.dim)}, 1, 0)]

    def bfloat16_of_its_own(self, model16, params, tokens, labels, got, want, arch,
                            monkeypatch):
        """Readings at this size (2 layers, 4 x 128 tokens, the gate's weight
        ten times its initial width), relative to the float32 reference: the
        program's ``ce`` 1.1e-5 (the float32 program's 1.5e-7), the reference
        run in bfloat16 THROUGHOUT 8.4e-4: 2e-4 separates them.
        ``exit_step_mean`` reads the gate's logit, which carries the bfloat16
        matmuls upstream: 6.2e-4 for the program, 1.6e-3 for the bfloat16
        reference."""
        states, z = got.outputs
        assert states.dtype == jnp.bfloat16 and z.dtype == jnp.float32


@pytest.mark.parametrize("seq", [128, 100])
def test_every_passes_logits_gates_and_exit_distribution_equal_the_reference(seq):
    model = build()
    params, _, tokens, labels = seeded(model, seq=seq)
    want_gates, want_logits = reference.logits(
        params, tokens, architecture=ROW.architecture(model))
    states, z = model.apply({"params": params}, tokens, hidden_only=True)
    logits = jnp.einsum("brsd,dv->rbsv", states, params["head"])
    np.testing.assert_allclose(logits, want_logits, atol=1e-5, rtol=0)
    # the plain call is the last pass
    np.testing.assert_allclose(
        model.apply({"params": params}, tokens), want_logits[-1], atol=1e-5, rtol=0)
    gates = jnp.moveaxis(jax.nn.sigmoid(z), 1, 0)
    np.testing.assert_allclose(gates, want_gates, atol=1e-5, rtol=0)
    assert float(jnp.abs(want_gates - 0.5).max()) > 0.1  # a gate that says something
    p = jnp.exp(ouro.exit_log_probs(z))
    np.testing.assert_allclose(
        jnp.moveaxis(p, 1, 0), reference.exit_distribution(want_gates),
        atol=1e-5, rtol=0)
    np.testing.assert_allclose(p.sum(1), 1.0, atol=1e-6)


def _unshared_loss(model, copies, rest, tokens, labels):
    """The model's walk with a separate copy of the stack a pass, from the
    program's own modules."""
    block = ouro.Block(model.dim, model.num_heads, model.mlp_hidden,
                       model.rms_norm_eps, model.rope_theta, model.dtype,
                       model.attn_impl, None)
    x = rest["tok_embed"]["embedding"][tokens]
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    states, gates = [], []
    for stack in copies:
        for i in range(model.depth):
            x = block.apply({"params": stack[f"Block_{i}"]}, x, positions)
        x = RMSNorm(model.rms_norm_eps).apply({"params": rest["final_norm"]}, x)
        states.append(x)
        gates.append(ouro.ExitGate().apply({"params": rest["exit_gate"]}, x))
    outputs = jnp.stack(states, 1), jnp.stack(gates, 1)
    return model.head_loss(outputs, rest["head"], labels, topk=(1,))[0]


def test_the_shared_gradient_is_the_sum_over_passes_of_an_unshared_models():
    """R independent copies of the stack set to the same values: the same
    loss, and the gradients of the copies add up to the shared stack's."""
    model = build(depth=2)
    params, _, tokens, labels = seeded(model, seq=64)
    stack = {k: v for k, v in params.items() if k.startswith("Block_")}
    rest = {k: v for k, v in params.items() if not k.startswith("Block_")}
    loss, (copies, rest_grads) = jax.value_and_grad(
        lambda c, r: _unshared_loss(model, c, r, tokens, labels), argnums=(0, 1)
    )([stack] * model.passes, rest)
    want, _, shared = value_and_grad(model, params, tokens, labels)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    summed = jax.tree.map(lambda *g: sum(g), *copies)
    assert_trees_close({**summed, **rest_grads}, shared, 1e-5)
    # and no pass's share is nothing: every copy of every leaf has a gradient
    for copy in copies:
        assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(copy))


def test_one_pass_is_the_plain_decoder():
    """R = 1: the token exits after the only pass (p = 1, entropy 0, the gate
    gets no gradient) and the loss is the plain cross-entropy of a decoder of
    these blocks."""
    from distribuuuu_tpu.utils.metrics import accuracy, cross_entropy

    model = build(passes=1)
    params, _, tokens, labels = seeded(model)
    loss, aux, grads = value_and_grad(model, params, tokens, labels)
    _, z = aux.outputs
    assert np.array_equal(jnp.exp(ouro.exit_log_probs(z)), jnp.ones_like(z))
    assert float(aux.extra["exit_entropy"]) == 0.0
    assert float(aux.extra["exit_step_mean"]) == 1.0
    logits = model.apply({"params": params}, tokens)
    np.testing.assert_allclose(loss, cross_entropy(logits, labels), rtol=1e-6)
    np.testing.assert_allclose(loss, aux.extra["ce_pass_0"], rtol=1e-6)
    want = reference.loss(params, tokens, labels, architecture=ROW.architecture(model))
    np.testing.assert_allclose(loss, want["loss"], rtol=1e-5)
    assert not any(float(jnp.abs(g).max()) for g in jax.tree.leaves(grads["exit_gate"]))
    np.testing.assert_allclose(aux.hits, accuracy(logits, labels, topk=(1, 5)), rtol=1e-6)


@pytest.mark.parametrize("bias", [-60.0, 60.0, -1e4, 1e4])
def test_a_gate_gone_to_0_or_to_1_keeps_the_loss_and_the_gradient_finite(bias):
    model = build(depth=1)
    params, _, tokens, labels = seeded(model, seq=32)
    params["exit_gate"]["bias"] = jnp.asarray([bias])
    loss, aux, grads = value_and_grad(model, params, tokens, labels)
    p = jnp.exp(ouro.exit_log_probs(aux.outputs[1]))
    np.testing.assert_allclose(p.sum(1), 1.0, atol=1e-6)
    # every token leaves after the first pass, or after the last
    assert float(aux.extra["exit_step_mean"]) == pytest.approx(1.0 if bias > 0 else 4.0)
    assert float(aux.extra["exit_entropy"]) == pytest.approx(0.0, abs=1e-6)
    assert np.isfinite(float(loss))
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))
    np.testing.assert_allclose(
        loss, aux.extra["ce_pass_0" if bias > 0 else "ce_pass_3"], rtol=1e-5)


def test_what_the_forward_keeps_for_the_backward_with_and_without_recomputation():
    """Read off the residuals of the loss (2 layers, 2 x 100 tokens): with
    recomputation an input a block application, its two branches' outputs as
    the post-norms read them (and the few states between passes), nothing of
    the MLP's width and no attention scores; without it, all of them. That
    the two steps are one step is the contract's
    ``test_the_recomputing_step_equals_the_step_that_keeps_everything``."""
    from jax._src.ad_checkpoint import saved_residuals

    model = build(depth=2)
    params, _, tokens, labels = seeded(model)
    kept, named = {}, {}
    for recompute in (True, False):
        variant = model.clone(recompute=recompute)
        residuals = saved_residuals(
            lambda p: program_loss(variant, p, None, tokens, labels)[0], params)
        kept[recompute] = [tuple(aval.shape) for aval, _ in residuals]
        named[recompute] = [tuple(aval.shape) for aval, why in residuals
                            if ouro.BRANCH_OUT in why]
    B, S = tokens.shape
    stream, applications = (B, S, model.dim), model.depth * model.passes

    def activations(shapes, width):
        return sum(s == (B, S, width) for s in shapes)

    assert named[True] == [stream] * 2 * applications
    assert 3 * applications <= kept[True].count(stream) <= (
        3 * applications + 4 * model.passes + 1)
    assert activations(kept[True], model.mlp_hidden) == 0
    assert not any(s[-2:] == (S, S) for s in kept[True])
    assert kept[False].count(stream) > 10 * applications
    assert activations(kept[False], model.mlp_hidden) >= 3 * applications


def test_a_step_that_recomputes_nothing_plans_to_keep_nothing():
    """``kept_plan`` at the cell's shape with ``recompute=False``: no byte is
    counted, whichever attention path runs."""
    model = models.build_model(ROW.full, **ROW.plan["build"])
    nothing = ouro.kept_plan(
        model.clone(recompute=False), 32, 1, 4096, 128, "", branches=64)
    assert nothing == {
        "kept_bytes": None, "kept_branch_bytes": None, "kept_flash_bytes": None,
        "recomputed": "nothing"}


def test_the_loop_says_its_plan_once_a_shape(tmp_path):
    from distribuuuu_tpu.telemetry import schema, spans

    spans.setup_telemetry(str(tmp_path), 0)
    try:
        model = build().clone(depth=2, seq_len=24)
        params, _, tokens, _ = seeded(model, batch=3, seq=24)
        for _ in range(2):
            model.apply({"params": params}, tokens, hidden_only=True)
    finally:
        spans.close_telemetry()
    plans = [r for r in contract.records(tmp_path, "loop.plan")
             if r["kept_bytes"] != 3 * 8 * 2 * 8 * 64 * 4]  # init's, on 2 x 8 tokens
    assert len(plans) == 1
    schema.check_fields("loop.plan", plans[0])
    assert (plans[0]["layers"], plans[0]["passes"],
            plans[0]["block_applications"]) == (2, 4, 8)
    # float32 here: an input and two branches' outputs a block application
    assert plans[0]["kept_branch_bytes"] == 2 * 8 * 3 * 24 * 64 * 4
    assert plans[0]["kept_bytes"] == 3 * 8 * 3 * 24 * 64 * 4
