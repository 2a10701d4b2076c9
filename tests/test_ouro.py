"""Ouro on the normal path, against the benchmark's plain reference
(``benchmark/reference/ouro.py``), at a size the CPU runs: hidden 64, 4 heads
of 16, an MLP of 176, 3 layers run 4 times, vocab 512, 128 tokens and a
length that is no multiple of the loss chunk."""

import functools
import importlib.util
import os

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distribuuuu_tpu import models
from distribuuuu_tpu.models import ouro
from distribuuuu_tpu.models.olmoe import RMSNorm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "ouro_reference", os.path.join(REPO, "benchmark", "reference", "ouro.py")
)
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

VOCAB, CHUNK, BETA = 512, 48, 0.05


def build(**kw):
    return models.build_model("ouro_tiny", num_classes=VOCAB, dtype=jnp.float32, **kw)


def architecture(model) -> dict:
    return {
        "layers": model.depth, "total_ut_steps": model.passes,
        "hidden_size": model.dim, "intermediate_size": model.mlp_hidden,
        "num_attention_heads": model.num_heads, "rms_norm_eps": model.rms_norm_eps,
        "rope_theta": model.rope_theta, "vocab_size": model.vocab_size,
        "exit_entropy_weight": model.exit_beta,
    }


def seeded(model, batch=2, seq=100, seed=0):
    """(params, tokens, labels): weights from the program's initialiser with
    the norm scales moved off 1, so that a dropped or misplaced scale would
    show, and a gate wide enough for its distribution to leave 1/2."""
    k_init, k_tok, k_scale = jax.random.split(jax.random.key(seed), 3)
    params = flax.linen.meta.unbox(
        model.init(k_init, jnp.zeros((1, 8), jnp.int32))["params"]
    )
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(k_scale, len(flat))
    flat = [
        leaf * (1 + 0.2 * jax.random.normal(k, leaf.shape))
        if "scale" in jax.tree_util.keystr(path) else leaf
        for (path, leaf), k in zip(flat, keys)
    ]
    params = jax.tree.unflatten(tree, flat)
    params["exit_gate"] = {"kernel": params["exit_gate"]["kernel"] * 10,
                           "bias": jnp.asarray([0.3])}
    ids = jax.random.randint(k_tok, (batch, seq + 1), 0, VOCAB, jnp.int32)
    return params, ids[:, :-1], ids[:, 1:]


def program_loss(model, params, tokens, labels):
    """(loss, step metrics, what ``hidden_only`` returned): the two calls the
    step's ``loss_fn`` makes."""
    outputs = model.apply({"params": params}, tokens, train=True, hidden_only=True)
    loss, hits, extra = model.head_loss(
        outputs, model.head_kernel(params), labels, topk=(1, 5))
    return loss, (extra, hits, outputs)


def walk(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from walk(inner)


def forward_matmuls(jaxpr, kernels) -> int:
    """``x [B, S, in] . W [in, out]`` with W's shape among ``kernels``, in a
    jaxpr and the jaxprs inside it: a projection's FORWARD matmul, wherever
    it runs (its dx contracts W's other dimension, its dW no W at all)."""
    return sum(
        eqn.primitive.name == "dot_general"
        and tuple(eqn.invars[1].aval.shape) in kernels
        and eqn.params["dimension_numbers"][0] == ((2,), (0,))
        for eqn in walk(jaxpr))


def assert_trees_close(got, want, tolerance):
    flat = jax.tree_util.tree_leaves_with_path(got)
    for (path, g), w in zip(flat, jax.tree.leaves(want), strict=True):
        norm = float(jnp.linalg.norm(w))
        assert norm > 0, jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(g - w)) <= tolerance * norm, jax.tree_util.keystr(path)


def test_registry_and_shapes():
    assert {"ouro_2_6b", "ouro_tiny"} <= set(models.available_models())
    model = build()
    params, tokens, _ = seeded(model, seq=16)
    assert model.apply({"params": params}, tokens).shape == (2, 16, VOCAB)
    states, gates = model.apply({"params": params}, tokens, hidden_only=True)
    assert states.shape == (2, 4, 16, 64) and gates.shape == (2, 4, 16)
    assert gates.dtype == jnp.float32
    published = models.build_model("ouro_2_6b")
    assert (published.dim, published.depth, published.passes, published.num_heads,
            published.mlp_hidden, published.vocab_size, published.seq_len,
            published.rms_norm_eps, published.rope_theta) == (
        2048, 48, 4, 16, 5632, 49152, 4096, 1e-6, 1e6)

    def parameters(model):  # the passes share them: one pass traces them all
        shapes = jax.eval_shape(lambda: model.clone(passes=1).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
        return sum(x.size for x in jax.tree.leaves(shapes["params"]))

    assert parameters(published) == 2_667_974_657
    # the one-chip depth of benchmark/configs/ouro_2_6b.json
    assert parameters(published.clone(depth=8)) == 612_438_017
    assert parameters(build()) == parameters(build(passes=1))


@pytest.mark.parametrize("seq", [128, 100])
def test_every_passes_logits_gates_and_exit_distribution_equal_the_reference(seq):
    model = build()
    params, tokens, labels = seeded(model, seq=seq)
    want_gates, want_logits = reference.logits(
        params, tokens, architecture=architecture(model))
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


def test_loss_terms_and_every_gradient_equal_the_reference():
    model = build()
    params, tokens, labels = seeded(model)
    arch = architecture(model)
    (loss, (extra, _, _)), grads = jax.value_and_grad(
        lambda p: program_loss(model, p, tokens, labels), has_aux=True)(params)
    (_, want), want_grads = jax.value_and_grad(
        lambda p: (lambda t: (t["loss"], t))(
            reference.loss(p, tokens, labels, architecture=arch)), has_aux=True)(params)
    np.testing.assert_allclose(loss, want["loss"], rtol=1e-5)
    for term in ("ce", "exit_entropy", "exit_step_mean"):
        np.testing.assert_allclose(extra[term], want[term], rtol=1e-5)
    np.testing.assert_allclose(
        [extra[f"ce_pass_{t}"] for t in range(4)], want["ce_pass"], rtol=1e-5)
    np.testing.assert_allclose(
        loss, extra["ce"] - BETA * extra["exit_entropy"], rtol=1e-6)
    assert len(jax.tree.leaves(grads)) == 5 + 11 * model.depth
    assert_trees_close(grads, want_grads, 1e-4)


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
    params, tokens, labels = seeded(model, seq=64)
    stack = {k: v for k, v in params.items() if k.startswith("Block_")}
    rest = {k: v for k, v in params.items() if not k.startswith("Block_")}
    loss, (copies, rest_grads) = jax.value_and_grad(
        lambda c, r: _unshared_loss(model, c, r, tokens, labels), argnums=(0, 1)
    )([stack] * model.passes, rest)
    (want, _), shared = jax.value_and_grad(
        lambda p: program_loss(model, p, tokens, labels), has_aux=True)(params)
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
    from distribuuuu_tpu.utils.metrics import cross_entropy

    model = build(passes=1)
    params, tokens, labels = seeded(model)
    (loss, (extra, hits, (_, z))), grads = jax.value_and_grad(
        lambda p: program_loss(model, p, tokens, labels), has_aux=True)(params)
    assert np.array_equal(jnp.exp(ouro.exit_log_probs(z)), jnp.ones_like(z))
    assert float(extra["exit_entropy"]) == 0.0 and float(extra["exit_step_mean"]) == 1.0
    logits = model.apply({"params": params}, tokens)
    np.testing.assert_allclose(loss, cross_entropy(logits, labels), rtol=1e-6)
    np.testing.assert_allclose(loss, extra["ce_pass_0"], rtol=1e-6)
    want = reference.loss(params, tokens, labels, architecture=architecture(model))
    np.testing.assert_allclose(loss, want["loss"], rtol=1e-5)
    assert not any(float(jnp.abs(g).max()) for g in jax.tree.leaves(grads["exit_gate"]))
    from distribuuuu_tpu.utils.metrics import accuracy

    np.testing.assert_allclose(hits, accuracy(logits, labels, topk=(1, 5)), rtol=1e-6)


@pytest.mark.parametrize("bias", [-60.0, 60.0, -1e4, 1e4])
def test_a_gate_gone_to_0_or_to_1_keeps_the_loss_and_the_gradient_finite(bias):
    model = build(depth=1)
    params, tokens, labels = seeded(model, seq=32)
    params["exit_gate"]["bias"] = jnp.asarray([bias])
    (loss, (extra, _, (_, z))), grads = jax.value_and_grad(
        lambda p: program_loss(model, p, tokens, labels), has_aux=True)(params)
    p = jnp.exp(ouro.exit_log_probs(z))
    np.testing.assert_allclose(p.sum(1), 1.0, atol=1e-6)
    # every token leaves after the first pass, or after the last
    assert float(extra["exit_step_mean"]) == pytest.approx(1.0 if bias > 0 else 4.0)
    assert float(extra["exit_entropy"]) == pytest.approx(0.0, abs=1e-6)
    assert np.isfinite(float(loss))
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))
    np.testing.assert_allclose(
        loss, extra["ce_pass_0" if bias > 0 else "ce_pass_3"], rtol=1e-5)


def test_the_recomputing_step_equals_the_step_that_keeps_everything():
    from jax._src.ad_checkpoint import saved_residuals

    model = build(depth=2)
    params, tokens, labels = seeded(model)
    out, kept, named = {}, {}, {}
    for recompute in (True, False):
        variant = model.clone(recompute=recompute)

        def loss(p, variant=variant):
            return program_loss(variant, p, tokens, labels)[0]

        out[recompute] = jax.value_and_grad(loss)(params)
        residuals = saved_residuals(loss, params)
        kept[recompute] = [tuple(aval.shape) for aval, _ in residuals]
        named[recompute] = [tuple(aval.shape) for aval, why in residuals
                            if ouro.BRANCH_OUT in why]
    np.testing.assert_allclose(out[True][0], out[False][0], rtol=1e-6)
    assert_trees_close(out[True][1], out[False][1], 1e-5)
    # what the forward keeps for the backward: with recomputation an input a
    # block application, its two branches' outputs as the post-norms read
    # them (and the few states between passes), nothing of the MLP's width
    # and no attention scores; without it, all of them
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


def test_what_the_recomputed_blocks_keep_of_the_flash_kernel_changes_no_bit(monkeypatch):
    """With the kernels run (the interpreter, forced, where ``auto`` runs
    them compiled on the chip) a recomputed block keeps what the backward
    kernel reads, the forward kernel's output and log-sum-exp and its q, k
    and v, and each branch's output: a block application runs the forward
    kernel, the three projections and ``W_o`` once (its four ``[dim, dim]``
    matmuls) and the MLP's ``down_proj`` once, where a plain ``nn.remat``
    (the policy keeping nothing) runs all of it twice, and the loss and
    every gradient leaf are that step's bit for
    bit: what is kept is what was recomputed. Against the step that
    recomputes nothing the loss is the same bits and the gradients are as
    near as they were before anything was kept (jax sums a value's several
    cotangents in another order under a checkpoint)."""
    from distribuuuu_tpu.ops import flash_attention as fa

    monkeypatch.setattr(
        fa, "flash_attention", functools.partial(fa.flash_attention, interpret=True))
    model = build(depth=2, attn_impl="flash")
    params, tokens, labels = seeded(model, batch=1, seq=40)
    blocks = model.depth * model.passes
    assert model.mlp_hidden != model.dim  # q, k, v and W_o alone are [dim, dim]

    def run(variant, forward_calls, projections, down_projs):
        def loss(p):
            return program_loss(variant, p, tokens, labels)[0]

        traced = jax.jit(jax.value_and_grad(loss)).trace(params)
        text = str(traced.jaxpr)
        assert text.count("name=dtpu_flash_fwd") == forward_calls
        assert text.count("name=dtpu_flash_bwd") == blocks
        assert forward_matmuls(
            traced.jaxpr.jaxpr, {(model.dim, model.dim)}) == projections
        assert forward_matmuls(
            traced.jaxpr.jaxpr, {(model.mlp_hidden, model.dim)}) == down_projs
        return traced.lower().compile()(params)

    kept = run(model, blocks, 4 * blocks, blocks)
    nothing_recomputed = run(model.clone(recompute=False), blocks, 4 * blocks, blocks)
    monkeypatch.setattr(
        jax.checkpoint_policies, "save_only_these_names",
        lambda *names: jax.checkpoint_policies.nothing_saveable)
    plain = run(model, 2 * blocks, 8 * blocks, 2 * blocks)
    assert float(kept[0]) == float(plain[0]) == float(nothing_recomputed[0])
    flat = jax.tree_util.tree_leaves_with_path(kept[1])
    for (path, got), want in zip(flat, jax.tree.leaves(plain[1]), strict=True):
        assert float(jnp.abs(want).max()) > 0, jax.tree_util.keystr(path)
        np.testing.assert_array_equal(got, want, err_msg=jax.tree_util.keystr(path))
    assert_trees_close(kept[1], nothing_recomputed[1], 1e-5)


@pytest.mark.parametrize("engaged", [True, False], ids=["kernel", "scan"])
def test_the_plan_says_what_the_cells_block_applications_keep(
        tmp_path, monkeypatch, engaged):
    """``loop.plan`` at ``ouro_2_6b.train_seq4096``'s shape (8 layers, 4
    passes, 1 x 4096 tokens): 32 float32 inputs of 32 MiB, 2 x 32 branch
    outputs of 16 MiB (bfloat16) and, where the flash kernel runs, 32 x (16
    MiB of output + 0.25 MiB of log-sum-exp + 3 x 16 MiB of q, k and v);
    where the scan runs in its place the kernel names nothing and nothing of
    it is kept."""
    import json

    from distribuuuu_tpu.ops import pallas as tier
    from distribuuuu_tpu.telemetry import schema, spans

    if engaged:  # what the tier answers on one chip
        monkeypatch.setattr(tier, "interpret_mode", lambda: False)
        monkeypatch.setattr(tier, "compiled_across_devices", lambda: False)
    model = models.build_model("ouro_2_6b", num_classes=49152, depth=8)
    ouro._planned.clear()
    path = spans.setup_telemetry(str(tmp_path), rank=0)
    try:
        for _ in range(2):  # once a shape
            ouro._say_plan(model, 1, 4096)
    finally:
        spans.close_telemetry()
        ouro._planned.clear()
    plans = [r for r in map(json.loads, open(path)) if r.get("kind") == "loop.plan"]
    assert len(plans) == 1
    plan = plans[0]
    schema.validate_record(plan)
    inputs = 32 * 4096 * 2048 * 4
    assert plan["block_applications"] == 32
    assert plan["kept_flash_bytes"] == (2_155_872_256 if engaged else 0)
    assert plan["kept_branch_bytes"] == 2 * 32 * 4096 * 2048 * 2 == 2**30
    assert plan["kept_bytes"] == inputs + 2**30 + plan["kept_flash_bytes"]
    said = ("every block application, from its float32 input, the outputs of "
            "its branches that are read again (whose last matmuls run once)")
    assert plan["recomputed"] == said + (
        " and the flash kernel's output, log-sum-exp, q, k and v" if engaged else "")
    nothing = ouro.kept_plan(
        model.clone(recompute=False), 32, 1, 4096, 128, "", branches=64)
    assert nothing == {
        "kept_bytes": None, "kept_branch_bytes": None, "kept_flash_bytes": None,
        "recomputed": "nothing"}


def test_bfloat16_program_stays_near_the_reference_because_its_float32_parts_do():
    """bfloat16 matmul inputs; residual stream, norms, gate, exit
    distribution and loss in float32. Readings at this size (2 layers, 4 x
    128 tokens, the gate's weight ten times its initial width), relative to
    the float32 reference: the program's ``ce`` 1.1e-5 (the float32
    program's 1.5e-7), the reference run in bfloat16 THROUGHOUT 8.4e-4:
    2e-4 separates them. ``exit_step_mean`` reads the gate's logit, which
    carries the bfloat16 matmuls upstream: 6.2e-4 for the program, 1.6e-3
    for the bfloat16 reference."""
    model32 = build(depth=2)
    model16 = model32.clone(dtype=jnp.bfloat16)
    params, tokens, labels = seeded(model32, batch=4, seq=128)
    arch = architecture(model32)
    want = reference.loss(params, tokens, labels, architecture=arch)
    low = reference.loss(params, tokens, labels, architecture=arch,
                         precision=jnp.bfloat16)
    _, (got, _, (states, z)) = program_loss(model16, params, tokens, labels)
    assert states.dtype == jnp.bfloat16 and z.dtype == jnp.float32

    def off(terms, key):
        return abs(float(terms[key]) - float(want[key])) / float(want[key])

    assert off(got, "ce") < 2e-4 < off(low, "ce")
    assert off(got, "exit_step_mean") < 1e-3 < off(low, "exit_step_mean")
    _, (exact, _, _) = program_loss(model32, params, tokens, labels)
    assert off(exact, "ce") < 2e-6
