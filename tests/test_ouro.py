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


# ------------------------------------------- the MLP's two products, kept
GIB = 2**30


@pytest.mark.parametrize("capacity,held,proj,applications,reserve,want", [
    (16 * GIB, 11 * GIB, GIB // 8, 32, 2 * GIB, 24),        # room for 24 of 32
    (16 * GIB, 11 * GIB, GIB // 8, 16, 2 * GIB, 16),        # capped at the applications
    (16 * GIB, 14 * GIB, GIB // 8, 32, 2 * GIB, 0),         # exactly full: none
    (16 * GIB, 14 * GIB - GIB // 8, GIB // 8, 32, 2 * GIB, 1),  # room for exactly one
    (16 * GIB, 15 * GIB, GIB // 8, 32, 2 * GIB, 0),         # nothing fits: never negative
    (None, 0, GIB // 8, 32, 0, 0),                          # no device declared, or the CPU
    (0, 0, GIB // 8, 32, 0, 0),
    (16 * GIB, 0, 0, 32, 0, 0),                             # nothing to keep
])
def test_the_planner_counts_what_fits_under_capacity_less_reserve(
        capacity, held, proj, applications, reserve, want):
    assert ouro.plan_kept_proj(capacity, held, proj, applications, reserve) == want


def test_the_planner_is_monotone_in_capacity_and_reads_no_environment(monkeypatch):
    counts = [ouro.plan_kept_proj(c * GIB // 4, 12 * GIB, 88 * 2**20, 32, 2 * GIB)
              for c in range(0, 100)]
    assert counts == sorted(counts) and counts[0] == 0 and counts[-1] == 32
    assert 0 < counts[64] < 32  # 16 GiB: the cell's own case lies between
    before = ouro.plan_kept_proj(16 * GIB, 12_500_000_000, 92_274_688, 32, 2 * GIB)
    for name in ("XLA_FLAGS", "JAX_PLATFORMS", "TPU_VISIBLE_CHIPS", "DTPU_CPU_PEAK_FLOPS",
                 "JAX_COMPILATION_CACHE_DIR", "MEMORY_BUDGET"):
        monkeypatch.setenv(name, "7")
    assert ouro.plan_kept_proj(16 * GIB, 12_500_000_000, 92_274_688, 32, 2 * GIB) == before


@pytest.mark.parametrize("kind,want", [
    ("TPU v5 lite", 16 * GIB), ("TPU v5e", 16 * GIB), ("TPU v4", 32 * GIB),
    ("cpu", None), ("a chip the table lacks", None), (None, None),
])
def test_the_capacity_is_the_tables_for_the_device_the_step_declared(kind, want):
    """By ``device_kind`` from ``costmodel.DEVICE_PEAKS`` of the device the
    trace was declared for (a described device has no allocator to ask);
    nothing where no step declared one, whatever the live backend is."""
    import contextlib
    import types

    from distribuuuu_tpu.ops import pallas as kernel_tier

    declared = contextlib.nullcontext() if kind is None else kernel_tier.lowered_for(
        types.SimpleNamespace(device_kind=kind))
    with declared:
        assert ouro._capacity_bytes() == want
    assert ouro._capacity_bytes() is None and kernel_tier.target_device() is None


def _capacity_for(monkeypatch, model, params, tokens, kept: int):
    return contract.room_for_kept_products(monkeypatch, model, params, tokens.shape, kept)


def _eqns(jaxpr, primitive):
    return [e for e in jaxpr.eqns if e.primitive.name == primitive]


def _recomputed_bodies(jaxpr) -> list:
    """The jaxprs of ``jax.checkpoint``'s equations, in program order."""
    return [e.params["jaxpr"] for e in _eqns(jaxpr, "remat2")]


def _recomputes_the_products(body) -> bool:
    """Whether a backward's recomputed body (``remat2``) runs ``gate_proj``
    and ``up_proj`` again: both or neither."""
    again = {proj for e in _eqns(body, "dot_general") for proj in ("gate_proj", "up_proj")
             if "rematted_computation" in str(e.source_info.name_stack)
             and str(e.source_info.name_stack).endswith(proj)}
    assert len(again) in (0, 2), again
    return bool(again)


@pytest.mark.parametrize("kept,want", [(0, 0), (3, 3), (8, 8), (11, 8)])
def test_the_last_applications_keep_the_products_and_the_others_run_them_again(
        monkeypatch, kept, want):
    """2 layers x 4 passes: with room for ``kept`` applications the forward
    names the two products in the LAST ``want`` of its 8 recomputed bodies
    and in no other, and the backward, which reaches those first, runs
    ``gate_proj`` and ``up_proj`` again in exactly the other ``8 - want``."""
    model = build(depth=2)
    params, _, tokens, labels = seeded(model)
    _capacity_for(monkeypatch, model, params, tokens, kept)

    def loss(p):
        return program_loss(model, p, None, tokens, labels)[0]

    forward = _recomputed_bodies(jax.make_jaxpr(loss)(params).jaxpr)
    named = [sum(e.params["name"] == ouro.KEPT_PROJ for e in _eqns(body, "name"))
             for body in forward]
    assert named == [0] * (8 - want) + [2] * want
    backward = _recomputed_bodies(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    assert [_recomputes_the_products(body) for body in backward] == (
        [False] * want + [True] * (8 - want))


@pytest.mark.parametrize("kept", [3, 8])
def test_keeping_the_products_changes_no_bit_of_the_step(monkeypatch, kept):
    """Loss, every gradient leaf and the parameters after one AdamW step with
    ``kept`` applications keeping their products equal the step's that keeps
    none, to the last bit: the kept values are the values it computes again."""
    import optax

    model = build(depth=2)
    params, _, tokens, labels = seeded(model, seq=40)
    optimizer = optax.adamw(1e-3, weight_decay=0.1)

    def a_step():  # a function of its own a plan: jax caches a function's trace
        def step(p):
            loss, grads = jax.value_and_grad(
                lambda p: program_loss(model, p, None, tokens, labels)[0])(p)
            updates, _ = optimizer.update(grads, optimizer.init(p), p)
            return loss, grads, optax.apply_updates(p, updates)
        return step

    ran = {}
    for n in (0, kept):
        _capacity_for(monkeypatch, model, params, tokens, n)
        assert str(jax.make_jaxpr(a_step())(params)).count(f"name={ouro.KEPT_PROJ}") == 2 * n
        ran[n] = jax.device_get(jax.jit(a_step())(params))
    for a, b in zip(jax.tree.leaves(ran[0]), jax.tree.leaves(ran[kept])):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("kept", [0, 5])
def test_the_plan_record_says_how_many_keep_and_what_it_was_planned_from(
        monkeypatch, tmp_path, kept):
    """``loop.plan`` of 2 layers x 4 passes on 3 x 24 tokens: the count, its
    bytes (two ``[tokens, hidden]`` products an application in the compute
    dtype) inside ``kept_bytes``, and the three numbers of the plan, which
    add up: three times the parameters, what is kept, the head's float32
    gradient and one chunk's logits over the 4 x 3 stacked rows."""
    from distribuuuu_tpu.telemetry import schema, spans

    model = build(depth=2, seq_len=24)
    params, _, tokens, _ = seeded(model, batch=3, seq=24)
    base = _capacity_for(monkeypatch, model, params, tokens, kept)
    ouro._planned.clear()
    spans.setup_telemetry(str(tmp_path), 0)
    try:
        for _ in range(2):
            model.apply({"params": params}, tokens, hidden_only=True)
    finally:
        spans.close_telemetry()
    (plan,) = contract.records(tmp_path, "loop.plan")
    schema.validate_record(plan)
    proj = 2 * 3 * 24 * model.mlp_hidden * 4
    inputs_and_branches = 3 * 8 * 3 * 24 * 64 * 4
    assert (plan["kept_proj_applications"], plan["kept_proj_bytes"]) == (kept, kept * proj)
    assert plan["kept_bytes"] == inputs_and_branches + kept * proj
    size = sum(p.size * 4 for p in jax.tree.leaves(params))
    head = 4 * model.vocab_size * (64 + 4 * 3 * 24)
    assert plan["planned_bytes"] == 3 * size + plan["kept_bytes"] + head
    assert plan["planned_bytes"] == base["planned_bytes"] + kept * proj
    assert plan["reserve_bytes"] == ouro.RESERVE_BYTES
    assert plan["planned_bytes"] <= plan["capacity_bytes"] - plan["reserve_bytes"] < (
        plan["planned_bytes"] + proj)
    assert ("the last 5 applications also keep" in plan["recomputed"]) == bool(kept)


@pytest.mark.parametrize("row", ["glm", "afmoe", "sdar"])
def test_the_other_recomputing_stacks_name_and_plan_nothing_new(
        monkeypatch, tmp_path, row):
    """``GLMMoE`` and ``share.run_blocks`` pass no per-application argument:
    with all the room in the world their dense MLPs name nothing, their
    blocks keep what they kept and their plan records hold the fields they
    held."""
    from distribuuuu_tpu.models import glm_moe, share
    from distribuuuu_tpu.telemetry import schema, spans

    monkeypatch.setattr(ouro, "_capacity_bytes", lambda: 2**50)
    row = contract.ROWS[row]
    model = contract.build(row, **row.small)
    params, biases, tokens, labels = seeded(model)
    for module in (glm_moe, share):
        module._planned.clear()
    spans.setup_telemetry(str(tmp_path), 0)
    try:
        jaxpr = jax.make_jaxpr(  # the forward names what a block keeps
            lambda p: program_loss(model, p, biases, tokens, labels)[0])(params)
    finally:
        spans.close_telemetry()
    assert ouro.KEPT_PROJ not in str(jaxpr) and ouro.BRANCH_OUT in str(jaxpr)
    (plan,) = contract.records(tmp_path, "share.plan")
    record = {"kind", "rank", "t", "v"}
    assert schema.KINDS["share.plan"] <= set(plan) - record <= (
        schema.KINDS["share.plan"] | {"layer_kinds", "dense_layers"})
    assert "keep the products" not in plan["recomputed"]
