"""Device scopes are HLO metadata: ``bwd`` on the transposed pass,
``opt_tile`` / ``opt_kernel`` inside the fused update, a stable ``name=`` on
every Pallas call — and nothing else about the step changes.

The benchmark's trace readers (``benchmark/layer_metrics/models.bwd_ms_per_step``
and friends) find device time by these names in the compiled program's HLO
text, so they are pinned here on toy steps: plain (one device, whole-leaf
update) and under ``shard_map`` (the 8-device mesh, stage 0).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distribuuuu_tpu import trainer
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu.ops.pallas import conv_epilogue, decode_attn, opt_update
from distribuuuu_tpu.parallel import mesh as mesh_lib
from distribuuuu_tpu.parallel.partition import lowering, topology as topo_lib
from distribuuuu_tpu.utils.optim import construct_optimizer

from benchmark.harness import trace

IM = 16


def _toy_cfg():
    cfg.MODEL.ARCH = "resnet18"
    cfg.MODEL.NUM_CLASSES = 4
    cfg.KERNELS.OPT_UPDATE = "pallas"  # interpret mode here, Mosaic on the chip


def _plain_step():
    """(jitted step, abstract state, abstract batch) built without a layout:
    one device, the whole-leaf update."""
    model = trainer.build_model_from_cfg()
    optimizer = construct_optimizer()
    variables = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((4, IM, IM, 3)), train=True)
    )
    state = lowering.TrainState(
        params=variables["params"], batch_stats=variables["batch_stats"],
        opt_state=jax.eval_shape(optimizer.init, variables["params"]),
        step=jax.ShapeDtypeStruct((), jnp.int32),
        key=jax.eval_shape(lambda: jax.random.key(1)),
    )
    batch = {"image": jax.ShapeDtypeStruct((4, IM, IM, 3), jnp.float32),
             "label": jax.ShapeDtypeStruct((4,), jnp.int32)}
    return lowering.make_train_step(model, optimizer, topk=2), state, batch


def _dp8_step():
    """The same through ``lower`` on the 8-device mesh, stage 0: the update
    runs per shard under ``shard_map``."""
    mesh = mesh_lib.build_mesh()
    topology = topo_lib.from_cfg(cfg)
    lowered = lowering.lower(
        trainer.build_model_from_cfg(topology), construct_optimizer(), topk=2,
        mesh=mesh, topology=topology, im_size=IM,
    )
    state, batch = lowered.abstract_args(8)
    return lowered.train_step, state, batch


@pytest.mark.parametrize("build,under_shard_map", [
    (_plain_step, False), (_dp8_step, True),
], ids=["plain", "shard_map"])
def test_compiled_step_carries_the_scopes(build, under_shard_map):
    _toy_cfg()
    step, state, batch = build()
    lowered = step.lower(state, batch)
    op_names = trace.op_names_from_hlo(lowered.compile().as_text())
    paths = list(op_names.values())

    def count(scope, also=()):
        return sum(
            trace.in_scope(p, scope) and all(trace.in_scope(p, a) for a in also)
            for p in paths
        )

    assert count("bwd") and count("opt_kernel")
    # ``opt_tile`` is the reshape of every leaf to the kernel's view and
    # back: in the program as lowered, but bitcasts once compiled (all of
    # them here; on the chip all but the copied leaves'), which run nothing
    assert "opt_tile/reshape" in lowered.as_text(debug_info=True)
    # the transposed pass carries the forward's scope inside ``bwd``
    assert any("bwd/transpose(jvp(fwd))" in p for p in paths)
    # forward-only operations exist and are told apart
    assert sum(trace.in_scope(p, "fwd") and not trace.in_scope(p, "bwd")
               for p in paths)
    # both halves of the update sit inside the step's optimizer_update scope,
    # and the kernel's own name is in the path (interpret mode has no custom
    # call; on the chip the instruction itself is dtpu_opt_update_sgd.N)
    assert count("opt_tile", ["optimizer_update"]) == count("opt_tile")
    assert count("opt_kernel", ["optimizer_update"]) == count("opt_kernel")
    assert count("dtpu_opt_update_sgd", ["opt_kernel"]) == count("opt_kernel")
    assert not count("opt_tile", ["opt_kernel"])
    assert bool(count("shard_map", ["opt_kernel"])) == under_shard_map


@pytest.mark.parametrize("build", [_plain_step, _dp8_step],
                         ids=["plain", "shard_map"])
def test_every_leaf_of_the_update_is_planned_where_it_rests(build):
    """``opt_update._plan`` on every leaf of the toy state: with one dtype
    for parameters and momentum every operand rests in one order, so none is
    copied for the call, and the view holds every element of the leaf. (The
    registry gauges that tallied this at trace time had no reader and went
    with PR 35; the plan itself is what the kernel runs on.)"""
    _toy_cfg()
    _step, state, _batch = build()
    leaves = jax.tree.leaves(state.params)
    assert leaves
    for leaf in leaves:
        order, view, block, copied = opt_update._plan(
            leaf.shape, [leaf.dtype] * 3)
        assert not copied
        assert sorted(order) == list(range(leaf.ndim))
        assert np.prod(view) == leaf.size and len(block) == len(view)
        assert all(1 <= b <= v for b, v in zip(block, view))


def _tpu_text(fn, *avals) -> str:
    return jax.jit(fn).trace(*avals).lower(lowering_platforms=("tpu",)).as_text()


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def test_pallas_calls_have_stable_names_in_the_tpu_lowering():
    """The Mosaic custom call is named after ``name=``, not after whatever
    scope happens to enclose it."""
    shape = (3, 3, 8, 8)
    sgd = _tpu_text(
        lambda p, g, t, lr: opt_update.sgd_leaf(
            p, g, t, lr, wd=5e-5, mom=0.9, nesterov=True, interpret=False),
        _f32(*shape), _f32(*shape), _f32(*shape), _f32(),
    )
    assert "dtpu_opt_update_sgd" in sgd and "tpu_custom_call" in sgd
    plain = _tpu_text(
        lambda p, g, lr: opt_update.sgd_leaf(
            p, g, None, lr, wd=5e-5, mom=0.0, nesterov=False, interpret=False)[0],
        _f32(*shape), _f32(*shape), _f32(),
    )
    assert "dtpu_opt_update_sgd_plain" in plain
    adamw = _tpu_text(
        lambda p, g, m, v, lr, c1, c2: opt_update.adamw_leaf(
            p, g, m, v, lr, c1, c2, b1=0.9, b2=0.999, eps=1e-8, wd=0.01,
            interpret=False),
        *[_f32(*shape)] * 4, _f32(), _f32(), _f32(),
    )
    assert "dtpu_opt_update_adamw" in adamw
    bf16 = jnp.bfloat16
    conv = _tpu_text(
        lambda x, w, a, c: conv_epilogue.conv1x1_bn_act(
            x, w, a, c, act="relu", interpret=False),
        jax.ShapeDtypeStruct((8, 7, 7, 64), bf16),
        jax.ShapeDtypeStruct((1, 1, 64, 128), bf16), _f32(128), _f32(128),
    )
    assert "dtpu_conv_epilogue" in conv
    cache = jax.ShapeDtypeStruct((2, 4, 256, 32), bf16)
    attn = _tpu_text(
        lambda q, k, v, n: decode_attn.decode_attention(
            q, k, v, n, scale=32 ** -0.5, blk_k=128),
        jax.ShapeDtypeStruct((2, 4, 32), bf16), cache, cache,
        jax.ShapeDtypeStruct((2,), jnp.int32),
    )
    assert "dtpu_decode_attn" in attn
    from distribuuuu_tpu.ops import moe as moe_ops

    experts, width = jax.ShapeDtypeStruct((4, 128, 128), bf16), 128
    moe = _tpu_text(
        lambda params, x, w, i: jax.value_and_grad(
            lambda p, x: moe_ops.sorted_experts(
                p, x, w, i, interpret=False).astype(jnp.float32).sum(),
            argnums=(0, 1))(params, x),
        {"w_gate": experts, "w_up": experts, "w_down": experts},
        jax.ShapeDtypeStruct((1024, width), bf16), _f32(1024, 2),
        jax.ShapeDtypeStruct((1024, 2), jnp.int32),
    )
    for call in ("gate_up", "fwd", "act_bwd", "dx_gate_up", "dw_down",
                 "dw_gate_up"):
        assert f"dtpu_moe_gmm_{call}" in moe, call


def _run(n_steps=3):
    model = trainer.build_model_from_cfg()
    optimizer = construct_optimizer()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, IM, IM, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 4, (4,)), jnp.int32)
    variables = model.init(jax.random.key(0), x, train=True)
    state = lowering.TrainState(
        params=variables["params"], batch_stats=variables["batch_stats"],
        opt_state=optimizer.init(variables["params"]),
        step=jnp.int32(0), key=jax.random.key(1),
    )
    step = lowering.make_train_step(model, optimizer, topk=2)
    losses = []
    for _ in range(n_steps):
        state, metrics = step(state, {"image": x, "label": y})
        losses.append(np.asarray(metrics["loss"]))
    return jax.device_get((state.params, state.batch_stats, losses))


def test_vjp_form_is_bit_identical_to_value_and_grad(monkeypatch):
    """The ``bwd`` scope stands on ``jax.vjp``; the trajectory is the one
    ``jax.value_and_grad`` gives, bit for bit (parameters, BN statistics and
    every loss of three steps)."""
    cfg.MODEL.ARCH = "resnet18"
    cfg.MODEL.NUM_CLASSES = 4
    scoped = _run()
    monkeypatch.setattr(
        lowering, "value_and_grad_scoped",
        lambda loss_fn: jax.value_and_grad(loss_fn, has_aux=True),
    )
    reference = _run()
    for a, b in zip(jax.tree.leaves(scoped), jax.tree.leaves(reference)):
        np.testing.assert_array_equal(a, b)
    assert not np.array_equal(scoped[2][0], scoped[2][-1])  # it trained


def test_the_looped_decoders_step_carries_its_scopes():
    """``ouro_tiny``'s step through ``lower`` (``models/ouro.py``): every
    scope ``telemetry/schema.py`` declares for it is in the compiled
    program, the backward's second run of the blocks carries
    ``rematted_computation`` (what ``models.recompute_ms_per_step`` sums) and
    holds attention and MLP but neither the head nor the gate, and the gate's
    scope covers both its projection (the model) and its distribution (the
    loss hook)."""
    import distribuuuu_tpu.config as config
    from distribuuuu_tpu.telemetry import schema

    config.reset_cfg()
    cfg.MODEL.ARCH, cfg.MODEL.NUM_CLASSES = "ouro_tiny", 512
    cfg.LM.SEQ_LEN, cfg.DEVICE.COMPUTE_DTYPE = 64, "float32"
    cfg.OPTIM.OPTIMIZER = "adamw"
    cfg.KERNELS.OPT_UPDATE = "pallas"
    try:
        mesh = mesh_lib.build_mesh()
        topology = topo_lib.from_cfg(cfg)
        lowered = lowering.lower(
            trainer.build_model_from_cfg(topology), construct_optimizer(), topk=5,
            mesh=mesh, topology=topology, im_size=IM,
        )
        state, batch = lowered.abstract_args(8)
    finally:
        config.reset_cfg()
    batch = {k: jax.ShapeDtypeStruct((8, 64), jnp.int32, sharding=v.sharding)
             for k, v in batch.items()}
    paths = list(trace.op_names_from_hlo(
        lowered.train_step.lower(state, batch).compile().as_text()).values())

    def among(items, *scopes):
        return [p for p in items if all(trace.in_scope(p, s) for s in scopes)]

    for scope in ("fwd", "bwd", "lm_head", "optimizer_update", "opt_kernel",
                  "attn", "mlp", "exit_gate"):
        assert scope in schema.DEVICE_SCOPES and among(paths, scope), scope
    # a scope that no metric read (``loop_pass``, PR 30) went with PR 35
    assert "loop_pass" not in schema.DEVICE_SCOPES and not among(paths, "loop_pass")
    again = among(paths, "rematted_computation")
    assert again == among(again, "bwd", "Ouro")
    assert among(again, "attn") and among(again, "mlp")
    assert not among(again, "lm_head") and not among(again, "exit_gate")
    assert among(paths, "exit_gate", "Ouro") and among(paths, "exit_gate", "Ouro.head_loss")
    # the blocks' own backward is under ``checkpoint`` and not recomputed
    assert len(among(paths, "bwd", "mlp")) > len(among(again, "mlp"))
    # the head's one walk sits outside every block
    assert not among(paths, "lm_head", "attn") and not among(paths, "lm_head", "mlp")
