"""ZeRO / FSDP sharding over the data axis (MESH.ZERO, parallel/zero.py).

The reference replicates params + optimizer state per rank (torch DDP,
ref: /root/reference/distribuuuu/trainer.py:134, utils.py:187-196). The
ZeRO stages must (a) actually deduplicate the state across the 8-device
CPU mesh — asserted on the placed shard sizes, not just on specs — and
(b) leave the math unchanged: the same stream trained at stage 0/1/3
produces the same trajectory modulo float reduction order.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

import distribuuuu_tpu.config as config
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu import trainer
from distribuuuu_tpu.parallel import mesh as mesh_lib, sharding as sharding_lib
from distribuuuu_tpu.parallel import zero
from distribuuuu_tpu.utils.optim import construct_optimizer

BATCH = 16
N_STEPS = 3


def stream_batch(step: int, n: int = BATCH):
    rng = np.random.default_rng(7_000 + step)
    images = rng.standard_normal((n, 32, 32, 3)).astype(np.float32)
    labels = (
        (images.mean(axis=(1, 2, 3)) * 40.0).astype(np.int64) % 10
    ).astype(np.int32)
    images += labels[:, None, None, None] * 0.1
    return {"image": images, "label": labels, "mask": np.ones((n,), np.float32)}


def _setup(stage: int, model_axis: int = 1, optimizer_kind: str = "sgd"):
    config.reset_cfg()
    cfg.MODEL.ARCH = "resnet18"
    cfg.MODEL.NUM_CLASSES = 10
    cfg.MODEL.BN_GROUP = 8
    cfg.OPTIM.BASE_LR = 0.05
    cfg.OPTIM.OPTIMIZER = optimizer_kind
    cfg.DEVICE.COMPUTE_DTYPE = "float32"
    cfg.MESH.DATA = -1
    cfg.MESH.MODEL = model_axis
    cfg.MESH.ZERO = stage
    trainer.check_trainer_mesh()
    mesh = mesh_lib.mesh_from_cfg(cfg)
    model = trainer.build_model_from_cfg()
    layout = trainer._state_layout(model, mesh, 32) if stage else None
    state = trainer.create_train_state(
        model, jax.random.key(0), mesh, 32, layout=layout
    )
    step = trainer.make_train_step(
        model, construct_optimizer(), topk=5, layout=layout
    )
    return mesh, model, state, step


def _momentum_leaves(opt_state):
    """All param-shaped momentum/trace arrays inside the optax state."""
    return [
        x
        for x in jax.tree.leaves(opt_state)
        if hasattr(x, "ndim") and x.ndim >= 2
    ]


def _run(stage: int, model_axis: int = 1):
    mesh, model, state, step = _setup(stage, model_axis)
    losses = []
    for it in range(N_STEPS):
        batch = sharding_lib.shard_batch(mesh, stream_batch(it))
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    return state, losses


# ---------------------------------------------------------------- unit level


def test_add_data_axis_picks_largest_free_divisible_dim():
    # conv kernel [3, 3, 64, 128], data=8: out-dim (largest, divisible)
    assert zero.add_data_axis(P(), (3, 3, 64, 128), 8) == P(
        None, None, None, "data"
    )
    # TP-taken out dim at real TP (model=2): remaining extents tie at 64,
    # the free in-dim wins
    assert zero.add_data_axis(
        P(None, None, None, "model"), (3, 3, 64, 128), 8, {"model": 2}
    ) == P(None, None, "data", "model")
    # TP annotation with a collapsed model axis (size 1): data appends to
    # the annotated out dim — largest remaining extent
    assert zero.add_data_axis(
        P(None, None, None, "model"), (3, 3, 64, 128), 8, {"model": 1}
    ) == P(None, None, None, ("model", "data"))
    # stem-shaped kernel (7,7,3,64): only the annotated out dim divides
    assert zero.add_data_axis(
        P(None, None, None, "model"), (7, 7, 3, 64), 8, {"model": 1}
    ) == P(None, None, None, ("model", "data"))
    # idempotent: an already-ZeRO'd spec is left alone
    assert zero.add_data_axis(
        P(None, None, None, ("model", "data")), (3, 3, 64, 128), 8
    ) == P(None, None, None, ("model", "data"))
    # nothing divisible: unchanged
    assert zero.add_data_axis(P(), (3, 3, 63, 127), 8) == P()
    # too small to be worth sharding: unchanged
    assert zero.add_data_axis(P(), (64,), 8) == P()
    # data axis of 1 (single chip): unchanged
    assert zero.add_data_axis(P(), (3, 3, 64, 128), 1) == P()


def test_zero_step_without_layout_refused():
    """ADVICE r4 (medium): the docstring's promise is now enforced — a
    step built without the ZeRO layout while MESH.ZERO is set raises
    instead of silently producing a neither-DDP-nor-ZeRO layout."""
    config.reset_cfg()
    cfg.MODEL.ARCH = "resnet18"
    cfg.MESH.ZERO = 1
    model = trainer.build_model_from_cfg()
    with pytest.raises(ValueError, match="ZeRO state layout"):
        trainer.make_train_step(model, construct_optimizer(), topk=5)
    config.reset_cfg()


def test_zero_stage_validation():
    config.reset_cfg()
    cfg.MESH.ZERO = 2
    with pytest.raises(ValueError, match="stage 2 is"):
        trainer.check_trainer_mesh()
    # ZeRO-3 under PP was refused before the partition layer (r11); it is
    # now a supported LAYOUT — FSDP params gather at the stage shard_map
    # boundary (in_specs), the backward reduce-scatters. The stanza must
    # validate and classify with both features.
    config.reset_cfg()
    cfg.MESH.ZERO = 3
    cfg.MESH.PIPE = 2
    cfg.MODEL.ARCH = "vit_tiny"
    topo = trainer.check_trainer_mesh()
    assert {"pp", "zero3"} <= set(topo.describe()["features"])


# ------------------------------------------------------------- layout level


def test_zero1_shards_optimizer_state_not_params():
    _, _, state, _ = _setup(stage=1)
    n_dev = jax.device_count()
    sharded = 0
    for leaf in _momentum_leaves(state.opt_state):
        if leaf.size >= zero.MIN_SHARD_ELEMS:
            shard = leaf.addressable_shards[0].data
            assert shard.size == leaf.size // n_dev, leaf.shape
            sharded += 1
    assert sharded >= 10  # every conv kernel's momentum buffer
    # params stay replicated (DDP rest layout)
    for leaf in jax.tree.leaves(state.params):
        assert leaf.addressable_shards[0].data.size == leaf.size


@pytest.mark.slow
def test_zero3_shards_params_too():
    _, _, state, _ = _setup(stage=3)
    n_dev = jax.device_count()
    sharded = 0
    for leaf in jax.tree.leaves(state.params):
        if leaf.addressable_shards[0].data.size == leaf.size // n_dev:
            sharded += 1
    assert sharded >= 10
    # batch_stats stay replicated (updated from in-graph psums every step)
    for leaf in jax.tree.leaves(state.batch_stats):
        assert leaf.addressable_shards[0].data.size == leaf.size


@pytest.mark.slow
def test_zero1_adamw_shards_both_moments():
    _, _, state, _ = _setup(stage=1, optimizer_kind="adamw")
    n_dev = jax.device_count()
    big = [
        leaf
        for leaf in _momentum_leaves(state.opt_state)
        if leaf.size >= zero.MIN_SHARD_ELEMS
    ]
    # adamw carries mu AND nu per param: both must be deduplicated
    assert len(big) >= 20
    for leaf in big:
        assert leaf.addressable_shards[0].data.size == leaf.size // n_dev


@pytest.mark.slow
def test_zero_composes_with_tp():
    mesh, _, state, _ = _setup(stage=1, model_axis=2)
    found_both = 0
    for leaf in _momentum_leaves(state.opt_state):
        spec = leaf.sharding.spec
        names = {n for e in spec if e for n in ((e,) if isinstance(e, str) else e)}
        if {"data", "model"} <= names:
            found_both += 1
    # TP-sharded kernels get ZeRO on a different dim: sharded over BOTH axes
    assert found_both >= 5, found_both


# ---------------------------------------------------------- trajectory level


@pytest.mark.slow
def test_zero_trajectories_match_ddp_layout():
    """Stages 0/1/3 run the same math — layout only. Step-0 loss is
    pre-update (identical init), later steps bound by reduction-order
    drift; all must stay in the same convergence family."""
    _, base = _run(stage=0)
    for stage in (1, 3):
        _, traj = _run(stage=stage)
        assert np.isfinite(traj).all(), (stage, traj)
        np.testing.assert_allclose(
            traj[0], base[0], rtol=0, atol=1e-5, err_msg=f"stage {stage}"
        )
        np.testing.assert_allclose(
            traj[1], base[1], rtol=0, atol=2e-2, err_msg=f"stage {stage}"
        )
        assert abs(traj[2] - base[2]) < 0.5, (stage, traj[2], base[2])


@pytest.mark.slow
def test_zero3_eval_step_works_on_sharded_params():
    mesh, model, state, _ = _setup(stage=3)
    eval_step = trainer.make_eval_step(model, topk=5)
    batch = sharding_lib.shard_batch(mesh, stream_batch(0))
    m = eval_step(state, batch)
    assert float(m["count"]) == BATCH
    assert np.isfinite(float(m["loss_sum"]))


@pytest.mark.slow
def test_zero_checkpoint_roundtrip(tmp_path):
    """Save at stage 1, restore through the template-driven placement
    (trainer._place_like): values equal, rest layout preserved."""
    from distribuuuu_tpu.utils import checkpoint as ckpt

    _, _, state, step = _setup(stage=1)
    mesh = mesh_lib.mesh_from_cfg(cfg)
    batch = sharding_lib.shard_batch(mesh, stream_batch(0))
    state, _ = step(state, batch)
    cfg.defrost()
    cfg.OUT_DIR = str(tmp_path)
    cfg.freeze()
    ckpt.save_checkpoint(trainer._state_tree(state), 0, 0.0, False)
    cfg.defrost()

    restored = ckpt.load_checkpoint(ckpt.get_last_checkpoint())
    placed = trainer._place_like(
        state.opt_state,
        ckpt.unpack_opt_state(state.opt_state, restored["opt_state"]),
    )
    for a, b in zip(
        _momentum_leaves(state.opt_state), _momentum_leaves(placed)
    ):
        assert a.sharding == b.sharding
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_zero1_composes_with_pp():
    """ZeRO-1 × MESH.PIPE>1 (ADVICE r4): the pipelined param tree — stacked
    per-stage leaves entering the pipe shard_map — is a materially
    different layout than the data-axis-only cases above. Asserts (a) the
    momentum buffers are genuinely deduplicated over data ON TOP of the
    pipe stacking (shard-size accounting) and (b) the trajectory matches
    the stage-0 PP run."""

    def run(stage):
        config.reset_cfg()
        cfg.MODEL.ARCH = "vit_tiny"
        cfg.MODEL.NUM_CLASSES = 10
        cfg.TRAIN.IM_SIZE = 32
        cfg.DEVICE.COMPUTE_DTYPE = "float32"
        cfg.MESH.PIPE = 4
        cfg.MESH.MICROBATCH = 4
        cfg.MESH.DATA = -1
        cfg.MESH.ZERO = stage
        trainer.check_trainer_mesh()
        mesh = mesh_lib.mesh_from_cfg(cfg)
        model = trainer.build_model_from_cfg()
        layout = trainer._state_layout(model, mesh, 32) if stage else None
        state = trainer.create_train_state(
            model, jax.random.key(0), mesh, 32, layout=layout
        )
        step = trainer.make_train_step(
            model, construct_optimizer(), topk=5, layout=layout
        )
        losses = []
        for it in range(N_STEPS):
            batch = sharding_lib.shard_batch(mesh, stream_batch(it))
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        return mesh, state, losses

    mesh, state, traj = run(stage=1)
    n_dev = jax.device_count()
    pipe = dict(mesh.shape)["pipe"]
    assert pipe == 4 and dict(mesh.shape)["data"] == n_dev // 4

    both = 0
    for leaf in _momentum_leaves(state.opt_state):
        if leaf.size // pipe < zero.MIN_SHARD_ELEMS:
            continue
        spec = leaf.sharding.spec
        names = {
            n
            for e in spec
            if e
            for n in ((e,) if isinstance(e, str) else e)
        }
        if {"data", "pipe"} <= names:
            shard = leaf.addressable_shards[0].data
            assert shard.size == leaf.size // n_dev, (leaf.shape, spec)
            both += 1
    # every stacked transformer-block kernel's momentum must carry both
    assert both >= 8, both

    _, _, base = run(stage=0)
    assert np.isfinite(traj).all(), traj
    np.testing.assert_allclose(traj[0], base[0], rtol=0, atol=1e-5)
    np.testing.assert_allclose(traj[1], base[1], rtol=0, atol=2e-2)
    assert abs(traj[2] - base[2]) < 0.5, (traj, base)
