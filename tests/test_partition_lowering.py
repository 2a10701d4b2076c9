"""The one lowering (parallel/partition/lowering.py): trajectory
equivalence against the hand-assembled legacy path for every shipped
topology class, and the ISSUE 9 acceptance compositions — ZeRO-3 under
PP and a dp×tp×ep 3-axis mesh with ZeRO-1 — training from a YAML mesh
stanza alone on the 8-device CPU mesh."""

import tempfile

import numpy as np
import jax
import pytest

import distribuuuu_tpu.config as config
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu import trainer
from distribuuuu_tpu.parallel import mesh as mesh_lib, sharding as sharding_lib
from distribuuuu_tpu.parallel.partition import lowering, topology
from distribuuuu_tpu.utils.optim import construct_optimizer

N_STEPS = 3


def stream_batch(step: int, n: int = 16, im: int = 32):
    rng = np.random.default_rng(11_000 + step)
    images = rng.standard_normal((n, im, im, 3)).astype(np.float32)
    labels = (
        (images.mean(axis=(1, 2, 3)) * 40.0).astype(np.int64) % 10
    ).astype(np.int32)
    images += labels[:, None, None, None] * 0.1
    return {
        "image": images, "label": labels, "mask": np.ones((n,), np.float32)
    }


def _merge_stanza(yaml_text: str):
    with tempfile.NamedTemporaryFile("w", suffix=".yaml") as f:
        f.write(yaml_text)
        f.flush()
        cfg.merge_from_file(f.name)


def _run_lowered(n_steps=N_STEPS, batch=16, im=32, seed=0):
    """The full partition path: registry → lowering → steps, as
    train_model wires it."""
    topo = trainer.check_trainer_mesh()
    mesh = mesh_lib.mesh_from_cfg(cfg)
    model = trainer.build_model_from_cfg(topo)
    low = lowering.lower(
        model, construct_optimizer(), 5, mesh=mesh, topology=topo, im_size=im
    )
    state = trainer.create_train_state(
        model, jax.random.key(seed), mesh, im, layout=low.layout
    )
    losses = []
    for it in range(n_steps):
        state, m = low.train_step(
            state, low.put_batch(stream_batch(it, batch, im))
        )
        losses.append(float(m["loss"]))
    return low, state, losses


def _run_legacy(n_steps=N_STEPS, batch=16, im=32, seed=0):
    """The pre-r11 hand assembly: _state_layout + make_train_step with the
    layout passed only when ZeRO is on."""
    mesh = mesh_lib.mesh_from_cfg(cfg)
    model = trainer.build_model_from_cfg()
    layout = trainer._state_layout(model, mesh, im) if cfg.MESH.ZERO else None
    state = trainer.create_train_state(
        model, jax.random.key(seed), mesh, im, layout=layout
    )
    step = trainer.make_train_step(
        model, construct_optimizer(), topk=5, layout=layout
    )
    losses = []
    for it in range(n_steps):
        state, m = step(
            state, sharding_lib.shard_batch(mesh, stream_batch(it, batch, im))
        )
        losses.append(float(m["loss"]))
    return state, losses


def _assert_lockstep(traj, base):
    """The repo's lockstep tolerance (tests/test_zero.py): step-0 loss is
    pre-update (identical init) — tight; later steps bounded by XLA
    reduction-order drift."""
    assert np.isfinite(traj).all(), traj
    np.testing.assert_allclose(traj[0], base[0], rtol=0, atol=1e-5)
    np.testing.assert_allclose(traj[1], base[1], rtol=0, atol=2e-2)
    assert abs(traj[2] - base[2]) < 0.5, (traj, base)


# ------------------------------------------------- acceptance compositions


def test_zero3_under_pp_trains_from_stanza_alone():
    """ZeRO-3 × PP — flatly refused before r11 (trainer.py:92-96) — trains
    from a YAML mesh stanza alone: FSDP params rest data-sharded, gather
    at the stage shard_map boundary, backward reduce-scatters."""
    config.reset_cfg()
    _merge_stanza(
        "MODEL: {ARCH: vit_tiny, NUM_CLASSES: 10}\n"
        "TRAIN: {IM_SIZE: 32}\n"
        "DEVICE: {COMPUTE_DTYPE: float32}\n"
        "MESH: {DATA: 2, PIPE: 4, MICROBATCH: 4, ZERO: 3}\n"
    )
    low, state, losses = _run_lowered(n_steps=2)
    assert np.isfinite(losses).all(), losses
    assert losses[1] < losses[0]  # the update actually lands on the layout

    # params genuinely deduplicated over data AT REST (shard accounting,
    # not specs): the composition is a layout, not a fallback
    deduped = 0
    for leaf in jax.tree.leaves(state.params):
        spec = getattr(leaf.sharding, "spec", ())
        names = {
            n for e in spec if e for n in ((e,) if isinstance(e, str) else e)
        }
        if "data" in names and leaf.addressable_shards[0].data.size < leaf.size:
            deduped += 1
    assert deduped >= 10, deduped


@pytest.mark.slow  # 41s: 3-axis mesh compile + train; tier-1 budget
def test_three_axis_ep_with_zero1_trains_from_stanza_alone():
    """dp2×tp2×ep2 + ZeRO-1 — pathless before r11 (no expert axis
    existed) — trains from a YAML stanza alone: experts on the dedicated
    axis, dense kernels on the TP axis, optimizer state ZeRO'd over
    data."""
    config.reset_cfg()
    _merge_stanza(
        "MODEL: {ARCH: vit_tiny_moe, NUM_CLASSES: 10}\n"
        "TRAIN: {IM_SIZE: 32}\n"
        "DEVICE: {COMPUTE_DTYPE: float32}\n"
        "MESH: {DATA: 2, MODEL: 2, EXPERT: 2, ZERO: 1}\n"
    )
    low, state, losses = _run_lowered(n_steps=2)
    assert np.isfinite(losses).all(), losses
    assert low.topology.moe_axis() == "expert"

    def axes_of(leaf):
        spec = getattr(leaf.sharding, "spec", ())
        return {
            n for e in spec if e for n in ((e,) if isinstance(e, str) else e)
        }

    p_axes = [axes_of(leaf) for leaf in jax.tree.leaves(state.params)]
    assert any("expert" in a for a in p_axes)  # expert tensors on ep
    assert any("model" in a for a in p_axes)   # dense kernels on tp
    zeroed = sum(
        1
        for leaf in jax.tree.leaves(state.opt_state)
        if hasattr(leaf, "sharding") and "data" in axes_of(leaf)
        and leaf.addressable_shards[0].data.size < leaf.size
    )
    assert zeroed >= 10, zeroed


# ------------------------------------------- equivalence vs the legacy path


@pytest.mark.slow  # 38s: legacy-vs-lowering A/B train; tier-1 budget (ISSUE 18)
def test_lowering_reproduces_legacy_dp_zero1():
    """dp8 + ZeRO-1 (resnet18): the declarative path and the hand
    assembly build the same program — trajectories agree to float-drift
    tolerance from the same seeds/stream."""
    config.reset_cfg()
    cfg.MODEL.ARCH = "resnet18"
    cfg.MODEL.NUM_CLASSES = 10
    cfg.MODEL.BN_GROUP = 8
    cfg.DEVICE.COMPUTE_DTYPE = "float32"
    cfg.MESH.ZERO = 1
    _, _, traj = _run_lowered()
    base, base_traj = _run_legacy()
    _assert_lockstep(traj, base_traj)


@pytest.mark.slow
@pytest.mark.parametrize(
    "stanza",
    [
        {"MODEL.ARCH": "resnet18"},                                   # dp
        {"MODEL.ARCH": "resnet18", "MESH.MODEL": 2},                  # dp×tp
        {"MODEL.ARCH": "resnet18", "MESH.ZERO": 3},                   # fsdp
        {"MODEL.ARCH": "vit_tiny", "MESH.PIPE": 4,
         "MESH.MICROBATCH": 4},                                       # pp
        {"MODEL.ARCH": "vit_tiny_moe", "MESH.MODEL": 2},              # ep
    ],
    ids=["dp", "dp_tp", "zero3", "pp", "moe"],
)
def test_lowering_reproduces_legacy_topologies(stanza):
    """Every shipped topology class: new lowering vs legacy assembly at
    the lockstep tolerance."""
    config.reset_cfg()
    cfg.MODEL.NUM_CLASSES = 10
    cfg.MODEL.BN_GROUP = 8
    cfg.TRAIN.IM_SIZE = 32
    cfg.DEVICE.COMPUTE_DTYPE = "float32"
    flat = [x for kv in stanza.items() for x in kv]
    cfg.merge_from_list(list(map(str, flat)))
    _, _, traj = _run_lowered()
    _, base_traj = _run_legacy()
    _assert_lockstep(traj, base_traj)


@pytest.mark.slow
def test_zero3_pp_trajectory_matches_stage0():
    """ZeRO-3 under PP is a LAYOUT: the trajectory matches the stage-0 PP
    run at the lockstep tolerance (same contract test_zero.py pins for
    the other stages)."""

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
        _, _, losses = _run_lowered()
        return losses

    traj = run(3)
    base = run(0)
    _assert_lockstep(traj, base)


def test_lowered_accum_path_builds():
    """The accumulating variant builds through the same lowering (accum
    routes put_batch to the micro split) and is one optimizer step."""
    config.reset_cfg()
    cfg.MODEL.ARCH = "resnet18"
    cfg.MODEL.NUM_CLASSES = 10
    cfg.MODEL.BN_GROUP = 4
    cfg.DEVICE.COMPUTE_DTYPE = "float32"
    topo = trainer.check_trainer_mesh()
    mesh = mesh_lib.mesh_from_cfg(cfg)
    model = trainer.build_model_from_cfg(topo)
    low = lowering.lower(
        model, construct_optimizer(), 5, mesh=mesh, topology=topo,
        im_size=32, accum=2,
    )
    assert low.accum == 2
    state = trainer.create_train_state(
        model, jax.random.key(0), mesh, 32, layout=low.layout
    )
    host = stream_batch(0)
    batch = low.put_batch(host)
    assert batch["image"].shape[:2] == (2, host["image"].shape[0] // 2)
    state, metrics = low.train_step(state, batch)
    assert np.isfinite(float(metrics["loss"])) and int(state.step) == 1
