"""fp64 trajectory equivalence across dispatch modes — VERDICT r3 #7.

The fp32 suite (test_trajectory.py) can only pin a 2-step exact window:
the dispatch modes round reductions in different orders and training
dynamics amplify the difference violently (measured ~0.13 loss drift by
step 2). This suite runs the same three modes with float64 compute AND a
float64-cast train state, where that rounding floor drops ~2^29×, and
demands lockstep over the full run — restoring the long exact window
r3's recalibration lost, and re-verifying the r4 shifted-variance BN
across every dispatch mode at a precision where formulation errors
cannot hide.

What the f64 harness exposed while being built (each a boundary that
silently re-rounded f64 values to f32, found by drift bisection):
  - classifier heads hard-cast activations to fp32 → layers.head_dtype
    (promote, not cast);
  - cross_entropy / eval log_softmax hard-cast logits → promoted;
  - BN stats hard-cast to fp32 → promoted (layers._BNCore);
  - fp32 *params* round gradients at mode-dependent granularity (accum
    casts each micro-grad, per-step casts once) → the state itself must
    be cast to f64, not just the compute dtype.

Measured with all four fixed (this harness, 12 steps, max over steps):
dptp 6.3e-9, accum 8.3e-9 — pure f64 rounding amplified
by the dynamics. Asserted at 1e-7 — still 6 orders below the fp32
suite's step-2 drift (~0.13).
"""

import numpy as np
import jax
import pytest

import distribuuuu_tpu.config as config
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu import trainer
from distribuuuu_tpu.parallel import mesh as mesh_lib, sharding as sharding_lib
from distribuuuu_tpu.utils.optim import construct_optimizer

pytestmark = pytest.mark.slow  # multi-minute on the 1-core CPU mesh

BATCH = 32
MICRO = 8
N_STEPS = 12


@pytest.fixture()
def x64():
    jax.config.update("jax_enable_x64", True)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", False)


def stream_batch(step: int, n: int = BATCH):
    rng = np.random.default_rng(10_000 + step)
    images = rng.standard_normal((n, 32, 32, 3)).astype(np.float64)
    labels = (
        (images.mean(axis=(1, 2, 3)) * 40.0).astype(np.int64) % 10
    ).astype(np.int32)
    images += labels[:, None, None, None] * 0.1
    return {
        "image": images,
        "label": labels,
        "mask": np.ones((n,), np.float64),
    }


def _to64(tree):
    import jax.numpy as jnp

    return jax.tree.map(
        lambda a: a.astype(jnp.float64)
        if hasattr(a, "dtype") and a.dtype == jnp.float32
        else a,
        tree,
    )


def _setup(model_axis=1):
    config.reset_cfg()
    cfg.MODEL.ARCH = "resnet18"
    cfg.MODEL.NUM_CLASSES = 10
    cfg.MODEL.BN_GROUP = MICRO  # identical normalization in ALL modes
    cfg.OPTIM.BASE_LR = 0.05
    cfg.DEVICE.COMPUTE_DTYPE = "float64"
    cfg.MESH.MODEL = model_axis
    cfg.MESH.DATA = -1
    mesh = mesh_lib.mesh_from_cfg(cfg)
    model = trainer.build_model_from_cfg()
    state = trainer.create_train_state(model, jax.random.key(0), mesh, 32)
    # f64 state: fp32 params would re-round gradients at mode-dependent
    # granularity (module docstring) — the whole chain must be f64
    state = state.replace(
        params=_to64(state.params),
        opt_state=_to64(state.opt_state),
        batch_stats=_to64(state.batch_stats),
    )
    return mesh, model, state


def _run_per_step(model_axis=1):
    mesh, model, state = _setup(model_axis)
    step = trainer.make_train_step(model, construct_optimizer(), topk=5)
    losses = []
    for it in range(N_STEPS):
        batch = sharding_lib.shard_batch(mesh, stream_batch(it))
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    return losses


def _run_accum(accum=BATCH // MICRO):
    mesh, model, state = _setup()
    step = trainer.make_train_step(
        model, construct_optimizer(), topk=5, accum_steps=accum
    )
    losses = []
    for it in range(N_STEPS):
        batch = sharding_lib.shard_micro_batch(mesh, stream_batch(it), accum)
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    return losses


def test_x64_trajectories_lockstep(x64):
    """Per-step, accumulation, and dp×tp trajectories agree at
    every one of the 12 steps under f64 compute + f64 state — the
    formulation-level equivalence claim, free of fp32 rounding chaos."""
    base = _run_per_step()
    accum = _run_accum()
    dptp = _run_per_step(model_axis=2)
    for name, traj in (("accum", accum), ("dptp", dptp)):
        assert np.isfinite(traj).all(), (name, traj)
        np.testing.assert_allclose(
            traj, base, rtol=0, atol=1e-7, err_msg=name
        )
    # the run must also be a real training trajectory, not a fixed point
    assert np.mean(base[-4:]) < 0.8 * np.mean(base[:3]), base
