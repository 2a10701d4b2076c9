"""TRAIN.REMAT — stage 1-2 rematerialization (models/resnet.py, the
remat-for-traffic roofline lever, VERDICT r5 #3): ``nn.remat`` changes
only what is stored vs recomputed for the backward, never the math or the
param tree, so the train step must be equivalent with the knob on or off.
The A/B on the chip is ``benchmark/run.py --workload resnet50.train --set
program.overrides=…`` with ``TRAIN.REMAT`` in the overrides.
"""

import jax
import numpy as np
import pytest

import distribuuuu_tpu.config as config
from distribuuuu_tpu.config import cfg


def _run_steps(remat: bool, hb, n_steps: int = 2):
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.parallel import mesh as mesh_lib, sharding as sharding_lib
    from distribuuuu_tpu.utils.optim import construct_optimizer

    config.reset_cfg()
    cfg.MODEL.ARCH = "resnet18"
    cfg.MODEL.NUM_CLASSES = 10
    cfg.DEVICE.COMPUTE_DTYPE = "float32"
    cfg.TRAIN.REMAT = remat
    mesh = mesh_lib.build_mesh()
    model = trainer.build_model_from_cfg()
    state = trainer.create_train_state(model, jax.random.key(0), mesh, 32)
    step = trainer.make_train_step(model, construct_optimizer(), 5)
    m = None
    for _ in range(n_steps):
        state, m = step(state, sharding_lib.shard_batch(mesh, hb))
    return jax.device_get(state.params), float(m["loss"])


@pytest.mark.slow  # 35s: remat on/off A/B steps; tier-1 budget (ISSUE 18)
def test_remat_step_equivalence():
    """Same init, same batches ⇒ same loss and same updated params with
    and without stage 1-2 rematerialization."""
    rng = np.random.default_rng(0)
    hb = {
        "image": rng.standard_normal((8, 32, 32, 3)).astype(np.float32),
        "label": rng.integers(0, 10, size=(8,)).astype(np.int32),
        "mask": np.ones((8,), np.float32),
    }
    params_plain, loss_plain = _run_steps(False, hb)
    params_remat, loss_remat = _run_steps(True, hb)
    assert loss_remat == pytest.approx(loss_plain, rel=1e-6)
    # identical param TREE (remat is a lifted transform — same names,
    # same shapes: checkpoints interchange) and matching values. The
    # forward is bitwise-identical; the UPDATED params carry ~1e-7 float
    # drift because remat rebuilds the backward graph (recompute instead
    # of reuse), so XLA reassociates its reductions — the same drift
    # class the scan-vs-per-step equivalence tests document.
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=5e-6
        ),
        params_plain, params_remat,
    )


def test_remat_refused_outside_resnet_family():
    """The knob must refuse archs it does not touch rather than silently
    measuring an unchanged step."""
    from distribuuuu_tpu import trainer

    config.reset_cfg()
    cfg.MODEL.ARCH = "vit_tiny"
    cfg.TRAIN.REMAT = True
    with pytest.raises(ValueError, match="TRAIN.REMAT"):
        trainer.build_model_from_cfg()
