"""Tutorial 6/6 — ImageNet end-to-end with the framework.

The capstone (≙ ref tutorial/imagenet.py): everything from tutorials 1-5
assembled by the framework proper — config system, mesh bootstrap, sharded
input pipeline, jitted train/eval steps, cross-replica metrics, and the
checkpoint save→barrier→load pattern that multi-process fine-tuning needs.

What the framework adds over the hand-rolled tutorials:

  - ``config``: yacs-style YAML + CLI overrides (tutorials hardcode).
  - ``mesh.setup_distributed()``: ALL of tutorials 4+5's rendezvous logic
    (env-var, torch-launcher, and Slurm derivation) behind one call.
  - ``data``: ImageFolder + RandomResizedCrop/flip pipeline, per-host
    sharded with deterministic per-epoch reshuffle; ``MODEL.DUMMY_INPUT``
    swaps in synthetic data so this script runs anywhere.
  - ``trainer.make_train_step``: fwd+loss+bwd+SGD+metrics in one compiled
    program, batch sharded over the ``data`` axis, BN stats global.
  - ``checkpoint``: epoch-granular orbax checkpoints, primary-writer.

Run it anywhere (synthetic data, resnet18, 2 short epochs; on a TPU host it
uses the real chips, and with JAX_PLATFORMS=cpu it fakes an 8-chip mesh):

    python tutorial/imagenet.py

Real ImageNet on a pod: point TRAIN.PATH/TEST.PATH at the extracted
ILSVRC folders, drop DUMMY_INPUT, and launch with srun as in tutorial 5:

    srun --nodes=4 --ntasks-per-node=1 python tutorial/imagenet.py \
        TRAIN.DATASET /data/ILSVRC TEST.DATASET /data/ILSVRC \
        MODEL.DUMMY_INPUT False OPTIM.MAX_EPOCH 100

Expected output (JAX_PLATFORMS=cpu, synthetic data — times vary; the dummy
dataset labels everything class 0, so the model learns it instantly):

    mesh {'data': 8, 'model': 1, 'seq': 1}, model resnet18: 11.228M params
    ... | Epoch[1/2][8/8]  Time ...  Loss 0.0000e+00 (5.5160e-01)  Acc@1 100.00 ( 87.70) ...
    ... | Eval[1]  Loss 0.0000  Acc@1 100.000  Acc@5 100.000  (1024 samples)
    checkpoint saved: .../ckpts/tutorial_imagenet/checkpoints/ckpt_ep_000
    === save → barrier → all-rank load (the fine-tune handoff) ===
    reloaded epoch 1 weights on every process: max |w - w_saved| = 0.00e+00
    ... | Eval[2]  Loss 0.0000  Acc@1 100.000  Acc@5 100.000  (1024 samples)
    done: 2 epochs, best Acc@1 100.000 (all-zero dummy labels ⇒ 100% expected)
"""

from __future__ import annotations

import os
import sys

# repo root onto sys.path so `python tutorial/<name>.py` works from anywhere
# (a script's sys.path[0] is tutorial/, not the repo root)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import shutil

# Demo-friendly: when forced onto CPU (JAX_PLATFORMS=cpu), present a virtual
# 8-chip mesh. Must happen before jax initializes its backend.
if "cpu" in os.environ.get("JAX_PLATFORMS", "") and (
    "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", "")
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax
import numpy as np


def main():
    import distribuuuu_tpu.config as config
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.data import construct_train_loader, construct_val_loader
    from distribuuuu_tpu.parallel import collectives, mesh as mesh_lib
    from distribuuuu_tpu.parallel import sharding as sharding_lib
    from distribuuuu_tpu.utils import checkpoint as ckpt
    from distribuuuu_tpu.utils.logger import setup_logger
    from distribuuuu_tpu.utils.optim import construct_optimizer
    from distribuuuu_tpu.utils.seed import setup_env, setup_seed

    # -- config: defaults < (optional YAML) < overrides ---------------------
    config.reset_cfg()
    cfg.MODEL.ARCH = "resnet18"
    cfg.MODEL.NUM_CLASSES = 100
    cfg.MODEL.DUMMY_INPUT = True          # synthetic data; flip off for ILSVRC
    cfg.TRAIN.IM_SIZE = 32                # tiny shapes so this runs fast anywhere
    cfg.TEST.IM_SIZE = 36
    cfg.TRAIN.BATCH_SIZE = 16             # per-chip (≙ per-GPU in the ref)
    cfg.TEST.BATCH_SIZE = 16
    cfg.TRAIN.PRINT_FREQ = 10
    cfg.OPTIM.MAX_EPOCH = 2
    cfg.OPTIM.BASE_LR = 0.05
    cfg.OUT_DIR = "ckpts/tutorial_imagenet"
    cfg.DEVICE.COMPUTE_DTYPE = "float32"  # bf16 on real TPU; fp32 for CPU demo
    cfg.freeze()

    mesh_lib.setup_distributed()          # tutorials 4+5, one call
    setup_env()
    logger = setup_logger()
    mesh = mesh_lib.mesh_from_cfg(cfg)
    key = setup_seed()

    model = trainer.build_model_from_cfg()
    state = trainer.create_train_state(model, key, mesh, cfg.TRAIN.IM_SIZE)
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    print(f"mesh {dict(mesh.shape)}, model {cfg.MODEL.ARCH}: {n_params / 1e6:.3f}M params")

    optimizer = construct_optimizer()
    train_loader = construct_train_loader()
    val_loader = construct_val_loader()
    topk = trainer.effective_topk()
    train_step = trainer.make_train_step(model, optimizer, topk)
    eval_step = trainer.make_eval_step(model, topk)

    from distribuuuu_tpu.utils import preempt

    preempt.install()  # SIGTERM → clean mid-epoch exit (utils/preempt.py)

    best = 0.0
    start_epoch = 0
    if ckpt.has_checkpoint():
        # a previous (possibly preempted) run left state — pick it up, the
        # same auto-resume the full trainer does
        state, start_epoch, best, pending, _ = trainer._resume(state, mesh)
        if pending is not None:
            # that run finished training epoch `pending` but its eval was
            # preempted: validate it now so it gets best-tracking and its
            # real checkpoint (which supersedes the preempt checkpoint)
            result = trainer.validate(
                val_loader, mesh, state, eval_step, pending, logger
            )
            if result is not None:
                acc1, _ = result
                best = max(best, acc1)
                ckpt.save_checkpoint(
                    trainer._state_tree(state), pending, best, acc1 >= best
                )
                ckpt.prune_preempts(pending + 1)
    for epoch in range(start_epoch, cfg.OPTIM.MAX_EPOCH):
        state, interrupted, _ = trainer.train_epoch(
            train_loader, mesh, state, train_step, epoch, logger
        )
        if interrupted:
            # preemption: persist progress the way the full trainer does
            # (trainer.train_model) so a rerun resumes this epoch
            path = ckpt.save_preempt_checkpoint(
                trainer._state_tree(state), epoch, best
            )
            print(f"preempted — state saved to {path}")
            break
        result = trainer.validate(val_loader, mesh, state, eval_step, epoch, logger)
        if result is None:  # eval preempted: save the trained state, stop
            path = ckpt.save_preempt_checkpoint(
                trainer._state_tree(state), epoch + 1, best, pending_eval=epoch
            )
            print(f"preempted during eval — state saved to {path}")
            break
        acc1, _ = result
        best = max(best, acc1)
        ckpt.save_checkpoint(trainer._state_tree(state), epoch, best, acc1 >= best)
        if epoch == 0:
            print(f"checkpoint saved: {ckpt.get_checkpoint(0)}")

            # -- the multi-process checkpoint handoff -----------------------
            # ≙ ref tutorial/imagenet.py:146-181: rank 0 saves, EVERYONE
            # barriers, then ALL ranks load the same file. Without the
            # barrier, other processes race a half-written checkpoint.
            print("=== save → barrier → all-rank load (the fine-tune handoff) ===")
            collectives.barrier("ckpt_written")
            restored = ckpt.load_checkpoint(ckpt.get_checkpoint(0))
            a = jax.tree.leaves(state.params)[0]
            b = np.asarray(jax.tree.leaves(restored["params"])[0], dtype=a.dtype)
            print(
                "reloaded epoch 1 weights on every process: "
                f"max |w - w_saved| = {float(abs(np.asarray(a) - b).max()):.2e}"
            )

    print(
        f"done: {cfg.OPTIM.MAX_EPOCH} epochs, best Acc@1 {best:.3f} "
        "(all-zero dummy labels ⇒ 100% expected)"
    )
    shutil.rmtree("ckpts/tutorial_imagenet", ignore_errors=True)


if __name__ == "__main__":
    main()
