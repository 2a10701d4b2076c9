"""Optimizer construction (ref: /root/reference/distribuuuu/utils.py:187-196).

The reference builds torch SGD with momentum/dampening/nesterov and L2 weight
decay applied to **all** params including BN (utils.py:187-196,
config.py:43-56). The optax chain below reproduces torch-SGD update order
exactly: decay is added to the gradient *before* the momentum buffer update.

LR is epoch-granular (set once per epoch, ref: trainer.py:25-26), so the
learning rate rides through ``optax.inject_hyperparams`` and the trainer
mutates it between epochs without rebuilding state — jit sees it as a traced
scalar, so no recompilation.
"""

from __future__ import annotations

import os

import optax

from distribuuuu_tpu.config import cfg


def _momentum_dtype():
    """``OPTIM.MOMENTUM_DTYPE``: accumulator dtype for the SGD momentum
    buffer. ``float32`` (default) matches torch bit-for-bit; ``bfloat16``
    keeps fp32 master params but halves the momentum buffer's HBM
    footprint and read+write traffic (~200 MB/step on ResNet-50) — a
    mixed-precision-optimizer configuration the reference cannot express.
    ``DISTRIBUUUU_MOMENTUM_DTYPE`` overrides at trace time."""
    mode = os.environ.get(
        "DISTRIBUUUU_MOMENTUM_DTYPE", cfg.OPTIM.MOMENTUM_DTYPE
    )
    if mode not in ("float32", "bfloat16"):
        raise ValueError(f"OPTIM.MOMENTUM_DTYPE={mode!r}")
    if mode == "bfloat16":
        import jax.numpy as jnp

        return jnp.bfloat16
    return None  # optax default: momentum inherits the param dtype (fp32)


def construct_optimizer() -> optax.GradientTransformation:
    """Build the configured optimizer (``OPTIM.OPTIMIZER``).

    ``sgd`` (default, the reference's only choice): momentum + nesterov +
    uniform L2 decay, torch-ordered. ``adamw``: decoupled weight decay —
    the usual recipe for the ViT extension archs.
    """
    kind = cfg.OPTIM.OPTIMIZER
    if kind not in ("sgd", "adamw"):
        raise ValueError(
            f"OPTIM.OPTIMIZER must be 'sgd' or 'adamw'; got {kind!r}"
        )
    mom_dtype = _momentum_dtype()

    @optax.inject_hyperparams
    def _make(learning_rate):
        if kind == "sgd":
            return optax.chain(
                optax.add_decayed_weights(cfg.OPTIM.WEIGHT_DECAY),
                optax.sgd(
                    learning_rate=learning_rate,
                    momentum=cfg.OPTIM.MOMENTUM or None,
                    nesterov=cfg.OPTIM.NESTEROV,
                    accumulator_dtype=mom_dtype,
                ),
            )
        if kind == "adamw":
            return optax.adamw(
                learning_rate=learning_rate,
                b1=cfg.OPTIM.BETA1,
                b2=cfg.OPTIM.BETA2,
                weight_decay=cfg.OPTIM.WEIGHT_DECAY,
            )
        raise ValueError(
            f"OPTIM.OPTIMIZER must be 'sgd' or 'adamw'; got {kind!r}"
        )

    return _make(learning_rate=cfg.OPTIM.BASE_LR)


def set_lr(opt_state, lr: float):
    """Mutate the injected learning rate (≙ set_lr, ref: utils.py:313-316)."""
    opt_state.hyperparams["learning_rate"] = lr
    return opt_state
