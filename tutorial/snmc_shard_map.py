"""Tutorial 3/6 — DDP derived by hand: shard_map + explicit psum.

Tutorial 2 said "XLA inserts the gradient allreduce for you". This script
shows EXACTLY what that means by writing the collective yourself — the JAX
analogue of the reference deriving DDP from raw ``init_process_group`` +
``DistributedSampler`` + per-rank model (≙ ref tutorial/mnmc_ddp_launch.py /
mnmc_ddp_mp.py, whose DDP wrapper hides a bucketed NCCL allreduce).

``jax.shard_map`` runs a PER-CHIP function over the mesh: inside it you see
only this chip's shard of the batch, and cross-chip communication is
explicit:

    loss = jax.lax.pmean(local_loss, "data")   # ≡ NCCL allreduce ÷ world

Differentiating through that one collective gives DDP's whole contract:
autodiff transposes the pmean into the cross-chip mean of the per-shard
gradients, so every replica steps with the same global gradient and the
replicated params never diverge. (SyncBatchNorm falls out of the same
primitive — psum the batch moments before normalizing. The model here is
deliberately BN-free so the manual program is equivalent to tutorial 2's
automatic one and we can assert they produce the SAME params; the
framework's BatchNorm gets global-batch stats under jit automatically.)

When do you write this instead of tutorial 2's automatic version? When you
need manual control of WHERE communication happens — to overlap it by hand,
fuse work into it, or implement schedules GSPMD cannot infer (the ring
attention in distribuuuu_tpu/ops/ring_attention.py is shard_map for exactly
that reason). For plain data parallelism, prefer tutorial 2.

Run (8 virtual chips on CPU):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tutorial/snmc_shard_map.py

Expected output (seed 0):

    mesh: {'data': 8}
    [epoch 1/2] step  97/ 97  loss 0.0211
    [epoch 2/2] step  97/ 97  loss 0.0255
    max |param_manual - param_auto| = 0.00e+00   (identical to jit's program)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

BATCH, EPOCHS, STEPS, LR, SEED = 512, 2, 97, 0.02, 0


class TinyCNN(nn.Module):
    """Minimal BN-free CIFAR net: 3 conv stages + linear head."""

    @nn.compact
    def __call__(self, x):
        for feats in (32, 64, 128):
            x = nn.Conv(feats, (3, 3), strides=(2, 2))(x)
            x = nn.relu(x)
        x = x.mean(axis=(1, 2))
        return nn.Dense(10)(x)


def synthetic_cifar(rng, n):
    images = rng.standard_normal((n, 32, 32, 3), dtype=np.float32)
    labels = ((images.mean(axis=(1, 2, 3)) * 40.0).astype(np.int64) % 10).astype(
        np.int32
    )
    images += labels[:, None, None, None] * 0.1
    return images, labels


def loss_fn(model, params, images, labels):
    logits = model.apply({"params": params}, images)
    return optax.softmax_cross_entropy(logits, jax.nn.one_hot(labels, 10)).mean()


def main():
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    print(f"mesh: {dict(mesh.shape)}")
    model = TinyCNN()
    tx = optax.sgd(LR, momentum=0.9, nesterov=True)
    init = model.init(jax.random.key(SEED), jnp.ones((1, 32, 32, 3)))["params"]

    replicate = NamedSharding(mesh, P())
    shard_data = NamedSharding(mesh, P("data"))
    params = jax.device_put(init, replicate)
    opt_state = jax.device_put(tx.init(params), replicate)

    # The per-chip program. Every array argument is the LOCAL shard: images
    # is [64,32,32,3] in here even though the caller passes [512,...].
    def per_chip_step(params, opt_state, images, labels):
        def global_loss(p):
            local = loss_fn(model, p, images, labels)  # this shard's mean
            # ----- THE LINE DDP HIDES -------------------------------------
            # One collective makes the objective global: mean over the data
            # axis (on TPU hardware: an ICI ring allreduce ÷ world — the
            # exact semantic of NCCL allreduce + scaling). Differentiating
            # THROUGH it is what produces DDP's gradient allreduce: autodiff
            # transposes the pmean into the cross-chip mean of the per-shard
            # gradients, so every replica steps identically.
            return jax.lax.pmean(local, "data")
            # (The pmap-era idiom — pmean'ing the *grads* after the fact —
            # assumes pre-0.9 semantics; under modern shard_map a gradient
            # w.r.t. replicated params already carries a pending cross-chip
            # sum, so reduce the LOSS and let AD do the rest.)

        loss, grads = jax.value_and_grad(global_loss)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    train_step = jax.jit(
        jax.shard_map(
            per_chip_step,
            mesh=mesh,
            in_specs=(P(), P(), P("data"), P("data")),
            out_specs=(P(), P(), P()),
        )
    )

    rng = np.random.default_rng(SEED)
    for epoch in range(EPOCHS):
        for step in range(STEPS):
            images, labels = synthetic_cifar(rng, BATCH)
            images = jax.device_put(images, shard_data)
            labels = jax.device_put(labels, shard_data)
            params, opt_state, loss = train_step(params, opt_state, images, labels)
            if (step + 1) == STEPS:
                print(
                    f"[epoch {epoch + 1}/{EPOCHS}] step {step + 1:3d}/{STEPS:3d}"
                    f"  loss {float(loss):.4f}"
                )

    # Cross-check against tutorial 2's automatic version: same seeds, same
    # data order ⇒ the manual pmean must reproduce the allreduce jit inserts.
    auto = _run_auto(mesh, model, tx)
    diff = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(auto))
    )
    print(f"max |param_manual - param_auto| = {diff:.2e}")


def _run_auto(mesh, model, tx):
    """Tutorial 2's automatic-parallelism loop, for the equivalence check."""
    replicate = NamedSharding(mesh, P())
    shard_data = NamedSharding(mesh, P("data"))
    init = model.init(jax.random.key(SEED), jnp.ones((1, 32, 32, 3)))["params"]
    params = jax.device_put(init, replicate)
    opt_state = jax.device_put(tx.init(params), replicate)

    @jax.jit
    def step_fn(params, opt_state, images, labels):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(model, p, images, labels)
        )(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    rng = np.random.default_rng(SEED)
    for _ in range(EPOCHS):
        for _ in range(STEPS):
            images, labels = synthetic_cifar(rng, BATCH)
            images = jax.device_put(images, shard_data)
            labels = jax.device_put(labels, shard_data)
            params, opt_state, _ = step_fn(params, opt_state, images, labels)
    return params


if __name__ == "__main__":
    main()
