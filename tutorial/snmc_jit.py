"""Tutorial 2/6 — SNMC: Single Node, Multi Chip via jit + sharding.

The reference's step 2 is ``nn.DataParallel`` (≙ ref tutorial/snmc_dp.py):
one process drives every local GPU by replicate-and-scatter. On TPU this
mode is not a wrapper — it is how JAX already works. One Python process sees
every local chip; you describe WHERE data and params live with a
``jax.sharding.Mesh`` + ``NamedSharding``, and ``jax.jit`` compiles ONE SPMD
program for all chips, inserting the cross-chip gradient reduction (the
NCCL-allreduce equivalent, compiled to ICI collectives) automatically.

The only changes from tutorial 1 (snsc.py):

  1. build a 1-axis mesh over the local chips:        Mesh(devices, ("data",))
  2. place the batch "sharded over data":             NamedSharding(P("data"))
  3. place params/opt-state "replicated":             NamedSharding(P())

The train_step body is UNCHANGED. That is the point: data parallelism on TPU
is a data-placement statement, not a code restructure. XLA sees replicated
params combined with sharded batch and emits psum for the grads on its own.

Run on a multi-chip host:

    python tutorial/snmc_jit.py

Or simulate 8 chips on CPU (the "multi-node without a cluster" trick,
≙ ref README.md:119-144 oversubscription):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tutorial/snmc_jit.py

Expected output (8 virtual CPU devices, synthetic data, seed 0):

    devices: 8 × cpu
    global batch 256 = 32 per chip
    [epoch 1/2] step  30/ 30  loss 0.0286
    [epoch 2/2] step  30/ 30  loss 0.0248
    done: final train loss 0.0248, sharded over 8 chips
"""

from __future__ import annotations

import os
import sys

# repo root onto sys.path so `python tutorial/<name>.py` works from anywhere
# (a script's sys.path[0] is tutorial/, not the repo root)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distribuuuu_tpu import models

BATCH = 256  # GLOBAL batch — jit shards it over the mesh
EPOCHS = 2
STEPS_PER_EPOCH = 30  # short demo epochs (CPU-simulation friendly)
LR = 0.1
SEED = 0


def synthetic_cifar(rng, n):
    images = rng.standard_normal((n, 32, 32, 3), dtype=np.float32)
    labels = ((images.mean(axis=(1, 2, 3)) * 40.0).astype(np.int64) % 10).astype(
        np.int32
    )
    images += labels[:, None, None, None] * 0.1
    return images, labels


def main():
    devices = jax.devices()
    print(f"devices: {len(devices)} × {devices[0].device_kind}")
    print(f"global batch {BATCH} = {BATCH // len(devices)} per chip")

    # 1. the mesh: one named axis, every local chip. This object replaces the
    #    whole process-group/init_process_group machinery for one host.
    mesh = Mesh(np.asarray(devices), ("data",))
    shard_data = NamedSharding(mesh, P("data"))  # split dim 0 across chips
    replicate = NamedSharding(mesh, P())         # same value on every chip

    model = models.build_model("resnet18", num_classes=10, dtype=jnp.float32)
    variables = model.init(jax.random.key(SEED), jnp.ones((1, 32, 32, 3)), train=False)
    tx = optax.sgd(LR, momentum=0.9, nesterov=True)

    # 2. placement: params/stats/opt-state replicated (≙ DDP keeping a full
    #    copy per rank), done once at init.
    params = jax.device_put(variables["params"], replicate)
    batch_stats = jax.device_put(variables["batch_stats"], replicate)
    opt_state = jax.device_put(tx.init(params), replicate)

    @jax.jit  # identical body to snsc.py — parallelism lives in the shardings
    def train_step(params, batch_stats, opt_state, images, labels):
        def loss_fn(p):
            logits, mutated = model.apply(
                {"params": p, "batch_stats": batch_stats},
                images, train=True, mutable=["batch_stats"],
            )
            loss = optax.softmax_cross_entropy(
                logits, jax.nn.one_hot(labels, 10)
            ).mean()
            return loss, mutated["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        # grads of replicated params w.r.t. sharded batch ⇒ XLA inserts the
        # cross-chip psum HERE. No DDP wrapper, no bucket tuning: the
        # allreduce is fused into the compiled step and overlapped by XLA's
        # latency-hiding scheduler.
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_stats, opt_state, loss

    rng = np.random.default_rng(SEED)
    final = 0.0
    for epoch in range(EPOCHS):
        for step in range(STEPS_PER_EPOCH):
            images, labels = synthetic_cifar(rng, BATCH)
            # 3. the batch is placed sharded: chip i holds rows [i*64, (i+1)*64)
            images = jax.device_put(images, shard_data)
            labels = jax.device_put(labels, shard_data)
            params, batch_stats, opt_state, loss = train_step(
                params, batch_stats, opt_state, images, labels
            )
            final = float(loss)
            if (step + 1) == STEPS_PER_EPOCH:
                print(
                    f"[epoch {epoch + 1}/{EPOCHS}] step {step + 1:3d}/"
                    f"{STEPS_PER_EPOCH:3d}  loss {final:.4f}"
                )
    print(f"done: final train loss {final:.4f}, sharded over {len(devices)} chips")


if __name__ == "__main__":
    main()
