"""Tutorial 4/6 — MNMC: Multi Node, Multi Chip — the multi-process jump.

Tutorials 2-3 drove every chip from ONE process. Across hosts that is no
longer possible: each host runs its own Python process, and the processes
must rendezvous into one global system (≙ ref tutorial/mnmc_ddp_launch.py's
``init_process_group(backend="nccl")`` + env vars, and mnmc_ddp_mp.py's
self-spawned TCP variant).

The JAX shape of the same idea:

  1. every process calls ``jax.distributed.initialize(coordinator, N, rank)``
     — process 0 is the coordinator (≙ MASTER_ADDR rendezvous);
  2. after it returns, ``jax.devices()`` is GLOBAL: all chips on all hosts;
     ``jax.local_devices()`` is what this process physically drives;
  3. each process loads only its OWN slice of the batch (≙
     DistributedSampler) and assembles a GLOBAL array from the local shards:
     ``jax.make_array_from_process_local_data(sharding, local_batch)``;
  4. the jitted train step is identical to tutorial 2. XLA compiles the same
     SPMD program on every host; gradient reduction rides ICI within a host
     and DCN across hosts. There is no "multi-node codepath" in the model.

Launch — torch-launcher-style env on each host (≙ ref README launcher):

    # host 0                                  # host 1
    MASTER_ADDR=host0 WORLD_SIZE=2 RANK=0 \\   MASTER_ADDR=host0 WORLD_SIZE=2 RANK=1 \\
        python tutorial/mnmc_multihost.py         python tutorial/mnmc_multihost.py

Or simulate 2 hosts × 4 chips on one machine (each process gets 4 virtual
CPU devices — the "multi-node without a cluster" trick):

    python tutorial/mnmc_multihost.py --spawn 2

Expected output (--spawn 2, seed 0; both processes print, rank 0 shown —
note both ranks report the SAME loss, the global one):

    [rank 0] local devices: 4, global devices: 8, processes: 2
    [rank 0] global batch 512 = 256 per process = 64 per chip
    [rank 0] epoch 1/2 final loss 0.0119
    [rank 0] epoch 2/2 final loss 0.0215
    [rank 0] done — same math as tutorials 2/3, now across processes
"""

from __future__ import annotations

import argparse
import os
import sys

BATCH, EPOCHS, STEPS, LR, SEED = 512, 2, 97, 0.1, 0


def run():
    # -- 1. rendezvous ------------------------------------------------------
    # torch-launcher-style env contract (≙ ref utils.py:41-43): every process
    # knows the coordinator address, world size, and its own rank.
    rank = int(os.environ.get("RANK", 0))
    world = int(os.environ.get("WORLD_SIZE", 1))
    import jax

    if world > 1:
        jax.distributed.initialize(
            coordinator_address=f"{os.environ['MASTER_ADDR']}:"
            f"{os.environ.get('MASTER_PORT', 29566)}",
            num_processes=world,
            process_id=rank,
        )
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax import linen as nn
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    def log(msg):  # every process may print; rank 0 is the canonical transcript
        print(f"[rank {rank}] {msg}", flush=True)

    # -- 2. global device view ---------------------------------------------
    log(
        f"local devices: {jax.local_device_count()}, "
        f"global devices: {jax.device_count()}, processes: {jax.process_count()}"
    )
    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    shard_data = NamedSharding(mesh, P("data"))
    replicate = NamedSharding(mesh, P())

    class TinyCNN(nn.Module):
        @nn.compact
        def __call__(self, x):
            for feats in (32, 64, 128):
                x = nn.relu(nn.Conv(feats, (3, 3), strides=(2, 2))(x))
            return nn.Dense(10)(x.mean(axis=(1, 2)))

    model = TinyCNN()
    tx = optax.sgd(LR, momentum=0.9, nesterov=True)
    # Same seed everywhere ⇒ identical init on every process; placing with a
    # replicated sharding keeps them in lockstep from then on (≙ DDP's
    # init-time param broadcast, without the broadcast).
    params = jax.device_put(
        model.init(jax.random.key(SEED), jnp.ones((1, 32, 32, 3)))["params"],
        replicate,
    )
    opt_state = jax.device_put(tx.init(params), replicate)

    @jax.jit  # unchanged from tutorial 2 — multi-host is a data-placement fact
    def train_step(params, opt_state, images, labels):
        def loss_fn(p):
            logits = model.apply({"params": p}, images)
            return optax.softmax_cross_entropy(
                logits, jax.nn.one_hot(labels, 10)
            ).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    # -- 3. per-process data shard → global array ---------------------------
    per_proc = BATCH // jax.process_count()
    log(
        f"global batch {BATCH} = {per_proc} per process = "
        f"{BATCH // jax.device_count()} per chip"
    )
    rng = np.random.default_rng(SEED)
    for epoch in range(EPOCHS):
        for step in range(STEPS):
            # Each process generates the FULL deterministic batch and keeps
            # its own rows — exactly DistributedSampler's contract (each rank
            # reads only indices rank::world). A real loader would read just
            # its slice from disk.
            images = rng.standard_normal((BATCH, 32, 32, 3), dtype=np.float32)
            labels = (
                (images.mean(axis=(1, 2, 3)) * 40.0).astype(np.int64) % 10
            ).astype(np.int32)
            images += labels[:, None, None, None] * 0.1
            lo, hi = rank * per_proc, (rank + 1) * per_proc

            gimages = jax.make_array_from_process_local_data(
                shard_data, images[lo:hi]
            )
            glabels = jax.make_array_from_process_local_data(
                shard_data, labels[lo:hi]
            )
            params, opt_state, loss = train_step(params, opt_state, gimages, glabels)
            if (step + 1) == STEPS:
                log(f"epoch {epoch + 1}/{EPOCHS} final loss {float(loss):.4f}")
    log("done — same math as tutorials 2/3, now across processes")


def _spawned(rank: int, world: int, port: int):
    """Child entry for --spawn: pin env BEFORE jax import (≙ mnmc_ddp_mp.py's
    computed global rank + TCP rendezvous, ref: mnmc_ddp_mp.py:41-66)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()
    os.environ.update(
        MASTER_ADDR="127.0.0.1",
        MASTER_PORT=str(port),
        WORLD_SIZE=str(world),
        RANK=str(rank),
    )
    run()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--spawn", type=int, default=0, metavar="N",
        help="self-spawn N localhost processes (simulated multi-host)",
    )
    ap.add_argument("--port", type=int, default=29566)
    args = ap.parse_args()
    if args.spawn > 1:
        import multiprocessing as mp

        ctx = mp.get_context("spawn")
        procs = [
            ctx.Process(target=_spawned, args=(r, args.spawn, args.port))
            for r in range(args.spawn)
        ]
        for p in procs:
            p.start()
        for p in procs:
            p.join()
        sys.exit(max(p.exitcode or 0 for p in procs))
    run()


if __name__ == "__main__":
    main()
