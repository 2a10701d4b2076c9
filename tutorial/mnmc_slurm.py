"""Tutorial 5/6 — MNMC on Slurm: the cluster launch path.

Same program as tutorial 4 — only the RENDEZVOUS changes. On a Slurm
cluster nobody exports RANK/MASTER_ADDR by hand: ``srun`` starts one task
per host and describes the allocation in ``SLURM_*`` env vars. This script
derives the JAX rendezvous from them (≙ ref tutorial/mnmc_ddp_slurm.py's
mmcv-style bridge, and distribuuuu_tpu.parallel.mesh.setup_distributed's
Slurm branch, which is the framework version of this file):

    SLURM_PROCID    → process_id            (global rank)
    SLURM_NTASKS    → num_processes         (world size)
    SLURM_NODELIST  → coordinator_address   (first host in the allocation,
                      expanded via `scontrol show hostname | head -n1`)

Launch on a TPU pod (one task per HOST — JAX drives all local chips from
one process, so ``--ntasks-per-node=1``; contrast the reference which needs
one task per GPU):

    srun --partition=tpu --nodes=4 --ntasks-per-node=1 \
        python tutorial/mnmc_slurm.py

Simulate the Slurm environment on one machine (spawns N localhost processes
with faked SLURM_* vars — verifies the derivation logic end-to-end):

    python tutorial/mnmc_slurm.py --simulate 2

Expected output (--simulate 2, seed 0; rank 0 shown):

    [rank 0] slurm: proc 0/2, coordinator 127.0.0.1:29567
    [rank 0] local devices: 4, global devices: 8, processes: 2
    [rank 0] epoch 1/2 final loss 0.0119
    [rank 0] epoch 2/2 final loss 0.0215
    [rank 0] done
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

BATCH, EPOCHS, STEPS, LR, SEED = 512, 2, 97, 0.1, 0


def first_host(nodelist: str) -> str:
    """Expand a Slurm nodelist to its first hostname.

    Uses ``scontrol`` when present (≙ ref utils.py:30); falls back to
    parsing simple lists ("host0,host1" or a bare hostname) so the logic is
    testable off-cluster.
    """
    out = subprocess.getoutput(f"scontrol show hostname {nodelist} | head -n1").strip()
    if out and "not found" not in out and "error" not in out.lower():
        return out.splitlines()[0]
    return nodelist.split(",")[0].strip()


def run():
    proc_id = int(os.environ.get("SLURM_PROCID", 0))
    n_procs = int(os.environ.get("SLURM_NTASKS", 1))
    port = int(os.environ.get("COORDINATOR_PORT", 29566))

    def log(msg):
        print(f"[rank {proc_id}] {msg}", flush=True)

    import jax

    if n_procs > 1:
        coord = f"{first_host(os.environ['SLURM_NODELIST'])}:{port}"
        log(f"slurm: proc {proc_id}/{n_procs}, coordinator {coord}")
        jax.distributed.initialize(
            coordinator_address=coord, num_processes=n_procs, process_id=proc_id
        )

    # -- identical training program to tutorial 4 from here on --------------
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from flax import linen as nn
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

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
    params = jax.device_put(
        model.init(jax.random.key(SEED), jnp.ones((1, 32, 32, 3)))["params"],
        replicate,
    )
    opt_state = jax.device_put(tx.init(params), replicate)

    @jax.jit
    def train_step(params, opt_state, images, labels):
        def loss_fn(p):
            logits = model.apply({"params": p}, images)
            return optax.softmax_cross_entropy(
                logits, jax.nn.one_hot(labels, 10)
            ).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    per_proc = BATCH // jax.process_count()
    rank = jax.process_index()
    rng = np.random.default_rng(SEED)
    for epoch in range(EPOCHS):
        for step in range(STEPS):
            images = rng.standard_normal((BATCH, 32, 32, 3), dtype=np.float32)
            labels = (
                (images.mean(axis=(1, 2, 3)) * 40.0).astype(np.int64) % 10
            ).astype(np.int32)
            images += labels[:, None, None, None] * 0.1
            lo, hi = rank * per_proc, (rank + 1) * per_proc
            gimages = jax.make_array_from_process_local_data(shard_data, images[lo:hi])
            glabels = jax.make_array_from_process_local_data(shard_data, labels[lo:hi])
            params, opt_state, loss = train_step(params, opt_state, gimages, glabels)
            if (step + 1) == STEPS:
                log(f"epoch {epoch + 1}/{EPOCHS} final loss {float(loss):.4f}")
    log("done")


def _simulated(proc_id: int, n: int, port: int):
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
    ).strip()
    os.environ.update(
        SLURM_PROCID=str(proc_id),
        SLURM_NTASKS=str(n),
        SLURM_NODELIST="127.0.0.1",
        COORDINATOR_PORT=str(port),
    )
    run()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--simulate", type=int, default=0, metavar="N",
                    help="fake a N-task Slurm allocation on localhost")
    ap.add_argument("--port", type=int, default=29567)
    args = ap.parse_args()
    if args.simulate > 1:
        import multiprocessing as mp

        ctx = mp.get_context("spawn")
        procs = [
            ctx.Process(target=_simulated, args=(r, args.simulate, args.port))
            for r in range(args.simulate)
        ]
        for p in procs:
            p.start()
        for p in procs:
            p.join()
        sys.exit(max(p.exitcode or 0 for p in procs))
    run()


if __name__ == "__main__":
    main()
