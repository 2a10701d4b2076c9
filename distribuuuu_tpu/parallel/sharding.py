"""Sharding specs and host→device placement for the training step.

The reference moves per-GPU batches with ``.cuda(non_blocking=True)``
(ref: /root/reference/distribuuuu/trainer.py:40) and relies on DDP to keep
replicated params in sync. Here placement is declarative: the global batch is
sharded over the ``data`` mesh axis, params are replicated (or sharded over
``model`` when tensor parallelism is on), and XLA compiles the collectives.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for a batch tensor: leading dim split over the data axis."""
    return NamedSharding(mesh, P("data"))


def replicate(mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding (params, scalars)."""
    return NamedSharding(mesh, P())


def _batch_leaf_specs(tree, batch_dim: int):
    """Per-leaf batch specs as a spec tree.

    Image/CNN batches keep the historical blanket layout — dim
    ``batch_dim`` over ``data``, everything else replicated. Token archs
    (``MODEL.ARCH`` gpt*) read ``specs.TOKEN_BATCH_TABLE`` instead, so
    ``[B, S]`` token leaves additionally shard the token dim over ``seq``
    (the dp×sp layout; the table collapses to the blanket form on seq=1
    meshes) while the per-sequence ``mask`` stays on ``data`` alone —
    which is why the spec must be PER LEAF: one shared spec cannot serve
    a rank-2 token leaf and the rank-1 mask at once.
    """
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.parallel.partition import specs as specs_lib

    blanket = P(*([None] * batch_dim + ["data"]))
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    if not specs_lib.is_token_arch(cfg.MODEL.ARCH):
        return jax.tree.unflatten(treedef, [blanket] * len(flat))
    table = specs_lib.batch_table_for(arch=str(cfg.MODEL.ARCH))
    out = []
    for path, _ in flat:
        try:
            base = table.spec_for(jax.tree_util.keystr(path))
        except specs_lib.UnknownLeafError:
            base = P("data")  # non-loader keys keep the blanket layout
        out.append(P(*([None] * batch_dim + list(tuple(base)))))
    return jax.tree.unflatten(treedef, out)


def _put_tree(mesh: Mesh, tree, batch_dim: int):
    """Place a host-local pytree with the dim ``batch_dim`` of every leaf
    sharded over ``data`` (dims before it unsharded) — plus, for token
    batches, the token dim over ``seq`` (``_batch_leaf_specs``).

    In multi-host runs each process holds its own shard of the batch dim
    (DistributedSampler semantics, ref: utils.py:141-143) and this assembles
    the global array from per-host shards; single-host it is a plain sharded
    device_put.
    """
    spec_tree = _batch_leaf_specs(tree, batch_dim)

    # the batch's global extent scales with DATA GROUPS, not processes:
    # processes sharing a data row (model/pipe axes spanning hosts) feed
    # identical copies of the same shard (parallel/mesh.data_process_groups)
    from distribuuuu_tpu.parallel.mesh import data_process_groups

    _, n_groups = data_process_groups(mesh)

    def _put(x, spec):
        x = np.asarray(x)
        sharding = NamedSharding(mesh, spec)
        if jax.process_count() == 1:
            return jax.device_put(x, sharding)
        global_shape = tuple(
            d * n_groups if i == batch_dim else d
            for i, d in enumerate(x.shape)
        )
        return jax.make_array_from_process_local_data(sharding, x, global_shape)

    return jax.tree.map(_put, tree, spec_tree)


def shard_batch(mesh: Mesh, batch):
    """Place a host-local batch pytree as global device arrays sharded on
    ``data``."""
    return _put_tree(mesh, batch, batch_dim=0)


def _micro_split(tree, accum: int):
    """Zero-copy view splitting the batch dim (size B) into
    ``(accum, B/accum)``; raises with per-axis numbers if indivisible."""

    def _split(x):
        x = np.asarray(x)
        b = x.shape[0]
        if b % accum:
            raise ValueError(
                f"batch dim {b} not divisible by GRAD_ACCUM_STEPS={accum}"
            )
        return x.reshape((accum, b // accum) + x.shape[1:])

    return jax.tree.map(_split, tree)


def shard_micro_batch(mesh: Mesh, batch, accum: int):
    """Split a host batch into ``(accum, micro_batch, ...)`` (zero-copy) and
    place it with the micro_batch dim on ``data`` — the input layout for the
    gradient-accumulation train step (TRAIN.GRAD_ACCUM_STEPS)."""
    return _put_tree(mesh, _micro_split(batch, accum), batch_dim=1)
