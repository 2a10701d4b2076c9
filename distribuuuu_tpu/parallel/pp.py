"""Pipeline parallelism: GPipe-style microbatch schedule over the ``pipe``
mesh axis.

Beyond the reference's capability set (it is DDP-only, SURVEY.md §2.3) —
pipeline parallelism is first-class here because multi-host scale is a core
goal. The design is the TPU-idiomatic SPMD pipeline: every device runs the
SAME compiled program; stage identity comes from ``lax.axis_index("pipe")``;
activations hop stage→stage+1 with ``ppermute`` inside one ``lax.scan`` over
schedule ticks. Differentiating straight through the schedule yields the
reverse pipeline (autodiff transposes ppermute to the opposite shift and the
scan to its reverse), so one ``jax.grad`` gives correct pipeline-parallel
training with no hand-written backward schedule.

Scope: stages must share one parameter structure and one activation shape —
the repeated-block regime PP is used for in practice (transformer stacks,
MLP towers). Stage params are a stacked pytree with leading dim S sharded
over ``pipe``; the heterogeneous-stage case (e.g. a CNN's shrinking
pyramid) is served by the framework's DP/TP/SP axes instead.

The schedule is plain GPipe (fill, steady state, drain): T = M + S - 1 ticks
for M microbatches over S stages. Bubble fraction (S-1)/T shrinks as M
grows; there is no interleaving — keep stages coarse.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P



_logged_schedules: set[tuple[int, int]] = set()


def log_bubble_fraction(S: int, M: int) -> None:
    """Record the statically-known GPipe bubble at step-build (trace) time:
    of the T = M + S - 1 schedule ticks, S - 1 are fill/drain — every stage
    idles for exactly that fraction of the step regardless of how fast the
    hardware runs. Emitted once per distinct (S, M) as a kind="pp_bubble"
    jsonlog record plus a rank-0 log line, so an operator sees the
    schedule-inherent ceiling next to the measured step time instead of
    hunting it in a trace (PERF.md "Pipeline bubble accounting")."""
    key = (int(S), int(M))
    if key in _logged_schedules:
        return
    _logged_schedules.add(key)
    T = M + S - 1
    bubble = (S - 1) / T
    from distribuuuu_tpu.utils.jsonlog import metrics_log

    metrics_log(
        "pp_bubble", stages=int(S), microbatches=int(M), ticks=int(T),
        bubble=round(bubble, 4),
    )
    if jax.process_index() == 0:
        from distribuuuu_tpu.utils.logger import get_logger

        get_logger().info(
            "PP schedule: %d stages × %d microbatches = %d ticks; "
            "statically-known bubble fraction (S-1)/(M+S-1) = %.3f "
            "(raise MESH.MICROBATCH to amortize fill/drain)",
            S, M, T, bubble,
        )


def stack_stage_params(param_list):
    """Stack per-stage param pytrees (same structure) into one pytree with a
    leading stage dim — shard that dim over ``pipe``."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *param_list)


def stage_params_sharding(mesh, stacked):
    """NamedSharding pinning the leading (stage) dim to the pipe axis."""
    return jax.tree.map(
        lambda x: NamedSharding(
            mesh, P("pipe", *([None] * (np.ndim(x) - 1)))
        ),
        stacked,
    )


def pipeline_apply(
    stage_fn: Callable,
    stacked_params,
    microbatches: jax.Array,
    *,
    axis: str = "pipe",
    stage_aux: bool = False,
):
    """Run the GPipe schedule. Call INSIDE shard_map/jit with ``axis`` bound.

    Args:
      stage_fn: ``(params_for_one_stage, x) -> y`` with ``y.shape == x.shape``
        (uniform activation contract; see module docstring). With
        ``stage_aux=True``: ``(params, x) -> (y, aux)`` where ``aux`` is a
        small pytree of per-application statistics (fixed structure/shapes).
      stacked_params: per-device slice of the stacked stage params — inside
        shard_map each device sees leading dim 1: its own stage's params.
      microbatches: ``[M, mb, ...]`` input microbatches (replicated over the
        pipe axis; only stage 0 reads them).
    Returns:
      ``[M, mb, ...]`` outputs of the LAST stage, valid on every device
      (broadcast via psum so the loss can be computed anywhere). With
      ``stage_aux=True``: ``(outputs, aux_mean)`` where ``aux_mean`` is THIS
      device's stage aux averaged over its M valid applications — fill/drain
      ticks, whose stage inputs are schedule garbage, are masked out of the
      accumulation (VERDICT r3 #2: the MoE balancing stats ride this
      channel; gradients flow through the scan carry, so an aux-derived
      loss term trains correctly through the pipeline).
    """
    S = jax.lax.axis_size(axis)
    s = jax.lax.axis_index(axis)
    M = microbatches.shape[0]
    T = M + S - 1
    log_bubble_fraction(S, M)  # static schedule cost, once per (S, M)
    my_params = jax.tree.map(lambda x: x[0], stacked_params)
    mb_shape = microbatches.shape[1:]

    perm = [(i, (i + 1) % S) for i in range(S)]  # stage i → i+1 ring

    if stage_aux:
        aux_shapes = jax.eval_shape(
            lambda p, x: stage_fn(p, x)[1],
            my_params, jax.ShapeDtypeStruct(mb_shape, microbatches.dtype),
        )
        aux_zero = jax.tree.map(
            lambda a: jnp.zeros(a.shape, a.dtype), aux_shapes
        )

    def tick(carry, t):
        if stage_aux:
            incoming, outputs, aux_acc = carry
        else:
            incoming, outputs = carry
        # stage 0 consumes microbatch t (clamped into range during drain);
        # other stages consume what arrived from the previous stage
        mb_idx = jnp.clip(t, 0, M - 1)
        x0 = jax.lax.dynamic_index_in_dim(
            microbatches, mb_idx, axis=0, keepdims=False
        )
        x = jnp.where(s == 0, x0, incoming)
        # attribution scopes: stage compute vs the ppermute hop land
        # named in HLO op metadata, so a device trace splits pipeline
        # compute from the stage→stage+1 communication (trace_report)
        if stage_aux:
            with jax.named_scope("pp_stage"):
                y, aux = stage_fn(my_params, x)
            # stage s processes microbatch t−s at tick t; anything else
            # (fill for s>t, drain re-runs on clamped inputs) is schedule
            # garbage and must not pollute the statistics
            aux_valid = jnp.logical_and(t >= s, t - s < M)
            aux_acc = jax.tree.map(
                lambda acc, a: acc + jnp.where(aux_valid, a, 0).astype(acc.dtype),
                aux_acc, aux,
            )
        else:
            with jax.named_scope("pp_stage"):
                y = stage_fn(my_params, x)
        # the last stage finished microbatch t-(S-1) at this tick
        out_idx = t - (S - 1)
        valid = jnp.logical_and(s == S - 1, out_idx >= 0)
        outputs = jax.lax.cond(
            valid,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, y, jnp.clip(out_idx, 0, M - 1), axis=0
            ),
            lambda o: o,
            outputs,
        )
        # hop to the next stage (the wrap S-1 → 0 carries garbage that stage
        # 0 never reads — it always selects the microbatch path)
        with jax.named_scope("pp_hop"):
            incoming = jax.lax.ppermute(y, axis, perm)
        if stage_aux:
            return (incoming, outputs, aux_acc), None
        return (incoming, outputs), None

    init = (
        jnp.zeros(mb_shape, microbatches.dtype),
        jnp.zeros((M,) + mb_shape, microbatches.dtype),
    )
    if stage_aux:
        init = init + (aux_zero,)
        (_, outputs, aux_acc), _ = jax.lax.scan(tick, init, jnp.arange(T))
    else:
        (_, outputs), _ = jax.lax.scan(tick, init, jnp.arange(T))

    # broadcast last-stage outputs to every pipe rank so downstream loss /
    # metrics code is position-independent
    with jax.named_scope("pp_gather_out"):
        outputs = jnp.where(s == S - 1, outputs, jnp.zeros_like(outputs))
        outputs = jax.lax.psum(outputs, axis)
    if stage_aux:
        return outputs, jax.tree.map(lambda a: a / M, aux_acc)
    return outputs


def pipelined(
    stage_fn: Callable,
    *,
    mesh,
    num_microbatches: int,
    axis: str = "pipe",
    data_axis: str | None = "data",
    stage_aux: bool = False,
    param_specs=None,
):
    """Wrap ``stage_fn`` into ``fn(stacked_params, batch) -> outputs`` that
    runs the pipeline over ``mesh`` under jit (shard_map inside).

    ``batch`` is ``[B, ...]`` (global); it is split into ``num_microbatches``
    equal microbatches. When ``data_axis`` is present in the mesh the batch
    dim is additionally sharded over it (PP × DP composition).

    ``param_specs``: optional pytree of ``PartitionSpec``s (same structure
    as the stacked params) replacing the default ``P(axis)`` — lets the
    caller split selected param dims over OTHER mesh axes at shard_map
    entry instead of replicating them per device (PP×EP expert tensors:
    ``P('pipe', 'model', ...)`` keeps per-device expert memory at O(E/n);
    ADVICE r3 #1). The stage_fn must expect the per-device local shards.

    ``stage_aux=True``: ``stage_fn`` returns ``(y, aux)`` and the wrapped
    function returns ``(outputs, aux_stacked)`` where each ``aux`` leaf
    gains a leading stage dim ``[S, ...]`` and holds that stage's statistic
    averaged over ALL the microbatches it processed — pmean'd over the data
    axis, so token-mean statistics equal the flat (non-pipelined) model's
    full-batch values exactly (see ops/moe.balance_stats). Replicated on
    every device.
    """
    S = mesh.shape[axis]
    M = num_microbatches

    data_sharded = bool(data_axis) and mesh.shape.get(data_axis, 1) > 1

    def per_device(stacked_params, batch):
        mb = batch.reshape((M, batch.shape[0] // M) + batch.shape[1:])
        if not stage_aux:
            return pipeline_apply(stage_fn, stacked_params, mb, axis=axis)
        out, aux = pipeline_apply(
            stage_fn, stacked_params, mb, axis=axis, stage_aux=True
        )
        if data_sharded:
            # each data shard accumulated stats over its own tokens; the
            # microbatch/shard token counts are equal, so the pmean IS the
            # full-batch token mean
            aux = jax.tree.map(
                lambda a: jax.lax.pmean(a, data_axis), aux
            )
        # stage s holds only its own stats — gather the stage dim so every
        # device returns the full [S, ...] (replicated ⇒ out_spec P())
        aux = jax.tree.map(lambda a: jax.lax.all_gather(a, axis), aux)
        return out, aux

    batch_spec = P(data_axis) if data_sharded else P()
    # per-device output is [M, mb, ...]: microbatch index replicated, the
    # per-microbatch batch dim sharded over data (when present)
    out_spec = P(None, data_axis) if data_sharded else P()

    fn = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(param_specs if param_specs is not None else P(axis),
                  batch_spec),
        out_specs=(out_spec, P()) if stage_aux else out_spec,
        check_vma=False,
    )

    def apply(stacked_params, batch):
        res = fn(stacked_params, batch)
        out = res[0] if stage_aux else res  # [M, mb_global, ...]
        if data_sharded:
            # each data shard microbatched its OWN contiguous slice of the
            # batch, so the gathered dim 1 is [dp × mb]; restore the original
            # row order (shard-major) before flattening
            dp = mesh.shape[data_axis]
            out = out.reshape((M, dp, -1) + out.shape[2:])
            out = jnp.moveaxis(out, 1, 0)
        out = out.reshape((-1,) + out.shape[out.ndim - (batch.ndim - 1):])
        return (out, res[1]) if stage_aux else out

    apply.num_stages = S
    apply.num_microbatches = M
    return apply
