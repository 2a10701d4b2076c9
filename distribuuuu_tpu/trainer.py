"""Trainer: mesh-data-parallel training and evaluation.

Capability mirror of the reference trainer (ref: /root/reference/distribuuuu/
trainer.py): ``train_model`` / ``test_model`` orchestration, per-epoch LR,
cross-replica metrics, best-tracking, epoch checkpoints with auto-resume.

TPU-first redesign of the hot loop (ref call stack: SURVEY.md §3.1):
  - One jitted ``train_step`` holds forward, loss, backward, optimizer
    update, and metric computation. The global batch is sharded over the
    ``data`` mesh axis and params are replicated, so XLA compiles the
    gradient allreduce into the step (the DDP-bucket/NCCL path,
    ref: trainer.py:134, disappears into the compiled program and rides ICI).
  - BN stats are computed over the global batch in-graph — SyncBatchNorm
    (ref: trainer.py:131) by construction.
  - Metrics are global means computed in-graph; the host fetches them at
    PRINT_FREQ instead of the reference's `.item()` + extra allreduce every
    step (ref perf hazard: trainer.py:51-55), so steps dispatch
    asynchronously back-to-back.
  - The ragged final eval batch is masked in-graph instead of silently
    double-counting DistributedSampler padding.
"""

from __future__ import annotations

import functools
import os
import time
from typing import Any

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

from distribuuuu_tpu import models
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu.data import (
    construct_train_loader,
    construct_val_loader,
    device_prefetch,
)
from distribuuuu_tpu.models.layers import head_dtype, resolve_dtype
from distribuuuu_tpu.parallel import (
    mesh as mesh_lib,
    sharding as sharding_lib,
    tp,
)
from distribuuuu_tpu.parallel.partition import (
    lowering as partition_lowering,
    specs as partition_specs,
    topology as partition_topology,
)
# The step builders and TrainState live in the partition lowering
# (parallel/partition/lowering.py) — ONE step body for every topology;
# re-exported here so the long-standing call sites (tests, tools, serve)
# keep their spelling.
from distribuuuu_tpu.parallel.partition.lowering import (  # noqa: F401
    TrainState,
    make_eval_step,
    make_train_step,
)
from distribuuuu_tpu import asyncplane
from distribuuuu_tpu.asyncplane import compile_cache, sequencer
from distribuuuu_tpu.resilience import manifest as manifest_lib, supervisor
from distribuuuu_tpu import telemetry
from distribuuuu_tpu.telemetry import (
    costmodel,
    runtime as telemetry_runtime,
    spans as telemetry_spans,
)
from distribuuuu_tpu.utils import checkpoint as ckpt
from distribuuuu_tpu.utils import faults
from distribuuuu_tpu.utils import preempt
from distribuuuu_tpu.utils.jsonlog import (
    metrics_log,
    setup_metrics_log,
    timeline_log,
)
from distribuuuu_tpu.utils.logger import get_logger, setup_logger
from distribuuuu_tpu.utils.meters import AverageMeter, construct_meters
from distribuuuu_tpu.utils.metrics import count_parameters
from distribuuuu_tpu.utils.optim import construct_optimizer, set_lr
from distribuuuu_tpu.utils.schedules import get_epoch_lr
from distribuuuu_tpu.utils.seed import setup_env, setup_seed


def check_trainer_mesh():
    """Validate the configured MESH stanza BEFORE any expensive
    init/compile.

    Delegates to the partition-layer topology registry
    (parallel/partition/topology.py): one capability table serves the
    trainer, the dryrun sweep, and the YAML stanza gate, and its errors
    are capability-derived — a stanza is refused because a named rule is
    broken, never because a code path happens to be missing. Compositions
    the old scattered refusals blocked without cause (ZeRO-3 under PP; a
    dp×tp×ep mesh) now validate and lower.
    """
    supervisor.validate_policy(cfg.TRAIN.NONFINITE)
    return partition_topology.from_cfg(cfg)


def bn_group_from_cfg() -> int:
    """BN statistic regime from the config (honors ``MODEL.SYNCBN``,
    ref: trainer.py:131 + config.py:14). ``SYNCBN True`` ⇒ 0 = global-batch
    stats (SyncBatchNorm). ``False`` (the reference default for every
    published baseline) ⇒ ghost groups of ``MODEL.BN_GROUP`` samples,
    defaulting to ``TRAIN.BATCH_SIZE`` — the reference's per-GPU BN batch."""
    if cfg.MODEL.SYNCBN:
        return 0
    return cfg.MODEL.BN_GROUP or cfg.TRAIN.BATCH_SIZE


def build_model_from_cfg(topology=None):
    """Build the configured arch (≙ models.build_model + timm fallback,
    ref: trainer.py:117-128 — the zoo here is closed, no fallback needed).

    Mesh-dependent construction (ring attention, pipeline stages, MoE
    axis/mesh threading) reads the RESOLVED topology
    (parallel/partition/topology.py) rather than raw ``cfg.MESH``
    integers, so ``-1`` wildcards and the dedicated ``expert`` axis
    resolve identically here and in the lowering."""
    if topology is None:
        topology = partition_topology.from_cfg(cfg)
    kwargs = dict(
        num_classes=cfg.MODEL.NUM_CLASSES,
        dtype=resolve_dtype(cfg.DEVICE.COMPUTE_DTYPE),
    )
    arch_traits = models.traits(cfg.MODEL.ARCH)
    if arch_traits.batch_norm:
        # every CNN arch in the zoo normalizes with BN (the transformer
        # families declare that they do not: models/traits.py)
        kwargs["bn_group"] = bn_group_from_cfg()
    if arch_traits.kwargs_from_cfg is not None:
        kwargs.update(arch_traits.kwargs_from_cfg(cfg, topology))
    if cfg.MODEL.ARCH.startswith(
        ("resnet", "resnext", "wide_resnet", "botnet", "densenet")
    ):
        kwargs["s2d_stem"] = cfg.DEVICE.S2D_STEM
    if cfg.MODEL.ARCH.startswith(("resnet", "resnext", "wide_resnet")):
        # remat-for-traffic on the bus-bound step (PERF.md roofline):
        # recompute stage 1-2 block activations in the backward instead of
        # storing them (models/resnet.py). Exact same math.
        kwargs["remat"] = bool(cfg.TRAIN.REMAT)
    elif cfg.TRAIN.REMAT:
        raise ValueError(
            f"TRAIN.REMAT targets the resnet/resnext/wide_resnet family "
            f"(stages 1-2 rematerialization); {cfg.MODEL.ARCH!r} does not "
            "take the knob (densenet always remats its dense layers) — "
            "refusing rather than silently measuring an unchanged step"
        )
    if cfg.MODEL.ARCH == "botnet50":
        # the attention grid follows the input size; each stride-2 op maps
        # n → ceil(n/2), so the stride-16 backbone gives ceil(IM_SIZE/16).
        # The reference instead hard-asserts 224 inputs (ref: botnet.py:270-271)
        fmap = max(1, -(-cfg.TRAIN.IM_SIZE // 16))
        kwargs["fmap_size"] = (fmap, fmap)
        kwargs["attn_impl"] = cfg.DEVICE.ATTN_IMPL
    if cfg.MODEL.ARCH.startswith("gpt"):
        # decoder-only LM (models/gpt.py): token batches, causal attention,
        # context length from LM.SEQ_LEN, vocab = MODEL.NUM_CLASSES (the
        # tokenizer's size — token-shard manifests are checked against it).
        # Same MoE knob plumbing as the ViT family; the partition layer
        # places everything from the LM spec-table rules + annotations.
        kwargs["seq_len"] = int(cfg.LM.SEQ_LEN)
        if topology.seq > 1:
            # sequence-sharded causal LM (ISSUE 19): causal ring attention
            # over the seq axis — the exact ViT wiring (the blocks are
            # shared modules), with the token dim of every batch leaf
            # declared over ``seq`` (specs.TOKEN_BATCH_TABLE). The ring
            # shard_map splits the token dim into EQUAL blocks; an uneven
            # dim would silently rest replicated on this jax line, so the
            # divisibility refusals carry the arithmetic.
            if int(cfg.LM.SEQ_LEN) % topology.seq:
                raise ValueError(
                    f"MESH.SEQ={topology.seq} does not divide LM.SEQ_LEN="
                    f"{int(cfg.LM.SEQ_LEN)} ({int(cfg.LM.SEQ_LEN)} % "
                    f"{topology.seq} = "
                    f"{int(cfg.LM.SEQ_LEN) % topology.seq}) — the causal "
                    "ring rotates equal K/V blocks per seq rank; use an "
                    "LM.SEQ_LEN that is a multiple of MESH.SEQ (e.g. "
                    f"{-(-int(cfg.LM.SEQ_LEN) // topology.seq) * topology.seq}"
                    ") or a smaller seq axis"
                )
            impl = (
                "ulysses" if cfg.DEVICE.ATTN_IMPL == "ulysses" else "ring"
            )
            kwargs["attn_impl"] = impl
            kwargs["mesh"] = mesh_lib.mesh_from_cfg(cfg)
        elif cfg.DEVICE.ATTN_IMPL in ("flash", "blockwise"):
            kwargs["attn_impl"] = cfg.DEVICE.ATTN_IMPL
        elif cfg.DEVICE.ATTN_IMPL in ("ring", "ulysses"):
            raise ValueError(
                f"DEVICE.ATTN_IMPL={cfg.DEVICE.ATTN_IMPL!r} needs a "
                "sequence-sharded mesh: set MESH.SEQ > 1"
            )
        elif cfg.DEVICE.ATTN_IMPL not in ("auto", "xla"):
            raise ValueError(
                f"DEVICE.ATTN_IMPL={cfg.DEVICE.ATTN_IMPL!r}: gpt archs "
                "accept 'auto'/'xla' (dense causal), 'flash', "
                "'blockwise', or MESH.SEQ>1 for ring/ulysses "
                "sequence-sharded attention"
            )
        if cfg.MODEL.ARCH.endswith("_moe"):
            kwargs["moe_experts"] = cfg.MODEL.MOE.NUM_EXPERTS
            kwargs["moe_top_k"] = cfg.MODEL.MOE.TOP_K
            kwargs["moe_every"] = cfg.MODEL.MOE.EVERY
            kwargs["moe_impl"] = cfg.MODEL.MOE.IMPL
            kwargs["moe_capacity_factor"] = cfg.MODEL.MOE.CAPACITY_FACTOR
            kwargs["moe_axis"] = topology.moe_axis()
            if topology.expert > 1 or topology.model > 1:
                kwargs["mesh"] = mesh_lib.mesh_from_cfg(cfg)
    if cfg.MODEL.ARCH.startswith("vit"):
        # seq axis populated means sequence-sharded attention: route
        # through ring attention over the seq axis. On a single chip,
        # DEVICE.ATTN_IMPL=blockwise selects O(L·chunk)-memory exact
        # attention (ops.ring_attention.blockwise_attention) for
        # high-resolution inputs. Dense XLA attention otherwise.
        if topology.seq > 1:
            kwargs["attn_impl"] = "ring"
            kwargs["mesh"] = mesh_lib.mesh_from_cfg(cfg)
        elif cfg.DEVICE.ATTN_IMPL in ("blockwise", "flash"):
            kwargs["attn_impl"] = cfg.DEVICE.ATTN_IMPL
        elif cfg.DEVICE.ATTN_IMPL == "auto":
            # per-shape resolution at trace time (models/vit.Attention):
            # Pallas flash kernel for long sequences on TPU, dense XLA below
            kwargs["attn_impl"] = "auto"
        elif cfg.DEVICE.ATTN_IMPL in ("ring", "ulysses"):
            raise ValueError(
                f"DEVICE.ATTN_IMPL={cfg.DEVICE.ATTN_IMPL!r} needs a "
                "sequence-sharded mesh: set MESH.SEQ > 1"
            )
        elif cfg.DEVICE.ATTN_IMPL != "xla":
            raise ValueError(
                f"DEVICE.ATTN_IMPL={cfg.DEVICE.ATTN_IMPL!r}: ViT archs "
                "accept 'auto', 'xla' (dense), 'flash' (Pallas kernel), "
                "'blockwise', or MESH.SEQ>1 for ring attention"
            )
        if topology.pipe > 1:
            # GPipe pipeline over the pipe axis (models/vit.PipelinedViT)
            kwargs["pipe_stages"] = topology.pipe
            kwargs["pipe_microbatches"] = cfg.MESH.MICROBATCH
            kwargs["mesh"] = mesh_lib.mesh_from_cfg(cfg)
        if cfg.MODEL.ARCH.endswith("_moe"):
            # expert parallelism: tensors/dispatch ride the dedicated
            # ``expert`` axis when MESH.EXPERT > 1 (composes with TP on a
            # 3-axis dp×tp×ep mesh), the ``model`` axis otherwise (the
            # legacy layout — EP and TP time-share one axis)
            kwargs["moe_experts"] = cfg.MODEL.MOE.NUM_EXPERTS
            kwargs["moe_top_k"] = cfg.MODEL.MOE.TOP_K
            kwargs["moe_every"] = cfg.MODEL.MOE.EVERY
            kwargs["moe_impl"] = cfg.MODEL.MOE.IMPL
            kwargs["moe_capacity_factor"] = cfg.MODEL.MOE.CAPACITY_FACTOR
            kwargs["moe_axis"] = topology.moe_axis()
            if topology.expert > 1 or topology.model > 1:
                kwargs["mesh"] = mesh_lib.mesh_from_cfg(cfg)
    model = models.build_model(cfg.MODEL.ARCH, **kwargs)
    if (
        topology.seq > 1
        and kwargs.get("attn_impl") == "ulysses"
        and int(getattr(model, "num_heads", 0)) % topology.seq
    ):
        heads = int(model.num_heads)
        raise ValueError(
            f"MESH.SEQ={topology.seq} does not divide num_heads={heads} "
            f"({heads} % {topology.seq} = {heads % topology.seq}) for "
            "DEVICE.ATTN_IMPL='ulysses' — the all-to-all re-shards "
            "sequence to heads, so each seq rank needs an equal head "
            "slice; use ring attention (the sp default) or an arch whose "
            "head count MESH.SEQ divides"
        )
    return model


@telemetry_spans.setup_timer("init_state")
def create_train_state(model, key, mesh, im_size: int, layout=None) -> TrainState:
    """Initialize params/stats/optimizer laid out over the mesh.

    Params are placed by their ``nn.with_partitioning`` metadata: replicated
    by default (≙ DDP's init broadcast, ref: trainer.py:134) and sharded over
    the ``model`` axis where a kernel is annotated (tensor parallelism —
    collapses to replication at MESH.MODEL=1). The optimizer's momentum
    buffers inherit the param layout through GSPMD propagation. With
    ``MESH.ZERO`` on, optimizer state (and at stage 3 the params) rest in
    the ZeRO layout instead. ``layout`` accepts a precomputed
    ``_state_layout`` result so callers that also need it for the train
    step don't trace the abstract init twice.
    """
    shardings = layout or _state_layout(model, mesh, im_size)
    optimizer = construct_optimizer()
    repl = sharding_lib.replicate(mesh)
    # the model's declared init dummy (token models declare their own —
    # models/gpt.py dummy_input; image models get the standard image dummy)
    dummy = partition_specs.model_dummy_input(model, im_size)

    def init_all(key):
        variables = flax.linen.meta.unbox(model.init(key, dummy, train=False))
        params = jax.lax.with_sharding_constraint(
            variables["params"], shardings["params"]
        )
        # stats-free models (e.g. ViT — LayerNorm only) have no batch_stats
        bs = variables.get("batch_stats", {})
        stats = jax.lax.with_sharding_constraint(
            bs, jax.tree.map(lambda _: repl, bs)
        )
        opt_state = tp.constrain_like(
            optimizer.init(params), params, shardings["opt"]
        )
        return TrainState(
            params=params,
            batch_stats=stats,
            opt_state=opt_state,
            step=jnp.int32(0),
            key=key,
        )

    return jax.jit(init_all)(key)


def _state_layout(model, mesh, im_size: int) -> dict:
    """Resolved NamedSharding trees for the configured layout regime:
    ``{"params", "opt", "grads"}`` — param-shaped trees, from the
    partition spec layer (parallel/partition/specs.state_layout: base
    declarations + the ZeRO transform per ``cfg.MESH.ZERO``, every
    derived leaf spec validated before GSPMD sees it)."""
    return partition_specs.state_layout(model, mesh, im_size, cfg.MESH.ZERO)


def effective_topk() -> int:
    """TOPK clamped to the class count, so 'Acc@k' labels match the math."""
    return min(cfg.TRAIN.TOPK, cfg.MODEL.NUM_CLASSES)


class _ProfilerWindow:
    """jax.profiler capture over steps [START, START+NUM) of the first
    *executed* epoch (auto-resumed runs profile their first epoch too)."""

    def __init__(self, epoch: int, first_epoch: int):
        self.active = False
        self.started = False
        self.enabled = (
            cfg.PROF.ENABLED and epoch == first_epoch and mesh_lib.is_primary()
        )
        if self.enabled and cfg.PROF.NUM_STEPS < 1:
            get_logger().warning(
                "PROF.NUM_STEPS=%d < 1; profiling disabled", cfg.PROF.NUM_STEPS
            )
            self.enabled = False
        if self.enabled:
            import os

            self.trace_dir = cfg.PROF.DIR or os.path.join(cfg.OUT_DIR, "profile")
            self.first = cfg.PROF.START_STEP
            self.last = cfg.PROF.START_STEP + cfg.PROF.NUM_STEPS

    def begin(self, it):
        # >= not ==: a resumed epoch starts at its restored cursor, so the
        # window opens at the first step at/after START_STEP
        if self.enabled and not self.started and it >= self.first:
            # the Python tracer off (under the default one every call of the
            # loop is an event); the host tracer keeps ``dtpu.*``. Necessary,
            # not sufficient: PJRT lays a ``uint8`` NHWC batch out for the
            # device tile by tile on the host, ~400,000 host events a batch of
            # the annotations' own level, so a capture of a host-fed epoch runs
            # about five times slower than the epoch whatever the options
            # (PERF.md section 6, PR 35). Read device kernels off it; read the
            # loop's shares off the ``trainer.*`` counters, which need none
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=options)
            self.active = self.started = True

    def _stop(self, state):
        # drain the async dispatch queue so the trace holds real device work
        jax.block_until_ready(state.params)
        jax.profiler.stop_trace()
        self.active = False
        get_logger().info("profiler trace written to %s", self.trace_dir)

    def end(self, it, state):
        # >= not ==: close at the first step covering the window end
        if self.active and it + 1 >= self.last:
            self._stop(state)

    def finish(self, state):
        """Epoch ended before the window did — close the trace anyway, and
        diagnose a window that never started (START_STEP past the epoch)."""
        if self.active:
            get_logger().warning(
                "profiler window truncated by epoch end (wanted steps "
                "[%d, %d))", self.first, self.last,
            )
            self._stop(state)
        elif self.enabled and not self.started:
            get_logger().warning(
                "profiler never started: PROF.START_STEP=%d not reached "
                "(epoch has fewer batches?) — no trace written", self.first,
            )


def _emit_batch_spans(phase: str, epoch: int, batch: int, tl: dict) -> None:
    """Per-rank wait/h2d/step spans for one dispatched batch, from the
    stage stamps the loop already measured (telemetry/spans.py — the
    write happens AFTER every measured interval closed, so telemetry
    never sits inside its own numbers). Unlike the primary-only
    ``kind="timeline"`` records, these land in EVERY rank's sink: the
    cross-rank step percentiles and straggler skew in
    tools/run_report.py come from exactly these spans."""
    attrs = {"phase": phase, "epoch": epoch, "batch": batch,
             "parent": "epoch", "depth": 1}
    if "get0" in tl and "get1" in tl:
        telemetry_spans.emit_span(
            "wait", tl["get0"], tl["get1"], track="pipeline", **attrs
        )
    if "put0" in tl and "put1" in tl:
        telemetry_spans.emit_span(
            "h2d", tl["put0"], tl["put1"], track="pipeline", **attrs
        )
    if "step0" in tl and "step1" in tl:
        telemetry_spans.emit_span(
            "step", tl["step0"], tl["step1"], track="pipeline",
            n=tl.get("n", 0), **attrs,
        )


def _step_spans_on() -> bool:
    return telemetry_spans.enabled() and cfg.TELEMETRY.STEP_SPANS


def _capture_step_cost(step_fn, state, batch, *, label: str,
                       phase: str) -> None:
    """XLA cost-model ledger for one step program (telemetry/costmodel.py):
    at the FIRST dispatch — state not yet donated, the live (state, batch)
    supply exact shapes/shardings — lower the jitted step and emit
    cost.step / cost.memory / cost.roofline records. Once per label per
    process (costmodel dedups); never raises."""
    if not (telemetry_spans.enabled() and cfg.TELEMETRY.COSTMODEL):
        return
    # every leading dim of the image leaf is batch-like: (batch, ...) /
    # (accum, micro, ...) — their product is the examples per step. Token
    # batches (the LM — integer [..., seq]) have ONE trailing payload dim
    # instead of the image's three; "images" then counts sequences
    # (run_report's lm section multiplies by seq len for tokens/s).
    img = batch["image"]
    lead = (
        img.shape[:-1]
        if jnp.issubdtype(img.dtype, jnp.integer)
        else img.shape[:-3]
    )
    images = 1
    for d in lead:
        images *= int(d)
    costmodel.capture_step(
        step_fn, (state, batch), label=label, phase=phase,
        images=max(1, images), arch=cfg.MODEL.ARCH,
        with_memory=cfg.TELEMETRY.COSTMODEL_MEMORY,
    )


def train_epoch(loader, mesh, state, train_step, epoch: int, logger,
                first_epoch: int = 0):
    """One epoch of the hot loop (ref: trainer.py:14-64).

    Returns ``(state, interrupted, batches_done)``: with
    ``TRAIN.PREEMPT_SAVE`` on, a SIGTERM (utils/preempt.py) ends the epoch
    at the next dispatch boundary with ``interrupted=True`` so the caller
    can write the mid-epoch checkpoint; ``batches_done`` is the absolute
    batch cursor (counting any resume-skipped prefix), which the shards
    pipeline persists for exact mid-epoch resume.

    When the loader was armed by ``load_state_dict`` (a restored shards
    cursor for THIS epoch), iteration skips the already-trained prefix —
    the epoch continues at the exact next batch instead of re-running.
    """
    lr = get_epoch_lr(epoch)
    set_lr(state.opt_state, lr)  # epoch-granular LR (ref: trainer.py:25-26)
    loader.set_epoch(epoch)  # reshuffle shards (ref: trainer.py:33)
    num_batches = len(loader)
    # exact mid-epoch resume (DATA.FORMAT=shards): batches [0, start) were
    # consumed and trained by the preempted run — continue, don't re-run
    start_batch = getattr(loader, "resume_skip", lambda e: 0)(epoch)
    if start_batch and mesh_lib.is_primary():
        logger.info(
            "exact mid-epoch resume: continuing epoch %d at batch %d/%d "
            "(restored global cursor)",
            epoch + 1, start_batch + 1, num_batches,
        )
    watch_preemption = cfg.TRAIN.PREEMPT_SAVE
    interrupted = False
    # multi-host: the cross-host flag agreement is a blocking collective,
    # so run it only every Nth window (deterministic sites — every process
    # reaches the same ones, exit stays agreed). Single-process reads the
    # local bool — free, so check every window.
    preempt_check_every = 1 if jax.process_count() == 1 else 8
    windows_seen = 0
    accum = max(1, cfg.TRAIN.GRAD_ACCUM_STEPS)

    # the loop's counts (PERF.md "spans, counters and scopes"): always
    # counted, like ``setup.*`` and ``jit.*``, from the stamps the loop takes
    # anyway; the train loop's alone (``validate`` counts nothing)
    count = telemetry.get_registry().counter
    n_steps, n_epochs = count("trainer.steps"), count("trainer.epochs")
    wait_s, h2d_s = count("trainer.wait_s"), count("trainer.h2d_s")
    h2d_bytes, fetch_s = count("trainer.h2d_bytes"), count("trainer.fetch_s")

    def put_batch(hb):
        h2d_bytes.inc(sum(getattr(v, "nbytes", 0) for v in hb.values()))
        if accum > 1:
            return sharding_lib.shard_micro_batch(mesh, hb, accum)
        return sharding_lib.shard_batch(mesh, hb)

    batch_time, data_time, losses, top1, topk_m, progress = construct_meters(
        num_batches, f"Epoch[{epoch + 1}/{cfg.OPTIM.MAX_EPOCH}]", effective_topk()
    )
    prof = _ProfilerWindow(epoch, first_epoch)
    pending = []  # each step's device metrics, awaiting the async fetch
    done = start_batch  # absolute batches dispatched (incl. skipped prefix)

    # dispatch-MoE only: fraction of routed assignments lost to capacity
    moe_dropped = AverageMeter("MoEDrop", ":.4f")

    # non-finite policy enforcement at flush granularity (the guard inside
    # the step already annotated/skipped in-graph; this is the host half —
    # count+log for "skip", raise for "raise"/"rollback")
    nf_mon = supervisor.NonFiniteMonitor(
        str(cfg.TRAIN.NONFINITE), epoch, logger
    )
    # stall watchdog: a wedged collective or hung storage flags instead of
    # hanging silently (TRAIN.STALL_TIMEOUT seconds; 0 = no thread)
    heartbeat = supervisor.Heartbeat(cfg.TRAIN.STALL_TIMEOUT, logger)

    def flush_pending():
        if not pending:
            return
        # the float() reads below are the loop's only fence on the device:
        # device-idle gaps under this span are the print interval's price
        fetch0 = time.perf_counter()
        with telemetry_spans.span("metrics_fetch", track="pipeline"):
            for m in pending:
                if nf_mon.observe(
                    float(m["loss"]), float(m.get("nonfinite", 0.0)), done
                ):
                    continue  # skipped in-graph — keep it out of the meters
                losses.update(float(m["loss"]))
                top1.update(float(m["top1"]))
                topk_m.update(float(m["topk"]))
                if "moe_dropped" in m:
                    moe_dropped.update(float(m["moe_dropped"]))
        fetch_s.inc(time.perf_counter() - fetch0)
        pending.clear()

    def maybe_print():
        if done % cfg.TRAIN.PRINT_FREQ == 0 or done == num_batches:
            flush_pending()
            if mesh_lib.is_primary():
                eta = progress.get_eta(
                    done,
                    (num_batches - done)
                    + (cfg.OPTIM.MAX_EPOCH - epoch - 1) * num_batches,
                )
                logger.info("%s  LR %.5f  ETA %s", progress.display(done), lr, eta)
                extra = (
                    {"moe_dropped": moe_dropped.avg} if moe_dropped.count else {}
                )
                metrics_log(
                    "train", epoch=epoch + 1, batch=done, loss=losses.avg,
                    top1=top1.avg, topk=topk_m.avg, lr=lr,
                    batch_time=batch_time.avg, data_time=data_time.avg,
                    **extra,
                )

    def preempt_break(batches_done: int) -> bool:
        """Preemption check at window granularity: requested_global() makes
        every process agree on the exit boundary (the save is collective).
        A COMPLETED epoch never reports interrupted — it falls through to
        the normal validate/save path (re-running a fully-trained epoch
        from its own end state would double-train it)."""
        nonlocal windows_seen, interrupted
        windows_seen += 1
        if (
            watch_preemption
            and batches_done < num_batches
            and windows_seen % preempt_check_every == 0
            and preempt.requested_global()
        ):
            flush_pending()
            if mesh_lib.is_primary():
                logger.warning(
                    "preemption signaled — leaving epoch %d at batch %d/%d",
                    epoch + 1, batches_done, num_batches,
                )
            interrupted = True
            return True
        return False

    emit_timeline = cfg.TRAIN.TIMELINE and mesh_lib.is_primary()
    emit_spans = _step_spans_on()
    try:
        # ``epoch`` holds the whole dispatch loop: wait, h2d, step and
        # metrics_fetch are its children on this thread, so its SELF time in
        # a capture is the loop's own host work (heartbeat, fault hooks, cost
        # capture, meters, log lines). A track of its own in the JSONL sink:
        # run_report and live.py take the pipeline track's first start and
        # last end for a window's wall, which an epoch-long record would
        # stretch back to the epoch's start
        with telemetry_spans.span(
            "epoch", track="epoch", epoch=epoch + 1, phase="train"
        ):
            # Per-step dispatch through the device-side prefetch ring
            # (data/loader.device_prefetch): the H2D transfer of batches
            # it+1..it+depth is dispatched while the step for batch `it` runs,
            # so transfer never serializes behind the step; depth 0 restores
            # the serial put-then-step order. Results are value-bit-identical
            # at every depth (same put/step order — tests/test_overlap.py).
            # Each dispatched batch leaves one kind="timeline" record with its
            # stage-boundary timestamps (tools/overlap_report.py attributes
            # the epoch wall from them).
            depth = max(0, cfg.TRAIN.PREFETCH_DEVICE)
            end = time.perf_counter()
            for it, batch, tl in device_prefetch(loader, put_batch, depth):
                abs_it = start_batch + it  # loader skipped the resumed prefix
                heartbeat.beat(f"epoch {epoch + 1} batch {abs_it}")
                faults.maybe_stall(epoch, abs_it)  # injection no-ops (FAULTS.*)
                faults.maybe_kill(epoch, abs_it)
                faults.maybe_preempt(epoch, abs_it)
                faults.maybe_recompile(epoch, abs_it)
                faults.maybe_slowdown(epoch, abs_it)
                data_time.update(tl["get1"] - tl["get0"])
                wait_s.inc(tl["get1"] - tl["get0"])
                h2d_s.inc(tl["put1"] - tl["put0"])
                _capture_step_cost(
                    train_step, state, batch, label="train_step", phase="train"
                )
                prof.begin(abs_it)
                tl["step0"] = time.perf_counter()
                with telemetry_spans.annotate("step"):
                    state, metrics = sequencer.dispatch(
                        sequencer.TRAIN_STREAM, train_step, state, batch
                    )
                tl["step1"] = time.perf_counter()
                prof.end(abs_it, state)
                pending.append(metrics)
                done += 1
                n_steps.inc(1)
                batch_time.update(time.perf_counter() - end)
                end = time.perf_counter()
                if emit_spans:
                    _emit_batch_spans("train", epoch + 1, abs_it, tl)
                if emit_timeline:
                    timeline_log("train", epoch + 1, abs_it, tl.pop("n", 0), **tl)
                maybe_print()
                if preempt_break(done):
                    break
            prof.finish(state)
        n_epochs.inc(1)
    finally:
        heartbeat.stop()
    return state, interrupted, done


def validate(loader, mesh, state, eval_step, epoch: int, logger,
             quiet: bool = False, watch_preemption: bool | None = None):
    """Full evaluation pass; returns ``(top1, topk, loss, samples)``
    (ref: trainer.py:67-103), or ``None`` if preemption was signaled
    mid-eval (``TRAIN.PREEMPT_SAVE`` — the caller persists state and
    exits inside the grace window rather than finishing a long eval).
    Per-batch progress at TEST.PRINT_FREQ (≙ ref validate's meter display,
    trainer.py:91-95) — totals stay on device between prints so batches
    dispatch asynchronously.

    ``quiet`` suppresses every log line and the ``kind="eval"`` record —
    the concurrent-eval worker (asyncplane/evalloop.py) runs this body
    off-thread and the MAIN thread logs the summary at join time, so the
    record order matches a synchronous run. ``watch_preemption`` False
    disables the mid-eval abandon (the concurrent path must complete:
    its result is joined before any preemption exit)."""
    if watch_preemption is None:
        watch_preemption = cfg.TRAIN.PREEMPT_SAVE
    # same collective-throttle as train_epoch: cross-host agreement only at
    # every Nth deterministic site; free local check at world size 1
    preempt_check_every = 1 if jax.process_count() == 1 else 8
    checks_seen = 0
    totals = None
    pending_print = None  # previous window's (batch_idx, totals) — async copy
    num_batches = len(loader)
    # same overlap machinery as train_epoch's per-step path (VERDICT r5
    # item 5 leftover: eval had none): the device prefetch ring dispatches
    # the H2D transfer of batches it+1..it+depth while eval_step(it) runs,
    # and each batch leaves a phase="eval" timeline record. Metric totals
    # are a pure sum — overlap order cannot change them (equivalence:
    # tests/test_overlap.py).
    emit_timeline = cfg.TRAIN.TIMELINE and mesh_lib.is_primary()
    emit_spans = _step_spans_on()
    depth = max(0, cfg.TRAIN.PREFETCH_DEVICE)
    # the same span as train_epoch's, told apart by ``phase``
    with telemetry_spans.span(
        "epoch", track="epoch", epoch=epoch + 1, phase="eval"
    ):
        end = time.perf_counter()
        for it, batch, tl in device_prefetch(
            loader, functools.partial(sharding_lib.shard_batch, mesh), depth
        ):
            _capture_step_cost(
                eval_step, state, batch, label="eval_step", phase="eval"
            )
            tl["step0"] = time.perf_counter()
            # eval steps do not chain through data dependencies, so under
            # the sequencer each one is dispatched fenced (outputs ready
            # before the token releases) — the eval thread absorbs the wait,
            # the train stream never fences on eval (asyncplane/sequencer.py
            # has the dispatch-ordering story); pass-through when inactive
            with telemetry_spans.annotate("step"):
                m = sequencer.dispatch(
                    sequencer.EVAL_STREAM, eval_step, state, batch, fence=True
                )
            totals = (
                m
                if totals is None
                else jax.tree.map(jnp.add, totals, m)
            )
            tl["step1"] = time.perf_counter()
            if emit_spans:
                _emit_batch_spans("eval", epoch + 1, it, tl)
            if emit_timeline:
                timeline_log("eval", epoch + 1, it, tl.pop("n", 0), **tl)
            at_check_site = (
                watch_preemption
                and (it + 1) % cfg.TEST.PRINT_FREQ == 0
                and it + 1 < num_batches
            )
            if at_check_site:
                checks_seen += 1
            if (
                at_check_site
                and checks_seen % preempt_check_every == 0
                and preempt.requested_global()
            ):
                # deterministic check sites (same batch indices on every
                # process) — abandon the eval; the caller saves and exits
                if mesh_lib.is_primary():
                    logger.warning(
                        "preemption signaled — abandoning eval at batch %d/%d",
                        it + 1, num_batches,
                    )
                return None
            if (it + 1) % cfg.TEST.PRINT_FREQ == 0 and mesh_lib.is_primary() \
                    and not quiet:
                # async metric fetch (same treatment the train loop gives its
                # metrics): start the host copy of THIS window's totals and log
                # the PREVIOUS window's — already landed, so reading it costs
                # nothing and eval batches keep dispatching back-to-back
                # (the blocking fetch here was the last per-N-batches host sync)
                for leaf in jax.tree.leaves(totals):
                    leaf.copy_to_host_async()
                if pending_print is not None:
                    pit, ptot = pending_print
                    acc1_so_far = (
                        float(ptot["correct1"]) / max(float(ptot["count"]), 1.0) * 100.0
                    )
                    window = time.perf_counter() - end
                    logger.info(
                        "Eval[%d][%d/%d]  Time %6.3f (%.3f/batch)  "
                        "Acc@1 %.3f (through batch %d)",
                        epoch + 1, it + 1, num_batches,
                        window, window / cfg.TEST.PRINT_FREQ, acc1_so_far, pit,
                    )
                end = time.perf_counter()
                pending_print = (it + 1, totals)
    totals = jax.tree.map(float, totals)
    n = max(totals["count"], 1.0)
    top1 = totals["correct1"] / n * 100.0
    topk = totals["correctk"] / n * 100.0
    loss = totals["loss_sum"] / n
    if not quiet:
        log_eval_result(logger, epoch, top1, topk, loss, int(n))
    return top1, topk, loss, int(n)


def log_eval_result(logger, epoch: int, top1: float, topk: float,
                    loss: float, samples: int) -> None:
    """The eval summary line + ``kind="eval"`` record — split out so the
    concurrent-eval join path emits them from the main thread in the same
    order a synchronous run would."""
    if mesh_lib.is_primary():
        logger.info(
            "Eval[%d]  Loss %.4f  Acc@1 %.3f  Acc@%d %.3f  (%d samples)",
            epoch + 1, loss, top1, effective_topk(), topk, samples,
        )
        metrics_log(
            "eval", epoch=epoch + 1, loss=loss, top1=top1, topk=topk,
            samples=samples,
        )


def _place_like(tmpl, new):
    """Place restored arrays with the live template's dtype + layout
    (replicated, TP- or ZeRO-sharded), leaf by leaf.

    Host (numpy) leaves go through a plain sharded device_put on a
    single-process run; on MULTI-HOST they place collective-free through
    ``jax.make_array_from_callback`` (each process feeds its addressable
    shards from its own host copy) — a cross-process ``device_put``
    dispatches per-leaf gloo/ICI collectives whose enqueue order is not
    agreed across hosts, and two hosts mid-restore can interleave them
    (observed: gloo "op.preamble.length <= op.nbytes" aborts restoring a
    multi-host async save; the same dispatch-ordering hazard the
    sequencer removes from the train loop). Restored ``jax.Array``
    leaves that SPAN processes (multi-host ZeRO resume: orbax hands back
    arrays in their saved sharding, of which this process addresses only
    its slice) cannot be fetched to host at all — those reshard
    on-device through a jitted identity with the template's sharding as
    out_shardings (compiles to the minimal collective)."""

    def _place(t, n):
        dtype = getattr(t, "dtype", None)
        if isinstance(n, jax.Array) and not n.is_fully_addressable:
            return _reshard_fn(dtype, t.sharding)(n)
        sharding = getattr(t, "sharding", None)
        if sharding is None:
            # non-array template leaf — e.g. the python-float LR that
            # set_lr injects in place (a mid-run rollback resumes against
            # a live, already-mutated state): keep it host-side
            return np.asarray(n, dtype=dtype) if dtype is not None else n
        host = np.asarray(n, dtype=dtype)
        if not sharding.is_fully_addressable:
            return jax.make_array_from_callback(
                host.shape, sharding, lambda idx: host[idx]
            )
        return jax.device_put(host, sharding)

    return jax.tree.map(_place, tmpl, new)


@functools.lru_cache(maxsize=None)
def _reshard_fn(dtype, sharding):
    """Jitted identity-cast keyed on (dtype, target sharding) — one
    compiled reshard program per distinct layout instead of one per leaf."""
    return jax.jit(
        lambda a: a.astype(dtype) if dtype is not None else a,
        out_shardings=sharding,
    )


def _state_tree(state: TrainState) -> dict:
    # key is intentionally excluded: it is re-derived from RNG_SEED at startup
    return {
        "params": state.params,
        "batch_stats": state.batch_stats,
        "opt_state": state.opt_state,
        "step": state.step,
    }


def _restore_weights(path: str, model):
    """Weights from an orbax checkpoint dir OR a torch ``.pth`` pickle
    (reference-trained weights / URL-zoo files, ref: resnet.py:23-33,
    trainer.py:204-205). Returns {"params", "batch_stats"} numpy/jax trees."""
    from distribuuuu_tpu.utils import torch_ingest

    if torch_ingest.is_torch_checkpoint(path):
        sd = torch_ingest.load_torch_state_dict(path)
        return torch_ingest.convert_state_dict(
            sd, torch_ingest.ordered_variables(model, im_size=cfg.TRAIN.IM_SIZE)
        )
    return ckpt.load_checkpoint(path)


def _with_restored_weights(state: TrainState, path: str, model) -> TrainState:
    """State with params/batch_stats replaced from ``path`` (orbax or torch),
    placed with the live layout; optimizer state and step untouched."""
    restored = _restore_weights(path, model)
    return TrainState(
        params=_place_like(state.params, restored["params"]),
        batch_stats=_place_like(state.batch_stats, restored["batch_stats"]),
        opt_state=state.opt_state,
        step=state.step,
        key=state.key,
    )


def _resume(
    state: TrainState, mesh
) -> tuple[TrainState, int, float, int | None, dict | None]:
    """Auto-resume from the last INTACT checkpoint (ref: trainer.py:143-149,
    hardened): candidates are manifest-verified newest-first, corrupt or
    partial saves are quarantined to ``*.corrupt`` and walked past
    (utils/checkpoint.find_last_valid_checkpoint), and the recorded world
    topology is compared against the live mesh — a dp=N save restores onto
    a dp=M mesh ("elastic resume": every array is re-placed onto the live
    layout by ``_place_like``; ZeRO opt-state shards reassemble through
    ``pack_opt_state``'s canonical leaf order), while a save whose param
    tree cannot feed this model is refused with the first mismatch."""
    logger = get_logger()
    path = ckpt.find_last_valid_checkpoint()
    man = manifest_lib.read_manifest(path)
    if man is not None:
        kind, detail = manifest_lib.classify_against_live(
            man, _state_tree(state), mesh
        )
        if kind == "incompatible":
            raise ckpt.CheckpointError(
                f"checkpoint {path} cannot feed the configured model: "
                f"{detail}. Match the config to the save (MODEL.ARCH / "
                "NUM_CLASSES / MOE), or start a fresh OUT_DIR."
            )
        if kind == "reshardable":
            logger.info(
                "elastic resume: saved world differs from the live one "
                "(%s) — re-placing restored arrays onto the live layout",
                detail,
            )
    restored = ckpt.load_checkpoint(path)

    params = _place_like(state.params, restored["params"])
    stats = _place_like(state.batch_stats, restored["batch_stats"])
    opt_state = state.opt_state
    if cfg.TRAIN.LOAD_OPT and "opt_state" in restored:
        try:
            # rebuild the optax structure against the LIVE optimizer first —
            # orbax restores namedtuple containers as plain dicts
            # (utils/checkpoint.pack_opt_state has the full story; before
            # r4 this mismatch made every auto-resume silently fall through
            # to a fresh optimizer)
            opt_state = _place_like(
                state.opt_state,
                ckpt.unpack_opt_state(state.opt_state, restored["opt_state"]),
            )
        except ValueError as e:  # structural mismatch from unpack_opt_state →
            # graceful weights-only fallback (utils.py:399-405). Deliberately
            # narrow: placement errors (device_put/OOM) must propagate rather
            # than silently degrade to a fresh optimizer (ADVICE r4).
            logger.warning("optimizer state not restored (%s); fresh optimizer", e)
    start_epoch = int(restored.get("epoch", -1)) + 1
    best_acc1 = float(restored.get("best_acc1", 0.0))
    pending = restored.get("pending_eval")
    pending_eval = None if pending is None else int(pending)
    # shards exact-resume cursor (save_preempt_checkpoint embedded the
    # loader's state_dict); None on epoch-boundary saves / older formats
    ds_arr = restored.get("data_state")
    data_state = None if ds_arr is None else ckpt.decode_data_state(ds_arr)
    logger.info("resumed from %s (epoch %d)", path, start_epoch)
    return (
        TrainState(
            params=params,
            batch_stats=stats,
            opt_state=opt_state,
            step=jnp.int32(int(restored.get("step", 0))),
            key=state.key,
        ),
        start_epoch,
        best_acc1,
        pending_eval,
        data_state,
    )


def check_batch_geometry(mesh, eval_only: bool = False):
    """Validate every batch-divisibility constraint before the expensive
    state init/compile, in the user's config units: grad-accum split, data
    axis sharding, GPipe microbatching (TRAIN **and** the padded eval
    batch — the val loader pads each batch to the full TEST.BATCH_SIZE, so
    an indivisible eval batch would otherwise train a whole epoch and then
    crash inside validate(), ADVICE r2), and ghost BN grouping.

    ``eval_only`` (ADVICE r3 #2): test_model() never trains, so it runs
    only the eval-batch checks — a train-invalid but eval-valid config
    (e.g. an accum setting left in a YAML) must not block evaluation.
    Returns the per-optimizer-step forward batch (None when eval_only).
    """
    data_size = dict(mesh.shape).get("data", 1)
    pipe_size = dict(mesh.shape).get("pipe", 1)
    pipe_mb = cfg.MESH.MICROBATCH or 2 * pipe_size
    # global batch = per-host × DATA GROUPS (≡ process_count in pure DP;
    # smaller when model/pipe axes span hosts — those hosts feed copies)
    _, n_groups = mesh_lib.data_process_groups(mesh)

    if not eval_only:
        accum = max(1, cfg.TRAIN.GRAD_ACCUM_STEPS)
        per_host_batch = cfg.TRAIN.BATCH_SIZE * jax.local_device_count()
        if per_host_batch % accum:
            raise ValueError(
                f"TRAIN.BATCH_SIZE={cfg.TRAIN.BATCH_SIZE} × "
                f"{jax.local_device_count()} local chips = {per_host_batch} "
                f"per host, not divisible by TRAIN.GRAD_ACCUM_STEPS={accum}"
            )
        global_micro = per_host_batch * n_groups // accum
        if accum > 1 and global_micro % data_size:
            raise ValueError(
                f"micro-batch {global_micro} (global batch "
                f"{per_host_batch * n_groups} / "
                f"TRAIN.GRAD_ACCUM_STEPS={accum}) does not shard over the "
                f"data axis of size {data_size}; raise TRAIN.BATCH_SIZE or "
                "lower GRAD_ACCUM_STEPS"
            )
        if pipe_size > 1:
            per_shard = global_micro // data_size
            if per_shard % pipe_mb:
                raise ValueError(
                    f"per-data-shard batch {per_shard} not divisible by the "
                    f"{pipe_mb} GPipe microbatches (MESH.MICROBATCH, 0 → "
                    "2×PIPE); adjust TRAIN.BATCH_SIZE or MESH.MICROBATCH"
                )
        bn_g = (
            bn_group_from_cfg() if models.traits(cfg.MODEL.ARCH).batch_norm
            else 0
        )
        if bn_g > 0 and global_micro > bn_g and global_micro % bn_g:
            # _BNCore would raise the same condition at first train-step trace
            raise ValueError(
                f"ghost BN group {bn_g} (MODEL.BN_GROUP, 0 → "
                f"TRAIN.BATCH_SIZE) does not divide the per-step forward "
                f"batch {global_micro}; adjust MODEL.BN_GROUP / "
                "TRAIN.BATCH_SIZE / GRAD_ACCUM_STEPS"
            )
    else:
        global_micro = None

    if pipe_size > 1:
        eval_global = (
            cfg.TEST.BATCH_SIZE * jax.local_device_count() * n_groups
        )
        eval_per_shard = eval_global // data_size
        # mirrors PipelinedViT's guard: below pipe_mb it falls back to the
        # math-identical sequential stage path, no error
        if eval_per_shard >= pipe_mb and eval_per_shard % pipe_mb:
            raise ValueError(
                f"per-data-shard eval batch {eval_per_shard} "
                f"(TEST.BATCH_SIZE={cfg.TEST.BATCH_SIZE}) not divisible by "
                f"the {pipe_mb} GPipe microbatches; adjust TEST.BATCH_SIZE "
                "or MESH.MICROBATCH"
            )
    return global_micro


def _arm_exact_resume(train_loader, data_state, start_epoch: int, logger):
    """Hand a restored shards cursor (``_resume``'s ``data_state``) to the
    loader so epoch ``start_epoch`` CONTINUES at the exact next batch. Any
    mismatch (format/corpus/shuffle-identity/epoch drift) degrades to the
    epoch-granular resume with a warning — exactness is best-effort, the
    resume itself never fails on a cursor."""
    if data_state is None:
        return
    if int(data_state.get("epoch", -1)) != start_epoch:
        logger.warning(
            "saved data cursor is for epoch %s but resume starts at epoch "
            "%d — re-running from batch 0",
            data_state.get("epoch"), start_epoch,
        )
        return
    try:
        skip = train_loader.load_state_dict(data_state)
    except ValueError as e:
        logger.warning(
            "mid-epoch data cursor not restored (%s) — re-running epoch %d "
            "from batch 0", e, start_epoch + 1,
        )
        return
    if mesh_lib.is_primary():
        logger.info(
            "restored shards data cursor: epoch %d resumes after %d "
            "batches (global sample cursor %d)",
            start_epoch + 1, skip, int(data_state.get("cursor", -1)),
        )


def train_model():
    """End-to-end training (ref: trainer.py:106-173)."""
    mesh_lib.apply_backend_flags(cfg.DEVICE.DETERMINISTIC or cfg.CUDNN.DETERMINISTIC)
    mesh_lib.apply_platform(cfg.DEVICE.PLATFORM)
    mesh_lib.setup_distributed()
    topo = check_trainer_mesh()
    setup_env()
    logger = setup_logger()
    # armed FAULTS.* knobs with impossible arithmetic fail HERE, naming
    # the knobs and units — not hours later at the injection point
    faults.validate_cfg()
    setup_metrics_log(cfg.OUT_DIR, primary=mesh_lib.is_primary())
    # per-rank telemetry sink (telemetry/): spans, compile events, registry
    # snapshots, mirrored resilience events — rank-local signals survive on
    # every process, unlike the primary-only metrics.jsonl above
    telemetry.setup_from_cfg(cfg, rank=jax.process_index())
    # persistent compilation cache (COMPILE_CACHE): must be applied
    # before the first jit below — a restart then loads every
    # previously-compiled step program from disk instead of recompiling
    # (counted as jit.cache_hits, not jit.compiles)
    compile_cache.setup_from_cfg(cfg)
    mesh = mesh_lib.mesh_from_cfg(cfg)
    # cost.* records carry the resolved mesh/topology so post-mortem
    # consumers attribute comm volume per mesh axis (ISSUE 9 satellite)
    costmodel.set_mesh_extras(
        {"mesh": topo.axes, "topology": topo.class_name()}
    )
    key = setup_seed()

    accum = max(1, cfg.TRAIN.GRAD_ACCUM_STEPS)
    check_batch_geometry(mesh)

    # ONE lowering for every topology (parallel/partition/lowering.py):
    # dp / dp×tp / PP / ZeRO-1/3 / EP and their compositions all build
    # from the declared specs — no per-topology step assembly left here.
    model = build_model_from_cfg(topo)
    lowered = partition_lowering.lower(
        model, construct_optimizer(), effective_topk(), mesh=mesh,
        topology=topo, im_size=cfg.TRAIN.IM_SIZE, accum=accum,
    )
    layout = lowered.layout
    state = create_train_state(model, key, mesh, cfg.TRAIN.IM_SIZE, layout=layout)
    m_params, mb = count_parameters(state.params)
    logger.info(
        "model %s: %.3fM params (%.2f MB fp32), mesh %s [%s], %s",
        cfg.MODEL.ARCH, m_params, mb, dict(mesh.shape), topo.class_name(),
        mesh_lib.describe_devices(),
    )

    train_loader = construct_train_loader()
    val_loader = construct_val_loader()
    train_step = lowered.train_step
    eval_step = lowered.eval_step

    start_epoch, best_acc1, pending_eval = 0, 0.0, None
    resumed = False
    if cfg.TRAIN.AUTO_RESUME and ckpt.has_checkpoint():
        try:
            state, start_epoch, best_acc1, pending_eval, data_state = _resume(
                state, mesh
            )
            resumed = True
            _arm_exact_resume(train_loader, data_state, start_epoch, logger)
        except ckpt.NoValidCheckpointError as e:
            # every checkpoint on disk failed verification (all quarantined
            # to *.corrupt) — recover by starting over rather than crashing
            logger.warning("%s — falling through to a fresh start", e)
    if resumed:
        pass
    elif cfg.MODEL.PRETRAINED and cfg.MODEL.WEIGHTS:
        # warm start from pretrained weights (≙ the reference's URL-zoo
        # `pretrained=True` path, ref: resnet.py:309-311 — here the file may
        # be a torch pickle or an orbax dir)
        state = _with_restored_weights(state, cfg.MODEL.WEIGHTS, model)
        logger.info("warm-started from pretrained weights %s", cfg.MODEL.WEIGHTS)
    elif cfg.MODEL.PRETRAINED:
        # The reference downloads zoo weights on PRETRAINED=True
        # (ref: resnet.py:23-33). Connectivity-guarded equivalent: fetch
        # from the URL zoo when reachable; otherwise raise the actionable
        # offline error rather than silently train from random init.
        from distribuuuu_tpu.utils import url_zoo

        path = url_zoo.fetch(cfg.MODEL.ARCH)  # raises offline / unknown
        state = _with_restored_weights(state, path, model)
        logger.info("warm-started from pretrained URL zoo: %s", path)
    elif cfg.MODEL.WEIGHTS:
        logger.warning(
            "MODEL.WEIGHTS is ignored during training unless "
            "MODEL.PRETRAINED True (evaluation uses test_net.py)"
        )

    if cfg.TRAIN.PREEMPT_SAVE:
        preempt.install()

    def _preempt_exit(path, resume_epoch):
        # a boundary save submitted just before the signal may still be
        # committing in the background — the grace window ends with every
        # manifest durable, never with a half-written directory
        asyncplane.join_commits(reason="preemption exit")
        if telemetry.enabled():  # final counters survive the preemption
            telemetry.emit_snapshot()
        if mesh_lib.is_primary():
            logger.warning(
                "preempted: state saved to %s; rerun to resume at epoch %d",
                path, resume_epoch + 1,
            )
        return best_acc1

    def _epoch_telemetry(epoch):
        """Epoch-boundary sampling: device memory stats (TPU/GPU — the
        CPU backend reports none) and one registry snapshot (recompile
        counters, IO tallies) per rank — run_report reads the last.
        With the dispatch sequencer active, its running token/fence
        aggregates land as a ``dispatch.token`` record too."""
        if not telemetry.enabled():
            return
        if cfg.TELEMETRY.MEMSTATS:
            telemetry_runtime.sample_memstats(epoch=epoch + 1)
        sequencer.emit_stats(epoch=epoch + 1)
        telemetry.emit_snapshot(epoch=epoch + 1)

    # concurrent eval (TRAIN.CONCURRENT_EVAL — asyncplane/evalloop.py):
    # validate() runs against an on-device epoch-boundary snapshot on a
    # worker thread while the next train epoch dispatches; results join
    # (with best-acc bookkeeping + the eval/epoch records) one boundary
    # later. Multi-device processes run under the dispatch sequencer
    # (asyncplane/sequencer.py): train/eval/snapshot dispatches are
    # token-ordered into one global program sequence, which removes the
    # cross-thread collective deadlock PR 10 pinned on the
    # 8-virtual-device mesh. Multi-host additionally attaches the
    # cross-host dispatch ring (asyncplane/ring.py, ISSUE 18): process 0
    # publishes its grant order through the shared OUT_DIR, followers
    # grant only in that order — two SPMD programs from two host threads
    # enqueue in ONE per-device order on EVERY host, which lifts the
    # PR 11 degrade-to-sync. ASYNC.SEQUENCER=False on multi-device stays
    # the explicit escape hatch.
    conc_eval = None
    if cfg.TRAIN.CONCURRENT_EVAL:
        if jax.device_count() > 1 and not cfg.ASYNC.SEQUENCER:
            logger.warning(
                "TRAIN.CONCURRENT_EVAL requested with "
                "ASYNC.SEQUENCER=False and device_count=%d — without "
                "token-ordered dispatch two multi-device programs can "
                "interleave their collectives per-device and deadlock; "
                "falling back to synchronous eval (re-enable "
                "ASYNC.SEQUENCER to overlap)", jax.device_count(),
            )
        else:
            if jax.device_count() > 1:
                sequencer.install(cfg.TRAIN.STALL_TIMEOUT, logger=logger)
                logger.info(
                    "dispatch sequencer active: train/eval/snapshot "
                    "dispatches token-ordered across %d devices "
                    "(ASYNC.SEQUENCER)", jax.device_count(),
                )
            if jax.process_count() > 1:
                # leader opens (fresh-clears) the ring FIRST, then every
                # host syncs, then followers attach — a follower can
                # never read a stale OPEN/watermark from a previous
                # attempt of this OUT_DIR
                from jax.experimental import multihost_utils

                ring_root = os.path.join(cfg.OUT_DIR, ".dispatch_ring")
                rank, world = jax.process_index(), jax.process_count()
                if rank == 0:
                    sequencer.install_ring(
                        ring_root, rank, world, cfg.ASYNC.RING_DEADLINE_S,
                        detach_after_s=cfg.ASYNC.BARRIER_TIMEOUT_S,
                        logger=logger,
                    )
                multihost_utils.sync_global_devices("dtpu dispatch ring open")
                if rank != 0:
                    sequencer.install_ring(
                        ring_root, rank, world, cfg.ASYNC.RING_DEADLINE_S,
                        detach_after_s=cfg.ASYNC.BARRIER_TIMEOUT_S,
                        logger=logger,
                    )
                logger.info(
                    "cross-host dispatch ring active: host %d/%d %s via "
                    "%s (deadline %.0fs — see docs/RUNBOOK.md 'Async on "
                    "a pod, for real')", rank, world,
                    "publishes the grant order" if rank == 0
                    else "follows the published order", ring_root,
                    cfg.ASYNC.RING_DEADLINE_S,
                )
            conc_eval = asyncplane.ConcurrentEval(
                lambda snap, ep: validate(
                    val_loader, mesh, snap, eval_step, ep, logger,
                    quiet=True, watch_preemption=False,
                )
            )
            logger.info(
                "concurrent eval: validate() overlaps the next train "
                "epoch; results join one boundary later"
            )

    def _ring_degraded_boundary():
        """Did ANY host miss its ring deadline this epoch? The answer is
        collective (``requested_global`` idiom) because the degraded
        boundary dispatches a different program sequence — a host-local
        decision would re-create the very cross-host inversion the ring
        exists to prevent. Safe to run a collective here: the previous
        eval has joined and the epoch's train steps are dispatched, so
        every host appends this program at the same sequence point.
        Clears the sticky flag (a persistent wedge re-flags next epoch)."""
        if not sequencer.ring_installed():
            return False
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.int32(1 if sequencer.ring_wedged() else 0)
        )
        sequencer.clear_ring_wedge()
        return bool(np.asarray(flags).sum() > 0)

    def _join_concurrent_eval():
        """Join the in-flight eval (no-op when none): emit the deferred
        eval summary + epoch record, update best-tracking, and side-write
        the ``best`` checkpoint from the eval's own snapshot — exactly
        what the synchronous boundary does, one epoch later."""
        nonlocal best_acc1
        if conc_eval is None:
            return
        joined = conc_eval.join()
        if joined is None:
            return
        ep, result, snap = joined
        if result is None:  # defensive: the worker runs with watch off
            logger.warning(
                "concurrent eval for epoch %d returned no result", ep + 1
            )
            return
        acc1, topk_v, loss, n = result
        log_eval_result(logger, ep, acc1, topk_v, loss, n)
        is_best = acc1 > best_acc1
        best_acc1 = max(acc1, best_acc1)
        if is_best:
            ckpt.save_best_checkpoint(snap.params, snap.batch_stats, ep)
        if mesh_lib.is_primary():
            logger.info(
                "epoch %d done: Acc@1 %.3f (best %.3f)",
                ep + 1, acc1, best_acc1,
            )
            metrics_log("epoch", epoch=ep + 1, acc1=acc1, best_acc1=best_acc1)

    def _finish_epoch(epoch):
        """Validate + best-track + save for a completed epoch. Returns the
        preempt-checkpoint path if the eval itself was preempted, else
        None."""
        nonlocal best_acc1
        result = validate(val_loader, mesh, state, eval_step, epoch, logger)
        if result is None:  # preempted mid-eval; epoch's training is done
            return ckpt.save_preempt_checkpoint(
                _state_tree(state), epoch + 1, best_acc1, pending_eval=epoch
            )
        acc1 = result[0]
        is_best = acc1 > best_acc1
        best_acc1 = max(acc1, best_acc1)
        ckpt.save_checkpoint(_state_tree(state), epoch, best_acc1, is_best)
        if mesh_lib.is_primary():
            logger.info(
                "epoch %d done: Acc@1 %.3f (best %.3f)",
                epoch + 1, acc1, best_acc1,
            )
            metrics_log(
                "epoch", epoch=epoch + 1, acc1=acc1, best_acc1=best_acc1
            )
        return None

    if pending_eval is not None:
        # the interrupted run finished training epoch `pending_eval` but
        # was preempted before/during its eval: validate it NOW so it gets
        # best-tracking and a real epoch checkpoint (which also supersedes
        # the preempt checkpoint we just resumed from)
        if mesh_lib.is_primary():
            logger.info(
                "running epoch %d's validation (skipped by the preemption)",
                pending_eval + 1,
            )
        path = _finish_epoch(pending_eval)
        if path is not None:  # preempted again
            return _preempt_exit(path, pending_eval + 1)
        # the eval-preempt checkpoint (named pending_eval+1, holding this
        # epoch's end state) is now fully superseded by ckpt_ep_{pending};
        # without this prune it would outrank the real checkpoints on
        # every restart and the run could never cleanly terminate
        ckpt.prune_preempts(pending_eval + 1)

    epoch = start_epoch
    rollbacks_left = max(0, int(cfg.TRAIN.MAX_ROLLBACKS))
    try:
        while epoch < cfg.OPTIM.MAX_EPOCH:
            try:
                state, interrupted, batches_done = train_epoch(
                    loader=train_loader, mesh=mesh, state=state,
                    train_step=train_step, epoch=epoch, logger=logger,
                    first_epoch=start_epoch)
            except supervisor.NonFiniteLossError as e:
                # TRAIN.NONFINITE=rollback: reload the last intact checkpoint
                # and re-run from there — the transient-corruption recovery.
                # A deterministic NaN re-trips and surfaces once the budget
                # (TRAIN.MAX_ROLLBACKS) is spent; "raise" propagates directly.
                if cfg.TRAIN.NONFINITE != "rollback":
                    raise
                if rollbacks_left <= 0:
                    logger.error(
                        "rollback budget exhausted (TRAIN.MAX_ROLLBACKS=%d) — "
                        "the non-finite loss reproduces from the checkpoint; "
                        "this is not transient corruption",
                        cfg.TRAIN.MAX_ROLLBACKS,
                    )
                    raise
                if not ckpt.has_checkpoint():
                    logger.error(
                        "non-finite loss before any checkpoint exists — "
                        "nothing to roll back to"
                    )
                    raise
                rollbacks_left -= 1
                logger.warning(
                    "non-finite loss at epoch %d batch ~%d — rolling back to "
                    "the last intact checkpoint (%d attempt(s) left)",
                    e.epoch + 1, e.batch, rollbacks_left,
                )
                # quiesce the async plane before reloading: the in-flight
                # eval joins (its best bookkeeping applies, then _resume
                # restores the checkpointed best), and find_last_valid joins
                # any commit still in flight
                _join_concurrent_eval()
                state, epoch, best_acc1, rb_pending, rb_ds = _resume(state, mesh)
                # the pre-epoch state's buffers were DONATED to the step calls
                # (donate_argnums=0) — its key is deleted; re-attach the live
                # base key (the value is seed-derived, identical by definition)
                state = state.replace(key=key)
                # rolling back onto a preempt save: honor its data cursor too
                _arm_exact_resume(train_loader, rb_ds, epoch, logger)
                if rb_pending is not None:
                    # rolled back onto an eval-pending preempt save: finish
                    # that epoch's validation first, as a fresh start would
                    path = _finish_epoch(rb_pending)
                    if path is not None:
                        return _preempt_exit(path, rb_pending + 1)
                    ckpt.prune_preempts(rb_pending + 1)
                continue
            watching = cfg.TRAIN.PREEMPT_SAVE
            if interrupted:
                # mid-epoch preemption: persist now; the next run's AUTO_RESUME
                # prefers this checkpoint and re-runs this epoch from it
                # (utils/preempt.py has the full story). The shards pipeline
                # additionally embeds the loader's exact global cursor, so the
                # re-run CONTINUES at batch `batches_done` instead of batch 0.
                # The previous epoch's concurrent eval joins first — its best
                # bookkeeping must ride the preempt save.
                _join_concurrent_eval()
                data_state = (
                    train_loader.state_dict(batches_done)
                    if train_loader.can_save_state()
                    else None
                )
                path = ckpt.save_preempt_checkpoint(
                    _state_tree(state), epoch, best_acc1, data_state=data_state
                )
                return _preempt_exit(path, epoch)
            if watching and preempt.requested_global():
                # signaled between the last batch and validate: the epoch is
                # COMPLETE — skip the (possibly long) validation, save the
                # finished state marked eval-pending, exit inside the grace
                # window; the resume validates it before continuing
                _join_concurrent_eval()
                path = ckpt.save_preempt_checkpoint(
                    _state_tree(state), epoch + 1, best_acc1, pending_eval=epoch
                )
                return _preempt_exit(path, epoch + 1)
            if conc_eval is not None:
                # concurrent boundary: join the PREVIOUS epoch's eval (its
                # result, best-tracking, and log records land now), commit
                # this epoch's checkpoint (async snapshot inside when
                # CHECKPOINT.ASYNC), then launch this epoch's eval — the next
                # train epoch dispatches while it runs. The boundary save
                # records best_acc1 as of the previous eval (this epoch's is
                # in flight); the best side-write itself lands at join.
                _join_concurrent_eval()
                if _ring_degraded_boundary():
                    # a host missed its ring deadline this epoch: every
                    # host (collectively agreed) runs THIS epoch's eval
                    # synchronously — graceful degradation, never a hang;
                    # the next boundary re-tries the concurrent path
                    logger.warning(
                        "dispatch ring wedged during epoch %d — running "
                        "this epoch's eval synchronously (the ring "
                        "re-arms next epoch; persistent wedges re-flag)",
                        epoch + 1,
                    )
                    path = _finish_epoch(epoch)
                    if path is not None:
                        return _preempt_exit(path, epoch + 1)
                else:
                    ckpt.save_checkpoint(
                        _state_tree(state), epoch, best_acc1, is_best=False
                    )
                    conc_eval.launch(state, epoch)
            else:
                path = _finish_epoch(epoch)
                if path is not None:  # eval was preempted (validate → None)
                    return _preempt_exit(path, epoch + 1)
            _epoch_telemetry(epoch)
            if watching and preempt.requested_global():
                # signaled during the save: ckpt_ep_{epoch} is already on
                # disk (or committing in the background — _preempt_exit
                # drains) — nothing more to persist; the in-flight eval
                # joins so its result is not lost
                _join_concurrent_eval()
                return _preempt_exit(ckpt.get_checkpoint(epoch), epoch + 1)
            epoch += 1
        # end of run: the final epoch's eval joins (best-tracking + records),
        # and the committer drains — no process exits with an uncommitted save
        _join_concurrent_eval()
        asyncplane.join_commits(reason="exit")
        return best_acc1
    finally:
        # quiesce the async plane on EVERY exit — including an
        # exception (e.g. NonFiniteLossError under policy "raise")
        # propagating to the caller: a worker thread still
        # dispatching device work during interpreter teardown aborts
        # the whole process, and a clean exit must never abandon an
        # uncommitted save. On the normal path the loop already
        # joined, so these are no-ops.
        if conc_eval is not None and conc_eval.in_flight:
            try:
                conc_eval.join()
            except Exception as qe:
                logger.warning(
                    "concurrent eval quiesced with error: %s", qe
                )
        try:
            asyncplane.join_commits()
        except asyncplane.AsyncCommitError as qe:
            logger.warning("async committer quiesced with error: %s", qe)
        # the sequencer's final stats, then back to the zero-overhead
        # pass-through (process-global, like the committer's state)
        sequencer.emit_stats(final=True)
        sequencer.shutdown()


def test_model():
    """Evaluate MODEL.WEIGHTS on the val split (ref: trainer.py:176-209)."""
    mesh_lib.apply_backend_flags(cfg.DEVICE.DETERMINISTIC or cfg.CUDNN.DETERMINISTIC)
    mesh_lib.apply_platform(cfg.DEVICE.PLATFORM)
    mesh_lib.setup_distributed()
    topo = check_trainer_mesh()
    logger = setup_logger()
    telemetry.setup_from_cfg(cfg, rank=jax.process_index())
    compile_cache.setup_from_cfg(cfg)  # warm eval compiles on restart
    mesh = mesh_lib.mesh_from_cfg(cfg)
    costmodel.set_mesh_extras(
        {"mesh": topo.axes, "topology": topo.class_name()}
    )
    # eval-only checks (GPipe eval divisibility), before the compile — a
    # train-invalid config must not block a pure evaluation (ADVICE r3 #2)
    check_batch_geometry(mesh, eval_only=True)
    model = build_model_from_cfg(topo)
    key = jax.random.key(cfg.RNG_SEED or 0)
    layout = _state_layout(model, mesh, cfg.TRAIN.IM_SIZE)
    state = create_train_state(
        model, key, mesh, cfg.TRAIN.IM_SIZE, layout=layout
    )
    if cfg.MODEL.WEIGHTS:
        state = _with_restored_weights(state, cfg.MODEL.WEIGHTS, model)
        logger.info("loaded weights from %s", cfg.MODEL.WEIGHTS)
    val_loader = construct_val_loader()
    # ZeRO rest layouts evaluate under the same gather-once schedule the
    # train path uses (partition/lowering.make_gather_entry)
    eval_step = make_eval_step(
        model, effective_topk(), layout=layout if cfg.MESH.ZERO else None
    )
    result = validate(val_loader, mesh, state, eval_step, 0, logger)
    if result is None:  # preempted mid-eval (TRAIN.PREEMPT_SAVE)
        if mesh_lib.is_primary():
            logger.warning("evaluation preempted before completion")
        return None
    top1, topk = result[0], result[1]
    if telemetry.enabled():
        telemetry.emit_snapshot()
    if mesh_lib.is_primary():
        logger.info("TEST  Acc@1 %.3f  Acc@%d %.3f", top1, effective_topk(), topk)
    return top1, topk
