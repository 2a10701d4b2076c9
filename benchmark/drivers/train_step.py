"""Driver ``train_step``: the trainer's per-step program on a device batch.

Builds the step exactly as ``trainer.train_model`` does (config -> mesh ->
topology -> model -> ``lower`` -> ``create_train_state``), makes ONE seeded
batch on the device, and dispatches ``lowered.train_step`` step by step over
whatever mesh the cell's chips form. No eval step, no fold, no loader: the
cell times the step program and bypasses the loop around it.

* set-up: weights and batch made on the device from ``--seed``; the plain
  float32 reference and the step program compile (or load from the cache);
  ``warmup_steps`` steps run; the first loss after them is compared with the
  reference on the same weights and batch.
* window: chunks of ``chunk_steps`` steps, one always queued behind the one
  that runs, until ``--seconds`` have passed; the rate is over the whole
  window, which ends in ``block_until_ready`` on the state.
* traced run: after the window, ``trace_steps`` further steps between two
  fences under the profiler, with host spans around dispatch and fence.

``attempted`` = steps in the window, ``failed`` = steps with a non-finite
loss. ``correct``: reference agreement, a finite loss that is lower at the
end of the window than at its start, and on several chips replicated
parameters that are bit-identical on every device after the window.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

# The program surface this driver stands on (PERF.md lists it): names with a
# home of their own, no re-export of trainer.py, no environment variable.
import distribuuuu_tpu.config as program_config
from distribuuuu_tpu.asyncplane.compile_cache import setup_from_cfg
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu.parallel.mesh import build_mesh
from distribuuuu_tpu.parallel.partition.lowering import lower
from distribuuuu_tpu.trainer import (
    build_model_from_cfg,
    check_trainer_mesh,
    create_train_state,
)
from distribuuuu_tpu.utils.optim import construct_optimizer

from benchmark.harness import profiler, stats, trace
from benchmark.harness.clock import Window, now
from benchmark.harness.observation import Observation
from benchmark.reference.common import cross_entropy


def configure(run, chips: int) -> dict:
    """Point the program's global config at this cell's training job; returns
    the job. ``TRAIN.BATCH_SIZE`` is per chip, as in the shipped YAMLs, and is
    also the ghost-BN group, so BN statistics never cross chips."""
    program, job = run.section("program"), run.section("train_job")
    program_config.reset_cfg()
    program_config.merge_from_file(os.path.join(run.root, program["cfg_file"]))
    overrides = {
        **program["overrides"],
        "TRAIN.BATCH_SIZE": job["per_chip_batch"],
        "TRAIN.IM_SIZE": job["im_size"],
        "DEVICE.COMPUTE_DTYPE": job["dtype"],
        "OPTIM.BASE_LR": job["lr"],
        "MESH.DATA": chips,
        "RNG_SEED": run.seed,
    }
    cfg.merge_from_list([str(x) for kv in overrides.items() for x in kv])
    return job


def build(run, chips: int, devices):
    """(lowered, job, global_batch): the step program for ``devices``."""
    job = configure(run, chips)
    mesh = build_mesh(data=chips, devices=devices)
    topology = check_trainer_mesh()
    model = build_model_from_cfg(topology)
    lowered = lower(
        model, construct_optimizer(), min(5, cfg.MODEL.NUM_CLASSES),
        mesh=mesh, topology=topology, im_size=job["im_size"],
    )
    return lowered, job, job["per_chip_batch"] * chips


def compile_only(run, devices) -> dict:
    """For ``rehearse_compile.py``: this cell's step program compiled for
    ``devices`` that are described, not attached. Nothing runs."""
    lowered, _job, global_batch = build(run, len(devices), devices)
    state, batch = lowered.abstract_args(global_batch)
    image = batch["image"]
    batch["image"] = jax.ShapeDtypeStruct(
        image.shape, jnp.uint8, sharding=image.sharding
    )
    return {"train_step": lowered.train_step.lower(state, batch).compile()}


def make_batch(seed: int, global_batch: int, im_size: int, num_classes: int,
               shardings: dict):
    """One batch of raw uint8 pixels and labels, made on the device from the
    seed in one jitted call and laid out as the step declares its batch (the
    trainer's loader ships uint8 and the step normalizes in-graph)."""

    def draw(seed):
        k_img, k_lbl = jax.random.split(jax.random.fold_in(jax.random.key(seed), 1))
        shape = (global_batch, im_size, im_size, 3)
        return {
            "image": jax.random.randint(k_img, shape, 0, 256, jnp.int32)
            .astype(jnp.uint8),
            "label": jax.random.randint(
                k_lbl, (global_batch,), 0, num_classes, jnp.int32
            ),
        }

    return jax.jit(draw, out_shardings=shardings)(np.int32(seed % 2**31))


def reference_loss(run, state, batch, bn_group: int) -> float:
    """The configuration's plain float32 reference on the same weights and
    batch, on one device, one BN group (one chip's batch) at a time; the loss
    is the mean over the groups."""
    reference = run.catalog.reference(run.cell.config["reference"])
    architecture = run.section("architecture")

    def group_loss(params, stats, group):
        logits = reference.logits(
            params, stats, group["image"], architecture=architecture,
            train=True,
        )
        return cross_entropy(logits, group["label"])

    @jax.jit
    def mean_loss(params, stats, batch):
        groups = jax.tree.map(
            lambda x: x.reshape((-1, bn_group) + x.shape[1:]), batch
        )
        return jax.lax.map(lambda g: group_loss(params, stats, g), groups).mean()

    local = jax.device_put(
        (state.params, state.batch_stats, batch), jax.devices()[0]
    )
    return float(mean_loss(*local))


def replicas_identical(params) -> bool:
    """Every replicated parameter holds the same bits on every device."""
    for leaf in jax.tree.leaves(params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        if any(not np.array_equal(shards[0], s) for s in shards[1:]):
            return False
    return True


def device_memory(devices) -> tuple[int, int]:
    """(peak bytes, limit bytes) of the fullest device. On this runtime the
    buffers a process holds (``peak_bytes_in_use``) and the temporaries its
    programs reserve while they run (``peak_bytes_reserved``) are counted
    apart, and both occupy the device's memory."""
    stats = [d.memory_stats() or {} for d in devices]
    peaks = [
        int(m.get("peak_bytes_in_use", 0)) + int(m.get("peak_bytes_reserved", 0))
        for m in stats
    ]
    fullest = peaks.index(max(peaks))
    return peaks[fullest], int(stats[fullest].get("bytes_limit", 0))


def run(run) -> Observation:
    chips = run.cell.chips
    run.mark("imports")
    devices = jax.devices()
    run.mark("reach the device")
    run.admit_device(devices[0].platform, devices[0].device_kind, len(devices))
    run.compiles.install()
    lowered, job, global_batch = build(run, chips, devices[:chips])
    setup_from_cfg(cfg)
    traffic = run.traffic

    _, batch_layout = lowered.abstract_args(global_batch)
    batch = make_batch(
        run.seed, global_batch, job["im_size"], cfg.MODEL.NUM_CLASSES,
        {k: v.sharding for k, v in batch_layout.items()},
    )
    state = create_train_state(
        lowered.model, jax.random.key(run.seed), lowered.mesh, job["im_size"],
        layout=lowered.layout,
    )
    jax.block_until_ready((state, batch))
    run.mark("weights and batch")
    leaves = jax.tree.leaves(state.params)
    counters = {
        "param_bytes": sum(x.size * x.dtype.itemsize for x in leaves),
        # SGD keeps one moment (the momentum trace) in the parameters' layout
        "moment_bytes": sum(
            x.size * x.dtype.itemsize
            for x in jax.tree.leaves(state.opt_state)
            if x.ndim > 0
        ),
    }
    losses = []

    def steps(state, n, annotate=False):
        for _ in range(n):
            if annotate:
                with profiler.span("dispatch"):
                    state, metrics = lowered.train_step(state, batch)
            else:
                state, metrics = lowered.train_step(state, batch)
            losses.append(metrics["loss"])
        return state

    # warm up the one shape the window uses, then hold the program's next
    # loss against the reference on the very same weights
    state = jax.block_until_ready(steps(state, traffic["warmup_steps"]))
    run.mark("step program and warm-up")
    want = reference_loss(run, state, batch, job["per_chip_batch"])
    state = jax.block_until_ready(steps(state, 1))
    got = float(jax.device_get(losses[-1]))
    tolerance = job["reference_tolerance"]
    agrees = abs(got - want) <= tolerance * max(1.0, abs(want))
    run.say(
        f"reference: program loss {got:.6f} vs plain float32 {want:.6f} "
        f"(|diff| {abs(got - want):.6f}, tolerance {tolerance} relative): "
        f"{'agrees' if agrees else 'DISAGREES'}"
    )
    del losses[:]
    run.mark("reference")

    # ---------------------------------------------------------------- window
    # One chunk is always queued behind the one that runs, as in the trainer's
    # loop (which dispatches ahead and reads metrics now and then): the host
    # waits for the previous chunk's last loss, never for the state, so a
    # pause of the host does not drain the device (with a fence after every
    # chunk, 3 runs of 25 on the chip lost 25-80 ms to one). The window ends
    # in a fence on the state.
    window = Window(run.seconds)
    chunk, chunk_s = traffic["chunk_steps"], []
    run.open_window()
    t = window.open()
    state = steps(state, chunk)
    while not window.expired():
        state = steps(state, chunk)
        jax.block_until_ready(losses[-chunk - 1])
        chunk_s.append(now() - t)
        t += chunk_s[-1]
    state = jax.block_until_ready(state)
    window.close()
    chunk_s.append(now() - t)
    n_steps = len(losses)
    in_window = jax.device_get(losses)

    trace_path = op_names_path = None
    if run.trace:
        with profiler.capture(run.trace_dir) as captured:
            with profiler.span("window"):
                state = steps(state, traffic["trace_steps"], annotate=True)
                with profiler.span("fence"):
                    state = jax.block_until_ready(state)
        trace_path = captured["path"]
    counters["compiles_in_window"] = run.compiles_since_open()
    if run.trace:
        # the trace names HLO instructions; their scopes are in the program's
        # own HLO text (a second lowering, served from the compile cache)
        hlo = lowered.train_step.lower(state, batch).compile().as_text()
        op_names_path = os.path.join(run.trace_dir, "op_names.json")
        with open(op_names_path, "w") as f:
            json.dump(trace.op_names_from_hlo(hlo), f)

    per_chunk = [c / chunk * 1e3 for c in chunk_s]
    q1, med, q3 = stats.quartiles(per_chunk)
    run.say(
        f"window: {n_steps} steps in {window.elapsed:.3f} s; ms/step over "
        f"{len(chunk_s)} chunks of {chunk}: "
        f"q1 {q1:.3f} median {med:.3f} q3 {q3:.3f}; "
        f"loss {in_window[0]:.4f} -> {in_window[-1]:.4f}"
    )
    finite = [bool(np.isfinite(x)) for x in in_window]
    learned = all(finite) and float(in_window[-1]) < float(in_window[0])
    identical = chips == 1 or replicas_identical(state.params)
    if chips > 1:
        run.say(f"replicas: parameters bit-identical on {chips} devices: {identical}")

    peak, limit = device_memory(devices[:chips])
    run.say(f"memory: peak {peak / 2**30:.2f} GiB of {limit / 2**30:.2f} GiB "
            "on the fullest chip")
    counters["trace_steps"] = traffic["trace_steps"] if run.trace else 0
    return Observation(
        correct=bool(agrees and learned and identical),
        attempted=n_steps,
        failed=finite.count(False),
        end_to_end={
            "train_items_per_s_per_chip":
                n_steps * global_batch / window.elapsed / chips,
        },
        counters=counters,
        device={
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": chips,
            "memory_peak_bytes": peak,
            "memory_limit_bytes": limit,
        },
        trace_path=trace_path,
        trace_op_names_path=op_names_path,
    )
