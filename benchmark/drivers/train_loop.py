"""Driver ``train_loop``: the program's own training loop, fed from the host.

Builds the step as ``drivers/train_step.py`` does (its ``build``, loaded by
name), then hands the PROGRAM's ``data.loader.Loader`` over a pool of
pre-decoded ``uint8`` images in host memory to the program's
``trainer.train_epoch``: loader workers -> ``device_prefetch`` ->
``shard_batch`` (H2D) -> ``sequencer.dispatch``, the print's fence every
``TRAIN.PRINT_FREQ`` steps, the flush at the epoch's end. Closed loop, no
rate: the loop pulls the next batch when it has dispatched a step.

* set-up: weights and pool from ``--seed``; a PLAIN loop of this driver's own
  over the warm-up epoch's batches (``for hb in loader: state, _ =
  train_step(state, shard_batch(hb))``: no ring, no sequencer, the epoch's
  learning rate set as ``train_epoch`` sets it), whose first loss is held to
  the float32 reference on the same weights and batch; then the warm-up epoch
  through ``train_epoch`` from a copy of the same state.
* window: whole epochs of ``epoch_steps`` back to back for as long as the
  next one would still end inside ``--seconds`` (never fewer than one); ends
  in ``block_until_ready`` on the state. No JSONL sink, no ``metrics.jsonl``,
  no profiler.
* traced run: after the window, one further epoch of ``trace_steps`` under
  ``harness/loop_capture.capture`` (the Python tracer off).

``attempted`` = steps dispatched in the window, ``failed`` = those with a
non-finite loss. ``correct``: the reference agrees; after the warm-up epoch
the state is BIT-identical to the plain loop's (every batch trained once, in
order) and ``trainer.steps`` counted the batches the loader assembled (where
the program has the counter); every loss finite and the mean over the
window's last quarter of an epoch under that over its first.
"""

from __future__ import annotations

import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np

from distribuuuu_tpu.asyncplane.compile_cache import setup_from_cfg
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu.data.loader import Loader
from distribuuuu_tpu.parallel import sharding as sharding_lib
from distribuuuu_tpu.telemetry import get_registry
from distribuuuu_tpu.trainer import create_train_state, train_epoch
from distribuuuu_tpu.utils.logger import get_logger
from distribuuuu_tpu.utils.optim import set_lr
from distribuuuu_tpu.utils.schedules import get_epoch_lr

from benchmark.harness import loop_capture, profiler, program_spans, trace
from benchmark.harness.clock import Window, now
from benchmark.harness.observation import Observation

COUNTERS = ("trainer.steps", "trainer.epochs", "trainer.wait_s",
            "trainer.h2d_s", "trainer.h2d_bytes", "trainer.fetch_s")
COARSE = 8  # the pool's images: COARSE x COARSE random blocks under noise


class Pool:
    """``length`` samples over ``len(images)`` pre-decoded images in host
    memory: sample i is image i mod the pool. Counts what it serves."""

    def __init__(self, images, labels, length: int):
        self.images, self.labels, self.length = images, labels, int(length)
        self.served = 0
        self._lock = threading.Lock()  # worker threads fetch concurrently

    def __len__(self):
        return self.length

    def __getitem__(self, i):
        with self._lock:
            self.served += 1
        j = i % len(self.images)
        return self.images[j], self.labels[j]


def make_pool(seed: int, n: int, size: int, num_classes: int):
    """``n`` distinct ``uint8`` images and uniform labels from the seed.
    Half of each image is a coarse pattern of its own and half per-pixel
    noise: pure noise images all look alike to a conv net, and a loop whose
    loss cannot fall cannot show that it trains."""
    rng = np.random.default_rng(seed)
    block = size // COARSE
    coarse = rng.integers(0, 256, (n, COARSE, COARSE, 3), dtype=np.uint8)
    images = np.repeat(np.repeat(coarse, block, axis=1), block, axis=2) >> 1
    images += rng.integers(0, 256, images.shape, dtype=np.uint8) >> 1
    labels = rng.integers(0, num_classes, n, dtype=np.int32)
    return images, labels


def compile_only(run, devices) -> dict:
    """For ``rehearse_compile.py``: the loop's step program (the loader's
    batch carries a ``mask``, so it is not ``train_step``'s executable)."""
    lowered, _job, global_batch = run.catalog.driver("train_step").build(
        run, len(devices), devices
    )
    state, batch = lowered.abstract_args(global_batch, with_mask=True)
    image = batch["image"]
    batch["image"] = jax.ShapeDtypeStruct(
        image.shape, jnp.uint8, sharding=image.sharding
    )
    return {"train_step": lowered.train_step.lower(state, batch).compile()}


def states_identical(a, b) -> bool:
    """Every leaf of two states holds the same bits."""
    def bits(x):
        if isinstance(x, jax.Array) and jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
            x = jax.random.key_data(x)
        return np.asarray(x)

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(bits(x), bits(y)) for x, y in zip(la, lb)
    )


def counted(registry) -> dict:
    """The loop's registry counters as they stand; a program without them
    (the parent of the PR that added them) gives an empty dict."""
    counters = registry.snapshot()["counters"]
    return {name: counters[name] for name in COUNTERS if name in counters}


def run(run) -> Observation:
    base = run.catalog.driver("train_step")
    chips = run.cell.chips
    run.mark("imports")
    devices = jax.devices()
    run.mark("reach the device")
    run.admit_device(devices[0].platform, devices[0].device_kind, len(devices))
    run.compiles.install()
    lowered, job, global_batch = base.build(run, chips, devices[:chips])
    traffic = run.traffic
    cfg.merge_from_list(
        [str(x) for kv in traffic["overrides"].items() for x in kv]
    )
    setup_from_cfg(cfg)
    mesh, registry, logger = lowered.mesh, get_registry(), get_logger()
    run.say(
        f"loop: TRAIN.WORKERS {cfg.TRAIN.WORKERS}, TRAIN.PREFETCH_DEVICE "
        f"{cfg.TRAIN.PREFETCH_DEVICE}, TRAIN.PRINT_FREQ {cfg.TRAIN.PRINT_FREQ}, "
        f"DATA.DEVICE_NORMALIZE {cfg.DATA.DEVICE_NORMALIZE}, batch {global_batch}"
    )

    images, labels = make_pool(
        run.seed, traffic["pool_images"], job["im_size"], cfg.MODEL.NUM_CLASSES
    )

    def loader_of(steps: int) -> Loader:
        return Loader(
            Pool(images, labels, steps * global_batch), batch_size=global_batch,
            shuffle=True, drop_last=True, workers=cfg.TRAIN.WORKERS,
            seed=run.seed,
        )

    state = create_train_state(
        lowered.model, jax.random.key(run.seed), mesh, job["im_size"],
        layout=lowered.layout,
    )
    twin = jax.tree.map(
        lambda x: x.copy() if isinstance(x, jax.Array) else x, state
    )
    jax.block_until_ready((state, twin))
    run.mark("weights and pool")
    leaves = jax.tree.leaves(state.params)
    counters = {
        "param_bytes": sum(x.size * x.dtype.itemsize for x in leaves),
        "moment_bytes": sum(
            x.size * x.dtype.itemsize
            for x in jax.tree.leaves(state.opt_state)
            if x.ndim > 0
        ),
    }

    # ------------------------------------- the plain loop, and the reference
    warm = loader_of(traffic["warmup_steps"])
    warm.set_epoch(0)
    set_lr(twin.opt_state, get_epoch_lr(0))
    want = first = last_batch = None
    for hb in warm:
        last_batch = sharding_lib.shard_batch(mesh, hb)
        if want is None:
            want = base.reference_loss(
                run, twin, {k: last_batch[k] for k in ("image", "label")},
                job["per_chip_batch"],
            )
        twin, metrics = lowered.train_step(twin, last_batch)
        first = metrics["loss"] if first is None else first
    twin = jax.block_until_ready(twin)
    got = float(jax.device_get(first))
    tolerance = job["reference_tolerance"]
    agrees = abs(got - want) <= tolerance * max(1.0, abs(want))
    run.say(
        f"reference: program loss {got:.6f} vs plain float32 {want:.6f} "
        f"(|diff| {abs(got - want):.6f}, tolerance {tolerance} relative): "
        f"{'agrees' if agrees else 'DISAGREES'}"
    )
    run.mark("step program, plain loop and reference")

    # ----------------------------------------------- the loop: warm-up epoch
    losses = []

    def step(state, batch):
        state, metrics = lowered.train_step(state, batch)
        losses.append(metrics["loss"])
        return state, metrics

    before, pulled = counted(registry), warm.dataset.served
    state, _interrupted, done = train_epoch(warm, mesh, state, step, 0, logger)
    state = jax.block_until_ready(state)
    pulled = (warm.dataset.served - pulled) // global_batch
    identical = states_identical(state, twin) and float(
        jax.device_get(losses[0])) == got
    after = counted(registry)
    steps_counted = (
        after["trainer.steps"] - before.get("trainer.steps", 0)
        if "trainer.steps" in after else None
    )
    in_order = done == pulled == len(warm) and steps_counted in (None, pulled)
    run.say(
        f"loop against the plain loop after {done} steps: state bit-identical "
        f"{identical}; batches assembled {pulled}, trainer.steps "
        f"{'absent from this program' if steps_counted is None else int(steps_counted)}"
        f": {'every batch once, in order' if identical and in_order else 'DIFFERS'}"
    )
    del twin, losses[:]
    run.mark("warm-up epoch")

    # ---------------------------------------------------------------- window
    loader = loader_of(traffic["epoch_steps"])
    window, epoch, epoch_s = Window(run.seconds), 1, []
    before = counted(registry)
    run.open_window()
    t = window.open()
    while True:
        state, _interrupted, _done = train_epoch(
            loader, mesh, state, step, epoch, logger
        )
        epoch += 1
        epoch_s.append(now() - t)
        t += epoch_s[-1]
        if t - window.t_open + epoch_s[-1] > window.seconds:
            break
    state = jax.block_until_ready(state)
    window.close()
    after = counted(registry)
    n_steps = len(losses)
    in_window = [float(x) for x in jax.device_get(losses)]
    del losses[:]
    counters.update({k: after[k] - before.get(k, 0) for k in after})
    counters["window_s"] = window.elapsed
    if "trainer.steps" in after:
        in_order = in_order and counters["trainer.steps"] == n_steps

    trace_path = op_names_path = None
    if run.trace:
        traced = loader_of(traffic["trace_steps"])
        with loop_capture.capture(run.trace_dir) as captured:
            t = now()
            with profiler.span("window"):
                state, _interrupted, _done = train_epoch(
                    traced, mesh, state, step, epoch, logger
                )
                with profiler.span("fence"):
                    state = jax.block_until_ready(state)
            traced_s = now() - t
        trace_path = captured["path"]
        del losses[:]
        run.say(
            f"traced epoch: {len(traced)} steps in {traced_s:.3f} s, "
            f"{traced_s * 1e3 / len(traced):.3f} ms a step under the capture "
            f"against {window.elapsed * 1e3 / n_steps:.3f} in the window"
        )
    counters["compiles_in_window"] = run.compiles_since_open()
    if run.trace:
        # the trace names HLO instructions; their scopes are in the program's
        # own HLO text (a second lowering, served from the compile cache)
        hlo = lowered.train_step.lower(state, last_batch).compile().as_text()
        op_names_path = os.path.join(run.trace_dir, "op_names.json")
        with open(op_names_path, "w") as f:
            json.dump(trace.op_names_from_hlo(hlo), f)
        events, found = loop_capture.load_capture(
            trace_path, trace.load_op_names(op_names_path)
        )
        reduction = trace.Reduction(events)
        counters.update(loop_capture.reduce_loop(found, reduction))
        say_traced_epoch(run, found, reduction, counters)

    k = max(1, traffic["epoch_steps"] // 4)
    finite = [bool(np.isfinite(x)) for x in in_window]
    head, tail = float(np.mean(in_window[:k])), float(np.mean(in_window[-k:]))
    learned = all(finite) and tail < head
    run.say(
        f"window: {n_steps} steps in {window.elapsed:.3f} s, {len(epoch_s)} "
        f"epoch(s) of {len(loader)} ({', '.join(f'{s:.3f}' for s in epoch_s)} s); "
        f"loss, mean of {k}: {head:.4f} -> {tail:.4f}"
    )
    if "trainer.steps" in counters:
        per_step = counters["trainer.h2d_bytes"] / max(1, counters["trainer.steps"])
        run.say(
            "counters over the window: " + ", ".join(
                f"{name} {counters[name]:.6g}" for name in COUNTERS
            ) + f"; {per_step / 1e6:.3f} MB a step over the wire"
        )
    peak, limit = base.device_memory(devices[:chips])
    run.say(f"memory: peak {peak / 2**30:.2f} GiB of {limit / 2**30:.2f} GiB "
            "on the fullest chip")
    counters["trace_steps"] = traffic["trace_steps"] if run.trace else 0
    return Observation(
        correct=bool(agrees and identical and in_order and learned),
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


def say_traced_epoch(run, found, reduction, counters) -> None:
    """Earlier lines of a traced run: the spans' totals, the device's idle
    time by cause and its ten longest gaps under the PROGRAM's span names
    (the result line's ``breakdown.idle_gaps`` knows ``bench.*`` only)."""
    for name, row in sorted(counters["program_spans"].items()):
        run.say(
            f"span {name}: {row['count']} x, total {row['total_s'] * 1e3:.3f} "
            f"ms, self {row['self_s'] * 1e3:.3f} ms"
        )
    idle = counters["idle_s"]
    if idle:
        run.say(
            f"device idle {idle['idle'] * 1e3:.3f} ms of a traced window of "
            f"{idle['window'] * 1e3:.3f} ms, by cause: " + ", ".join(
                f"{cause} {idle[cause] * 1e3:.3f}"
                for cause in loop_capture.CAUSES
            )
        )
        gaps = program_spans.ProgramSpans(found).idle_gaps(
            reduction, 10, *loop_capture.window_of(reduction)
        )
        run.say("longest device-idle gaps: " + "; ".join(
            f"{name} {seconds * 1e3:.3f} ms" for name, seconds in gaps
        ))
    busy = loop_capture.workers_busy_share_of_wait(found)
    if busy is not None:
        run.say(f"workers busy for {busy:.1%} of the loop's wait time")
