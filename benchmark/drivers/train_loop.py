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
  learning rate set as ``train_epoch`` sets it); then the warm-up epoch
  through ``train_epoch`` from a copy of the same state, the one compiled
  step and state that the window goes on with. Of its first ``follow_steps``
  steps the driver keeps, on the host, the batch as the step got it, the
  loss, the momentum buffer and the BatchNorm statistics after the first and
  the parameters after the last (:class:`FirstSteps`).
* window: whole epochs of ``epoch_steps`` back to back for as long as the
  next one would still end inside ``--seconds`` (never fewer than one); ends
  in ``block_until_ready`` on the state. No JSONL sink, no ``metrics.jsonl``,
  no profiler.
* traced run: after the window, one further epoch of ``trace_steps`` under
  ``harness/loop_capture.capture`` (the Python tracer off).
* after the window, the memory reading and the state's release: the plain
  float32 reference (``reference/sgd_steps.py``) follows those first steps
  from the same initial weights, on batches it draws ITSELF from the pool
  (each kept row is looked up by its first pixel row; a row that is not the
  pool's is a number of its own), and ``correct`` is decided.

``attempted`` = steps dispatched in the window, ``failed`` = those with a
non-finite loss. ``correct``: every number compared is within its limit
(``compared``, in the result's line and the last lines of standard error).
Against the reference, each a gap of NORMS on the median leaf: the BatchNorm
statistics' move in the first step (forward only: the number that follows
the precision), the first gradient as the optimizer got it
(the momentum buffer after one step less the weight decay) and the
parameters' change after the last followed step; limits from the traffic
file, set between the program's readings over a dozen seeds and the
control's and the planted faults' (PERF.md section 2, which also says why the
losses and the worst leaves are read and not compared). Exact,
limit 0: rows not from the pool; whether the state after the warm-up epoch
differs in any bit from the plain loop's (every batch trained once, in
order); batches assembled that ``trainer.steps`` or the loop's own count
missed; non-finite losses.
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

from benchmark.harness import loop_capture, profiler, trace
from benchmark.harness.clock import Window, now
from benchmark.harness.observation import Observation
from benchmark.reference import sgd_steps

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


def momentum_of(opt_state):
    """The SGD momentum buffer of the program's optimizer state."""
    def holds(x):  # optax's TraceState; an array has a ``trace`` method too
        return isinstance(x, tuple) and hasattr(x, "trace")

    found = [
        x.trace for x in jax.tree.leaves(opt_state, is_leaf=holds) if holds(x)
    ]
    if len(found) != 1:
        raise ValueError("the optimizer state holds no single momentum buffer")
    return found[0]


class FirstSteps:
    """The step the warm-up epoch dispatches, which keeps on the host what
    the reference follows: of each of the first ``n`` steps the batch as the
    step got it and the loss, the momentum buffer after the first, the
    parameters after the last. Each read waits for its step (the state is
    donated to the next one); after ``n`` steps it only passes through."""

    def __init__(self, step, n: int):
        self.step, self.n = step, n
        self.batches, self.losses = [], []
        self.momentum = self.statistics = self.params = None

    def __call__(self, state, batch):
        state, metrics = self.step(state, batch)
        i = len(self.losses)
        if i < self.n:
            self.batches.append(
                jax.device_get({k: batch[k] for k in ("image", "label")})
            )
            self.losses.append(float(jax.device_get(metrics["loss"])))
            if i == 0:
                self.momentum, self.statistics = jax.device_get(
                    (momentum_of(state.opt_state), state.batch_stats)
                )
            if i == self.n - 1:
                self.params = jax.device_get(state.params)
        return state, metrics

    def observed(self, before, stats, weight_decay: float) -> dict:
        """Losses, the statistics' move, first gradient and change, as
        ``sgd_steps.follow`` gives the reference's."""
        return {
            "loss": self.losses,
            "statistics": jax.tree.map(lambda a, b: a - b, self.statistics, stats),
            "gradient": jax.tree.map(
                lambda m, p: m - weight_decay * p, self.momentum, before
            ),
            "change": jax.tree.map(lambda a, b: a - b, self.params, before),
        }


def drawn_from_pool(images, labels, batches) -> tuple[list, int]:
    """The batches as the REFERENCE draws them: each kept row is looked up in
    the pool by its first row of pixels and the pool's own image and label
    take its place. Returns them and how many rows were not the pool's (such
    a row keeps the program's pixels, so the losses differ too)."""
    index = {images[j, 0].tobytes(): j for j in range(len(images))}
    drawn, strangers = [], 0
    for batch in batches:
        rows = [index.get(row[0].tobytes()) for row in batch["image"]]
        strangers += rows.count(None)
        drawn.append({
            "image": np.stack([
                batch["image"][i] if j is None else images[j]
                for i, j in enumerate(rows)
            ]),
            "label": np.asarray([
                batch["label"][i] if j is None else labels[j]
                for i, j in enumerate(rows)
            ], np.int32),
        })
    return drawn, strangers


def reference_follows(run, before, stats, batches, bn_group: int) -> dict:
    """The plain float32 reference over the followed steps, on one device."""
    reference = run.catalog.reference(run.cell.config["reference"])
    architecture = run.section("architecture")
    return sgd_steps.follow(
        sgd_steps.follower(
            reference, architecture, run.traffic["reference_sgd"], bn_group
        ),
        sgd_steps.statistics_after(reference, architecture, bn_group),
        before, stats, batches,
    )


def run(run) -> Observation:
    return drive(run)


def drive(run, sabotage=None) -> Observation:
    """One run. ``sabotage`` (the tests' hook, never the benchmark's) takes
    the program's compiled step and returns the step to dispatch instead."""
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
    train_step = sabotage(lowered.train_step) if sabotage else lowered.train_step
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
    before, stats = jax.device_get((state.params, state.batch_stats))
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

    # -------------------------------------------------------- the plain loop
    warm = loader_of(traffic["warmup_steps"])
    warm.set_epoch(0)
    set_lr(twin.opt_state, get_epoch_lr(0))
    last_batch = None
    for hb in warm:
        last_batch = sharding_lib.shard_batch(mesh, hb)
        twin, _metrics = train_step(twin, last_batch)
    twin = jax.block_until_ready(twin)
    run.mark("step program and plain loop")

    # ----------------------------------------------- the loop: warm-up epoch
    losses = []

    def step(state, batch):
        state, metrics = train_step(state, batch)
        losses.append(metrics["loss"])
        return state, metrics

    first = FirstSteps(step, traffic["follow_steps"])
    counted_before, pulled = counted(registry), warm.dataset.served
    state, _interrupted, done = train_epoch(warm, mesh, state, first, 0, logger)
    state = jax.block_until_ready(state)
    pulled = (warm.dataset.served - pulled) // global_batch
    differs = not states_identical(state, twin)
    counted_after = counted(registry)
    missed = abs(done - pulled) + abs(len(warm) - pulled)
    if "trainer.steps" in counted_after:
        missed += abs(
            counted_after["trainer.steps"]
            - counted_before.get("trainer.steps", 0) - pulled
        )
    run.say(
        f"loop against the plain loop after {done} steps: state bit-identical "
        f"{not differs}; batches assembled {pulled}, missed by the loop's "
        f"counts {int(missed)}"
        f"{'' if 'trainer.steps' in counted_after else ' (this program has no trainer.steps)'}"
        f": {'DIFFERS' if differs or missed else 'every batch once, in order'}"
    )
    del twin, losses[:]
    run.mark("warm-up epoch")

    # ---------------------------------------------------------------- window
    loader = loader_of(traffic["epoch_steps"])
    window, epoch, epoch_s = Window(run.seconds), 1, []
    counted_before = counted(registry)
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
    counted_after = counted(registry)
    n_steps = len(losses)
    in_window = [float(x) for x in jax.device_get(losses)]
    del losses[:]
    counters.update(
        {k: counted_after[k] - counted_before.get(k, 0) for k in counted_after}
    )
    counters["window_s"] = window.elapsed
    if "trainer.steps" in counted_after:
        missed += abs(counters["trainer.steps"] - n_steps)

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

    k = max(1, min(n_steps, 128) // 4)
    finite = [bool(np.isfinite(x)) for x in in_window]
    run.say(
        f"window: {n_steps} steps in {window.elapsed:.3f} s, {len(epoch_s)} "
        f"epoch(s) of {len(loader)} ({', '.join(f'{s:.3f}' for s in epoch_s)} s); "
        f"loss, mean of {k}: {float(np.mean(in_window[:k])):.4f} -> "
        f"{float(np.mean(in_window[-k:])):.4f}"
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

    # ------------------ the reference follows the first steps; the comparison
    del state, last_batch
    t = now()
    drawn, strangers = drawn_from_pool(images, labels, first.batches)
    got = first.observed(
        before, stats, traffic["reference_sgd"]["weight_decay"]
    )
    want = reference_follows(run, before, stats, drawn, job["per_chip_batch"])
    limits = traffic["limits"]
    compared = {
        name: {"value": value, "limit": limits[name]}
        for name, value in sgd_steps.gaps(got, want).items()
    }
    for name, value in (
        ("rows_not_from_pool", strangers),
        ("state_bits_differ_from_plain_loop", int(differs)),
        ("batches_missed_by_the_counts", int(missed)),
        ("nonfinite_losses", finite.count(False)),
    ):
        compared[name] = {"value": value, "limit": 0}
    run.say("reference: read and not compared: " + ", ".join(
        f"{name} {value:.3g}{' on ' + where if where else ''}"
        for name, (value, where) in sgd_steps.others(got, want).items()
    ))
    run.say(
        f"reference: {len(drawn)} steps followed in {now() - t:.1f} s; losses "
        f"{', '.join(f'{x:.6f}' for x in got['loss'])} against plain float32 "
        f"{', '.join(f'{x:.6f}' for x in want['loss'])}"
    )
    return Observation(
        correct=all(c["value"] <= c["limit"] for c in compared.values()),
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
        compared=compared,
    )
