"""Driver ``lm_dense_train_step``: ``lm_train_step``'s shape of run for a
decoder LM without experts, whose step reports the terms of a looped model's
expected-exit loss (``models/ouro.py``).

Everything the two share is ``lm_train_step``'s own, loaded by name: the seed's
key, the program's configuration and step (config -> mesh -> topology -> model
-> ``lower``), the batch of uniform token ids made on the device, the first
step's check (the gradient it applied, read back from AdamW's first moment,
against the reference's on every leaf; its parameters and second moment
against a plain AdamW step), the window's memory and the kernels a traced run
must hold. What differs is here: the reference's terms and the comparison.

* set-up: weights and batch from ``--seed``; the FIRST step of the fresh state
  against the float32 reference's gradient on the whole batch; after the
  ``warmup_steps`` the next step's ``ce``, every ``ce_pass_t``,
  ``exit_entropy``, ``exit_step_mean`` and ``loss`` against the reference on
  the very same weights.
* window: chunks of ``chunk_steps`` steps, one always queued behind the one
  that runs, until ``--seconds`` have passed; ends in a fence on the state.
* traced run: after the window, ``trace_steps`` further steps under the
  profiler.

``attempted`` = steps in the window, ``failed`` = steps with a non-finite
loss. ``correct``: every term, the gradient and the update within their
tolerances of the reference, every loss finite, the loss lower at the
window's end than at its start and, in a traced run, every kernel of
``train_job.trace_kernels`` in the trace.

A program without this configuration's arch (the parent of the PR that added
it) is refused before the device is touched.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from distribuuuu_tpu.asyncplane.compile_cache import setup_from_cfg
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu.trainer import create_train_state

from benchmark.harness import profiler, stats, trace
from benchmark.harness.clock import Window, now
from benchmark.harness.observation import Observation

SCALARS = ("ce", "exit_entropy", "exit_step_mean", "loss")


def compile_only(run, devices) -> dict:
    return run.catalog.driver("lm_train_step").compile_only(run, devices)


# ------------------------------------------------------------- the reference
def reference_terms(reference, architecture, params, tokens, labels,
                    precision=jnp.float32):
    """``(terms, gradient of the loss)`` of the reference on the whole
    batch, one sequence after the other: every term is a mean over tokens
    and the sequences are as long as each other, so the batch's terms and
    gradient are the means of the sequences' (the cell has one sequence,
    and nothing is added)."""

    def total(p, i):
        terms = reference.loss(
            p, tokens[i:i + 1], labels[i:i + 1], architecture=architecture,
            precision=precision,
        )
        return terms["loss"], {k: terms[k] for k in (*SCALARS, "ce_pass")}

    per = [jax.value_and_grad(total, has_aux=True)(params, i)
           for i in range(tokens.shape[0])]
    return jax.tree.map(
        lambda *x: sum(x) / len(x), *[(terms, grads) for (_, terms), grads in per])


class Reference:
    """The configuration's plain reference on the cell's batch, on one
    device. ONE program, compiled once, gives the terms and the gradient:
    the first step reads the gradient, the step after the warm-up the terms
    (and drops a gradient it did not need: seconds, against a second
    compilation of the same forward). ``low`` is the terms in bfloat16
    throughout: the nearest precision below the configuration's, which must
    NOT pass."""

    def __init__(self, run, batch):
        fixed = (run.catalog.reference(run.cell.config["reference"]),
                 run.section("architecture"))
        self.device = jax.devices()[0]
        self.batch = jax.device_put((batch["image"], batch["label"]), self.device)
        self.both = jax.jit(lambda *a: reference_terms(*fixed, *a))
        self.low = jax.jit(lambda *a: reference_terms(*fixed, *a, jnp.bfloat16)[0])

    def first_step(self, params) -> tuple:
        """(params, the reference's gradient on them), both on the host: the
        step donates the first, and the two do not fit the chip beside the
        step's temporaries."""
        params = jax.device_put(params, self.device)
        return jax.device_get((params, self.both(params, *self.batch)[1]))

    def step(self, params, teeth: bool) -> dict:
        params = jax.device_put(params, self.device)
        out = {"want": self.both(params, *self.batch)[0]}
        if teeth:  # by hand: --set traffic.reference_teeth=true
            out["low"] = self.low(params, *self.batch)
        return jax.device_get(out)


def term_errors(got: dict, want: dict) -> dict:
    """Relative error of each term against the reference's (over at least
    1, as ``lm_train_step`` has it); ``ce_pass`` is the worst pass's."""

    def relative(a, b):
        return abs(float(a) - float(b)) / max(1.0, abs(float(b)))

    errors = {k: relative(got[k], want[k]) for k in SCALARS}
    errors["ce_pass"] = max(
        relative(a, b) for a, b in zip(got["ce_pass"], want["ce_pass"]))
    return errors


def compare(run, job, expected, metrics, errors) -> bool:
    """The timed program against the reference: ``metrics`` of a step on the
    weights ``expected`` was computed on, ``errors`` of its first step."""
    want, tolerance, agrees = expected["want"], job["reference_tolerance"], True
    got = {k: metrics[k] for k in SCALARS}
    got["ce_pass"] = [metrics[f"ce_pass_{t}"] for t in range(len(want["ce_pass"]))]
    for term, error in term_errors(got, want).items():
        ok = error <= tolerance[term]
        agrees &= ok
        run.say(
            f"reference: {term} step {np.round(np.asarray(got[term], np.float64), 7)} "
            f"vs plain float32 {np.round(np.asarray(want[term], np.float64), 7)} "
            f"(relative {error:.2e}, tolerance {tolerance[term]}): "
            f"{'agrees' if ok else 'DISAGREES'}"
        )
    run.say("reference: gradient of the first step, relative, leaf by leaf: " + ", ".join(
        f"{path} {e['gradient']:.1e}" for path, e in errors.items()))
    for kind, against in (
        ("gradient", "the reference's"),
        ("update", "a plain AdamW step on the gradient it applied"),
        ("second_moment", "that step's"),
    ):
        path = max(errors, key=lambda p: errors[p][kind])
        ok = errors[path][kind] <= tolerance[kind]
        agrees &= ok
        run.say(
            f"reference: {kind} of the first step against {against}, worst of "
            f"{len(errors)} leaves {path}: relative {errors[path][kind]:.2e} "
            f"(tolerance {tolerance[kind]}): {'agrees' if ok else 'DISAGREES'}"
        )
    if "low" in expected:
        for term, error in term_errors(expected["low"], want).items():
            run.say(
                f"teeth: {term} reference in bfloat16 "
                f"{np.round(np.asarray(expected['low'][term], np.float64), 7)} "
                f"(relative {error:.2e}, tolerance {tolerance[term]}): "
                f"{'would PASS' if error <= tolerance[term] else 'fails, as it must'}"
            )
    return bool(agrees)


def run(run) -> Observation:
    base = run.catalog.driver("lm_train_step")
    chips = run.cell.chips
    run.mark("imports")
    base.refuse_without_arch(run)  # before the chip is touched
    devices = jax.devices()
    run.mark("reach the device")
    run.admit_device(devices[0].platform, devices[0].device_kind, len(devices))
    run.compiles.install()
    lowered, job, _state, avals = base.build(run, chips, devices[:chips])
    setup_from_cfg(cfg)
    traffic = run.traffic

    batch = base.make_batch(run.seed, avals, cfg.MODEL.NUM_CLASSES)
    state = create_train_state(
        lowered.model, base.seed_key(run.seed), lowered.mesh, cfg.TRAIN.IM_SIZE,
        layout=lowered.layout,
    )
    jax.block_until_ready((state, batch))
    run.mark("weights and batch")
    counters = {
        "param_bytes": sum(
            x.size * x.dtype.itemsize for x in jax.tree.leaves(state.params)
        ),
        # AdamW keeps two moments in the parameters' layout
        "moment_bytes": sum(
            x.size * x.dtype.itemsize
            for x in jax.tree.leaves(state.opt_state) if x.ndim > 0
        ),
        "tokens_per_step": int(np.prod(avals["image"].shape)),
    }
    seen = []  # per step: loss, exit_step_mean

    def steps(state, n, annotate=False):
        for _ in range(n):
            if annotate:
                with profiler.span("dispatch"):
                    state, metrics = lowered.train_step(state, batch)
            else:
                state, metrics = lowered.train_step(state, batch)
            seen.append([metrics["loss"], metrics["exit_step_mean"]])
        return state

    # the FIRST step of the fresh state (zero moments), the program the
    # window times on the batch it times it on: the gradient it applied and
    # its AdamW arithmetic (fenced: its temporaries and the reference's two
    # trees do not fit the chip together)
    reference = Reference(run, batch)
    before = reference.first_step(state.params)
    run.mark("reference gradient")
    state, _ = jax.block_until_ready(lowered.train_step(state, batch))
    layout = jax.tree.map(lambda x: x.sharding, state.params)
    errors = base.first_step_errors(
        job["adamw"], job["lr"], *jax.device_put(before, (layout, layout)), state
    )
    del before
    # after the warm-up: the program's next terms against the reference on
    # the very same weights
    state = jax.block_until_ready(steps(state, traffic["warmup_steps"] - 1))
    expected = reference.step(state.params, bool(traffic.get("reference_teeth")))
    state, metrics = lowered.train_step(state, batch)
    agrees = compare(run, job, expected, jax.device_get(metrics), errors)
    del seen[:], expected, reference
    run.mark("step program, warm-up, the step against the reference")

    # ---------------------------------------------------------------- window
    # as lm_train_step: one chunk always queued behind the one that runs; the
    # host waits for the previous chunk's last loss, never for the state
    window = Window(run.seconds)
    chunk, chunk_s = traffic["chunk_steps"], []
    run.open_window()
    t = window.open()
    state = steps(state, chunk)
    while not window.expired():
        state = steps(state, chunk)
        jax.block_until_ready(seen[-chunk - 1][0])
        chunk_s.append(now() - t)
        t += chunk_s[-1]
    state = jax.block_until_ready(state)
    window.close()
    chunk_s.append(now() - t)
    n_steps = len(seen)
    losses, exit_step = np.asarray(jax.device_get(seen), np.float64).T

    trace_path = op_names_path = None
    missing = []
    if run.trace:
        with profiler.capture(run.trace_dir) as captured:
            with profiler.span("window"):
                state = steps(state, traffic["trace_steps"], annotate=True)
                with profiler.span("fence"):
                    state = jax.block_until_ready(state)
        trace_path = captured["path"]
        missing = base.kernels_missing(job, trace_path)
        if job.get("trace_kernels"):
            run.say(f"trace: kernels {job['trace_kernels']}: "
                    f"{'all there' if not missing else f'MISSING {missing}'}")
    counters["compiles_in_window"] = run.compiles_since_open()
    if run.trace:
        hlo = lowered.train_step.lower(state, batch).compile().as_text()
        op_names_path = os.path.join(run.trace_dir, "op_names.json")
        with open(op_names_path, "w") as f:
            json.dump(trace.op_names_from_hlo(hlo), f)

    per_chunk = [c / chunk * 1e3 for c in chunk_s]
    q1, med, q3 = stats.quartiles(per_chunk)
    run.say(
        f"window: {n_steps} steps of {counters['tokens_per_step']} tokens in "
        f"{window.elapsed:.3f} s; ms/step over {len(chunk_s)} chunks of "
        f"{chunk}: q1 {q1:.3f} median {med:.3f} q3 {q3:.3f}; loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}; mean exit step "
        f"{exit_step[0]:.4f} -> {exit_step[-1]:.4f}"
    )
    finite = np.isfinite(losses)
    learned = bool(finite.all() and losses[-1] < losses[0])
    counters["exit_step_mean"] = float(exit_step.mean())
    counters["trace_steps"] = traffic["trace_steps"] if run.trace else 0

    peak, limit = base.device_memory(devices[:chips])
    run.say(f"memory: peak {peak / 2**30:.2f} GiB of {limit / 2**30:.2f} GiB "
            "on the fullest chip")
    return Observation(
        correct=bool(agrees and learned and not missing),
        attempted=n_steps,
        failed=int((~finite).sum()),
        end_to_end={
            "train_items_per_s_per_chip":
                n_steps * counters["tokens_per_step"] / window.elapsed / chips,
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
