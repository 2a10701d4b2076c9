"""Driver ``lm_pattern_train_step``: ``lm_share_train_step``'s shape of run
(one chip's share of an expert-parallel group, a router whose balancing bias
is state the step moves by a rule) for a decoder whose layers are of two
kinds by a published pattern and whose loss is ONE head's cross-entropy and
the balancing term (``models/lfm2_moe.py``): no multi-token-prediction head,
which ``lm_share_train_step`` names among its terms, and mixtures from
``architecture.num_dense_layers`` on.

Everything it shares is the two accepted drivers' own, loaded by name:
``lm_train_step``'s seed key, program (config -> mesh -> topology -> model ->
``lower``), first-step check, memory and traced kernels, and
``lm_share_train_step``'s batch (ids over the rows of the vocabulary this
chip holds), ``Reference`` (float32 terms, routing and gradient; the
bfloat16 control), routing agreement, bias check and gradient classes. What
differs is here: which blocks hold a mixture, the terms, and the comparison,
which hands every number it compared beside its limit to the harness
(``Observation.compared``: the result line's last key and the last lines of
standard error).

* set-up: weights and batch from ``--seed``; the FIRST step of the fresh
  state against the float32 reference's gradient on the whole batch, and the
  biases it left against the rule applied to the reference's own counts;
  after the ``warmup_steps`` the next step's ``ce``, ``moe_aux``, ``loss``
  and ``moe_held_row_share`` against the reference on the very same weights
  and biases, and the experts the model's routers choose there against the
  reference's.
* window: chunks of ``chunk_steps`` steps, one always queued behind the one
  that runs, until ``--seconds`` have passed; ends in a fence on the state.
* traced run: after the window, ``trace_steps`` further steps under the
  profiler; the counter ``moe_held_row_share`` is then THOSE steps' mean (the
  share moves through a window, and the readers divide the held rows' work
  by the traced steps' time), in an untraced run the window's.

``attempted`` = steps in the window, ``failed`` = steps with a non-finite
loss. ``correct``: every number of ``compared`` within its limit (the terms,
the held share, the three gradient classes, the update and second moment,
the routing's and the biases' disagreement and margins, non-finite losses,
dropped rows, a loss that did not fall, traced kernels missing).

``traffic.reference_teeth`` (by hand) also runs the reference in bfloat16
throughout and says, number by number, whether it would pass: the control
that shows the limits part a precision below the configuration's.

A program without this configuration's arch (the parent of the PR that added
it) is refused before the device is touched.
"""

from __future__ import annotations

import json
import os

import jax
import numpy as np

from distribuuuu_tpu.asyncplane.compile_cache import setup_from_cfg
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu.trainer import create_train_state

from benchmark.harness import profiler, stats, trace
from benchmark.harness.clock import Window, now
from benchmark.harness.observation import Observation

TERMS = ("ce", "load_balance", "loss")
STEP_METRIC = {"ce": "ce", "load_balance": "moe_aux", "loss": "loss"}
SEEN = ("loss", "moe_dropped", "moe_load_max_over_mean", "moe_held_row_share")


def compile_only(run, devices) -> dict:
    return run.catalog.driver("lm_train_step").compile_only(run, devices)


def share_architecture(architecture: dict) -> dict:
    """``architecture`` with the three keys by which
    ``lm_share_train_step``'s helpers (``mixture_names``, ``Reference``,
    ``bias_errors``) name the blocks that hold a mixture: every layer that is
    run from ``num_dense_layers`` on, and no MTP module."""
    a = architecture
    return {**a, "layers": len(a["layer_types"]),
            "first_k_dense_replace": a["num_dense_layers"],
            "num_nextn_predict_layers": 0}


class AsShare:
    """A run as ``lm_share_train_step.Reference`` reads it: its catalog, its
    cell, and the architecture under :func:`share_architecture`'s keys."""

    def __init__(self, run):
        self.catalog, self.cell, self._section = run.catalog, run.cell, run.section

    def section(self, name: str) -> dict:
        values = self._section(name)
        return share_architecture(values) if name == "architecture" else values


def gradient_classes(share, architecture: dict, errors) -> dict:
    """``lm_share_train_step.gradient_classes`` (routers / routed experts /
    the rest, by how a routing flip reaches a leaf's gradient) with the
    mixtures' ``ffn_norm`` scales counted among the experts: this mixture has
    no shared expert, so ALL that passes such a norm goes to routed experts,
    and a (token, slot) pair that went to another expert than the reference's
    (or from a held one to an absent one) takes a whole row out of the norm's
    gradient as it does out of an expert's. GLM's mixture norms read like the
    rest because its shared expert carries every token whatever the router
    chose."""
    classes = share.gradient_classes(errors)
    norms = {f"['{name}']['ffn_norm']['scale']"
             for name in share.mixture_names(share_architecture(architecture))}
    classes["gradient_experts"] += [p for p in classes["gradient"] if p in norms]
    classes["gradient"] = [p for p in classes["gradient"] if p not in norms]
    return classes


def numbers(run, job, want, metrics, chosen, errors=None, bias=None) -> dict:
    """Every number the comparison holds to a limit, name -> {value, limit}
    (within its limit: value <= limit): ``metrics`` (the step's, or another
    reference's terms) and ``chosen`` (experts ``[mixtures, T, k]``) against
    the float32 reference ``want``; with ``errors`` and ``bias`` the first
    step's gradient classes, update, second moment and biases too."""
    share = run.catalog.driver("lm_share_train_step")
    tolerance = job["reference_tolerance"]
    out = {}
    for term in TERMS:
        ref = float(want[term])
        out[term] = {
            "value": abs(float(metrics[term]) - ref) / max(1.0, abs(ref)),
            "limit": tolerance[term]}
    out["held_row_share"] = {
        "value": abs(float(metrics["held_row_share"]) - float(want["held_row_share"])),
        "limit": tolerance["held_row_share"]}
    same, margin = share.routing_agreement(np.asarray(chosen), want)
    out["experts_disagreeing"] = {
        "value": 1.0 - same, "limit": 1.0 - job["expert_agreement_min"]}
    out["expert_tie_margin"] = {"value": margin, "limit": job["expert_tie_margin"]}
    if errors is not None:
        classes = gradient_classes(share, run.section("architecture"), errors)
        for limit, leaves in classes.items():
            out[limit] = {"value": max(errors[p]["gradient"] for p in leaves),
                          "limit": tolerance[limit]}
        for kind in ("update", "second_moment"):
            out[kind] = {"value": max(e[kind] for e in errors.values()),
                         "limit": tolerance[kind]}
    if bias is not None:
        same, off = bias
        out["biases_disagreeing"] = {
            "value": 1.0 - same, "limit": 1.0 - job["bias_agreement_min"]}
        out["bias_count_margin"] = {"value": off, "limit": job["bias_count_margin"]}
    return out


def compare(run, job, expected, metrics, errors, bias) -> dict:
    """The timed program against the reference: ``metrics`` of a step on the
    weights and biases ``expected`` was computed on, ``errors`` and ``bias``
    of its first step. Says every number and returns them."""
    want = expected["want"]
    step = {term: metrics[STEP_METRIC[term]] for term in TERMS}
    step["held_row_share"] = metrics["moe_held_row_share"]
    compared = numbers(run, job, want, step, expected["chosen"], errors, bias)
    run.say("reference: gradient of the first step, relative, leaf by leaf: " + ", ".join(
        f"{path} {e['gradient']:.1e}" for path, e in errors.items()))
    for term in TERMS:
        run.say(f"reference: {term} step {float(step[term]):.7f} vs plain float32 "
                f"{float(want[term]):.7f}")
    for name, c in compared.items():
        run.say(f"reference: {name} {c['value']:.3e} (limit {c['limit']}): "
                f"{'agrees' if c['value'] <= c['limit'] else 'DISAGREES'}")
    if "low" in expected:
        low = expected["low"]
        teeth = numbers(run, job, want, low, low["experts"])
        failed = [n for n, c in teeth.items() if c["value"] > c["limit"]]
        for name, c in teeth.items():
            run.say(
                f"teeth: {name} of the reference in bfloat16 {c['value']:.3e} "
                f"(limit {c['limit']}): "
                f"{'would PASS' if c['value'] <= c['limit'] else 'fails, as it must'}")
        run.say(f"teeth: the reference in bfloat16 throughout fails {len(failed)} "
                f"of {len(teeth)} limits: {failed}")
    return compared


def run(run) -> Observation:
    base = run.catalog.driver("lm_train_step")
    share = run.catalog.driver("lm_share_train_step")
    chips = run.cell.chips
    run.mark("imports")
    base.refuse_without_arch(run)  # before the chip is touched
    devices = jax.devices()
    run.mark("reach the device")
    run.admit_device(devices[0].platform, devices[0].device_kind, len(devices))
    run.compiles.install()
    lowered, job, _state, avals = base.build(run, chips, devices[:chips])
    setup_from_cfg(cfg)
    traffic, architecture = run.traffic, run.section("architecture")

    batch = share.make_batch(
        base.seed_key(run.seed), avals,
        architecture["share_rank"] * architecture["vocab_held"],
        architecture["vocab_held"],
    )
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
    seen = []  # per step: SEEN

    def steps(state, n, annotate=False):
        for _ in range(n):
            if annotate:
                with profiler.span("dispatch"):
                    state, metrics = lowered.train_step(state, batch)
            else:
                state, metrics = lowered.train_step(state, batch)
            seen.append([metrics[k] for k in SEEN])
        return state

    # the FIRST step of the fresh state (zero moments, zero biases), the
    # program the window times on the batch it times it on. Fenced, and the
    # optimizer's moments wait on the host meanwhile, as
    # lm_share_train_step's: the reference's gradient and its backward want
    # the room
    reference = share.Reference(AsShare(run), batch)
    moments = jax.tree.map(lambda x: x.sharding, state.opt_state)
    aside = jax.device_get(state.opt_state)
    jax.tree.map(lambda x: x.delete(), state.opt_state)
    *before, counts = reference.first_step(state.params, state.batch_stats)
    state = state.replace(opt_state=jax.device_put(aside, moments))
    del aside
    run.mark("reference gradient")
    state, _ = jax.block_until_ready(lowered.train_step(state, batch))
    layout = jax.tree.map(lambda x: x.sharding, state.params)
    errors = base.first_step_errors(
        job["adamw"], job["lr"], *jax.device_put(tuple(before), (layout, layout)),
        state,
    )
    bias = share.bias_errors(
        share_architecture(architecture), counts, jax.device_get(state.batch_stats))
    del before
    # after the warm-up: the program's next terms against the reference on
    # the very same weights and biases
    state = jax.block_until_ready(steps(state, traffic["warmup_steps"] - 1))
    expected = reference.step(
        lowered.model, state.params, state.batch_stats,
        bool(traffic.get("reference_teeth")),
    )
    state, metrics = lowered.train_step(state, batch)
    compared = compare(run, job, expected, jax.device_get(metrics), errors, bias)
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
    losses, dropped, load, held = np.asarray(jax.device_get(seen), np.float64).T

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
    bias_max = float(max(
        np.abs(b).max() for b in jax.tree.leaves(jax.device_get(state.batch_stats))))
    run.say(
        f"window: {n_steps} steps of {counters['tokens_per_step']} tokens in "
        f"{window.elapsed:.3f} s; ms/step over {len(chunk_s)} chunks of "
        f"{chunk}: q1 {q1:.3f} median {med:.3f} q3 {q3:.3f}; loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}; moe_dropped max "
        f"{dropped.max():.3g}; expert load max/mean {load.mean():.3f}; share "
        f"of the choices on held experts {held.mean():.5f} (first step "
        f"{held[0]:.5f}, last {held[-1]:.5f}); largest bias {bias_max:.4f}"
    )
    finite = np.isfinite(losses)
    # exact: none may be over 0
    compared["losses_not_finite"] = {"value": float((~finite).sum()), "limit": 0}
    compared["loss_did_not_fall"] = {
        "value": float(not (finite.all() and losses[-1] < losses[0])), "limit": 0}
    compared["rows_dropped"] = {"value": float(np.nanmax(dropped)), "limit": 0}
    compared["traced_kernels_missing"] = {"value": float(len(missing)), "limit": 0}
    counters["moe_dropped"] = float(dropped.max())
    counters["moe_load_max_over_mean"] = float(load.mean())
    counters["moe_held_row_share"] = float(held.mean())
    counters["trace_steps"] = traffic["trace_steps"] if run.trace else 0
    if run.trace:
        # the share moves through a window (a rank's router comes to prefer
        # its own experts, then the bias pushes back): the readers that set
        # the held rows' work against the TRACED steps' time (kernels.
        # moe_held_roofline) need the share those steps had, and per-layer
        # metrics are printed by a traced run alone
        traced = np.asarray(jax.device_get(seen[n_steps:]), np.float64)[:, 3]
        counters["moe_held_row_share"] = float(traced.mean())
        run.say(f"trace: share of the choices on held experts over the "
                f"{len(traced)} traced steps {traced.mean():.5f} (the window's "
                f"mean {held.mean():.5f})")

    peak, limit = base.device_memory(devices[:chips])
    run.say(f"memory: peak {peak / 2**30:.2f} GiB of {limit / 2**30:.2f} GiB "
            "on the fullest chip")
    return Observation(
        correct=all(c["value"] <= c["limit"] for c in compared.values()),
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
        compared=compared,
    )
