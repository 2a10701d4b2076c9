"""Driver ``lm_share_train_step``: ``lm_train_step``'s shape of run for a
decoder that is ONE chip's share of an expert-parallel group
(``models/glm_moe.py``): a router whose balancing bias is state the step
moves by a rule, experts of which only some are held here, a second
(multi-token-prediction) head in the loss.

Everything it shares is ``lm_train_step``'s own, loaded by name: the seed's
key, the program's configuration and step (config -> mesh -> topology ->
model -> ``lower``), the first step's check (the gradient it applied, read
back from AdamW's first moment, against the reference's on every leaf; its
parameters and second moment against a plain AdamW step), the window's
memory and the kernels a traced run must hold. What differs is here: the batch (ids over the rows of the vocabulary
this chip holds), the reference's terms, which take the routers' biases
beside the parameters, and the comparison.

* set-up: weights and batch from ``--seed``; the FIRST step of the fresh
  state against the float32 reference's gradient on the whole batch, and the
  biases it left against the rule applied to the reference's own counts;
  after the ``warmup_steps`` the next step's ``ce``, ``ce_mtp``, ``moe_aux``,
  ``loss`` and ``moe_held_row_share`` against the reference on the very same
  weights and biases, and the experts the model's routers choose there
  against the reference's.
* window: chunks of ``chunk_steps`` steps, one always queued behind the one
  that runs, until ``--seconds`` have passed; ends in a fence on the state.
* traced run: after the window, ``trace_steps`` further steps under the
  profiler.

``attempted`` = steps in the window, ``failed`` = steps with a non-finite
loss. ``correct``: every term, the share of the choices that fell on held
experts, the gradient, the update and the biases within their tolerances of
the reference, the experts chosen equal in at least ``expert_agreement_min``
of the (token, slot) pairs, every loss finite, the loss lower at the
window's end than at its start, ``moe_dropped`` 0 in every step and, in a
traced run, every kernel of ``train_job.trace_kernels`` in the trace.

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

TERMS = ("ce", "ce_mtp", "load_balance", "loss")
STEP_METRIC = {"ce": "ce", "ce_mtp": "ce_mtp", "load_balance": "moe_aux",
               "loss": "loss"}
SEEN = ("loss", "moe_dropped", "moe_load_max_over_mean", "moe_held_row_share")


def compile_only(run, devices) -> dict:
    return run.catalog.driver("lm_train_step").compile_only(run, devices)


def make_batch(key, avals: dict, first: int, rows: int):
    """Token ids uniform over the ``rows`` rows of the vocabulary from
    ``first`` (this chip's), one jitted call on the device; labels are the
    inputs shifted by one."""
    batch, seq = avals["image"].shape

    def draw(key):
        ids = first + jax.random.randint(key, (batch, seq + 1), 0, rows, jnp.int32)
        return {"image": ids[:, :-1], "label": ids[:, 1:]}

    shardings = {k: v.sharding for k, v in avals.items()}
    return jax.jit(draw, out_shardings=shardings)(jax.random.fold_in(key, 1))


def mixture_names(architecture: dict) -> list:
    """The blocks that hold a mixture, in the reference's order: the
    trunk's, then the MTP module's."""
    a = architecture
    return [f"Block_{i}" for i in range(a["first_k_dense_replace"], a["layers"])
            ] + ["mtp_block"] * a["num_nextn_predict_layers"]


# ------------------------------------------------------------- the reference
def reference_terms(reference, architecture, params, biases, tokens, labels,
                    precision=jnp.float32):
    """``(terms and routing, gradient of the loss)`` of the reference on the
    whole batch."""

    def total(p):
        terms = reference.loss(
            p, biases, tokens, labels, architecture=architecture,
            precision=precision,
        )
        return terms["loss"], terms

    (_, terms), grads = jax.value_and_grad(total, has_aux=True)(params)
    return terms, grads


class Reference:
    """The configuration's plain reference on the cell's batch, on one
    device. ``both`` gives terms, routing and gradient for the first step
    (the gradient and the counts); ``terms`` the terms and the routing alone
    for the step after the warm-up: a program of its own, a forward's size,
    where the gradient is not wanted (the state leaves the chip 5.2 GB, and
    ``both`` takes most of it: 2.8 GB of gradient and a block's backward).
    ``low`` is the
    terms in bfloat16 throughout: the nearest precision below the
    configuration's, which must NOT pass."""

    def __init__(self, run, batch):
        self.architecture = run.section("architecture")
        fixed = (run.catalog.reference(run.cell.config["reference"]),
                 self.architecture)
        self.device = jax.devices()[0]
        self.tokens = batch["image"]  # where the step has them
        self.batch = jax.device_put((batch["image"], batch["label"]), self.device)
        self.both = jax.jit(lambda *a: reference_terms(*fixed, *a))
        self.terms, self.low = (
            jax.jit(lambda p, b, x, y, dtype=dtype: fixed[0].loss(
                p, b, x, y, architecture=fixed[1], precision=dtype))
            for dtype in (jnp.float32, jnp.bfloat16)
        )

    def first_step(self, params, biases) -> tuple:
        """(params, the reference's gradient on them, its counts [mixtures,
        E]), on the host: the step donates the first, and the trees do not
        fit the chip beside the step's temporaries."""
        params, biases = jax.device_put((params, biases), self.device)
        terms, grads = self.both(params, biases, *self.batch)
        return jax.device_get((params, grads, terms["counts"]))

    def program_experts(self, model, params, biases):
        """The experts the program's routers choose on the whole batch,
        ``[mixtures, T, k]``: a forward of the model's own modules, because
        the step reports its routing only as counts."""
        _, sown = model.apply(
            {"params": params, "batch_stats": biases}, self.tokens,
            hidden_only=True, mutable=["moe_route"],
        )
        experts = jnp.stack([
            sown["moe_route"][name]["moe"]["experts"][0]
            for name in mixture_names(self.architecture)
        ])
        return experts.reshape(experts.shape[0], -1, experts.shape[-1])

    def step(self, model, params, biases, teeth: bool) -> dict:
        out = {"chosen": jax.jit(
            lambda p, b: self.program_experts(model, p, b))(params, biases)}
        params, biases = jax.device_put((params, biases), self.device)
        out["want"] = self.terms(params, biases, *self.batch)
        if teeth:  # by hand: --set traffic.reference_teeth=true
            out["low"] = self.low(params, biases, *self.batch)
        return jax.device_get(out)


def routing_agreement(chosen, want: dict) -> tuple:
    """(share of the (token, slot) pairs of every mixture whose expert is
    also the reference's; the largest tie margin of a token that differs
    though all that fed it agreed). ``chosen`` and ``want["experts"]`` are
    ``[mixtures, T, k]`` in the reference's order, ``want["chosen_by"]``
    the reference's own biased scores ``[mixtures, T, E]``.

    The margin of a token is how far, in the reference's scores, the worst
    expert the program chose lies under the reference's k-th: small where
    rounding upstream broke a near-tie the other way, large where the
    routing itself is wrong. It is read only on a mixture's tokens whose
    choices agreed in EVERY earlier mixture: a token that went to another
    expert one layer up is another input here, and a far choice on it is
    the flip's consequence, not a second fault."""
    k = chosen.shape[-1]
    same = (chosen[..., :, None] == want["experts"][..., None, :]).any(-1)
    agreed = same.all(-1)  # [mixtures, T]
    fed_alike = np.concatenate(
        [np.ones_like(agreed[:1]), np.cumprod(agreed[:-1], axis=0).astype(bool)])
    kth = np.sort(want["chosen_by"], -1)[..., -k]
    worst = np.take_along_axis(want["chosen_by"], chosen, -1).min(-1)
    return float(same.mean()), float((kth - worst)[fed_alike].max())


def bias_errors(architecture, counts, biases) -> tuple:
    """(share of the bias entries the first step left where the rule puts
    them from the REFERENCE's counts; for the entries that differ, how far
    the reference's count lies from the mean, at most). The step starts from
    zero biases; a routing flip upstream moves a count by one, and only an
    expert whose count is within the flips of the mean can end on the other
    side of it."""
    counts = np.asarray(counts, np.float64)
    want = architecture["bias_update_rate"] * np.sign(
        counts.mean(-1, keepdims=True) - counts)
    got = np.stack([
        np.asarray(biases[name]["moe"]["router_bias"], np.float64)
        for name in mixture_names(architecture)
    ])
    same = np.isclose(got, want, rtol=0, atol=1e-9)
    off = np.abs(counts - counts.mean(-1, keepdims=True))[~same]
    return float(same.mean()), float(off.max()) if off.size else 0.0


def gradient_classes(errors) -> dict:
    """The leaves by how a routing flip reaches their gradient, each class
    under a limit of its own (``reference_tolerance``'s key -> its leaves): a
    (token, slot) pair that went to another expert than the reference's takes
    a whole row out of one expert's gradient and puts one into another's
    (``gradient_experts``), and changes the normalisation over the token's
    chosen experts, so all its router weights' cotangents
    (``gradient_router``); every other leaf sees the flip only through the
    token's changed output (``gradient``)."""
    classes = {"gradient_router": [], "gradient_experts": [], "gradient": []}
    for path in errors:
        routed = "['moe']['router']" in path, "['moe']['w_" in path
        classes["gradient_router" if routed[0] else
                "gradient_experts" if routed[1] else "gradient"].append(path)
    return classes


def compare(run, job, expected, metrics, errors, bias) -> bool:
    """The timed program against the reference: ``metrics`` of a step on the
    weights and biases ``expected`` was computed on, ``errors`` and ``bias``
    of its first step."""
    want, tolerance, agrees = expected["want"], job["reference_tolerance"], True

    def relative(got, term, against=None):
        ref = float((against or want)[term])
        return abs(float(got) - ref) / max(1.0, abs(ref))

    def say(what, error, limit):
        nonlocal agrees
        ok = error <= limit
        agrees &= ok
        run.say(f"reference: {what} (relative {error:.2e}, tolerance {limit}): "
                f"{'agrees' if ok else 'DISAGREES'}")

    for term in TERMS:
        got = float(metrics[STEP_METRIC[term]])
        say(f"{term} step {got:.7f} vs plain float32 {float(want[term]):.7f}",
            relative(got, term), tolerance[term])
    got = float(metrics["moe_held_row_share"])
    say(f"share of the (token, slot) choices on held experts, step {got:.6f} "
        f"vs plain float32 {float(want['held_row_share']):.6f}",
        abs(got - float(want["held_row_share"])), tolerance["held_row_share"])
    same, margin = routing_agreement(expected["chosen"], want)
    ok = same >= job["expert_agreement_min"] and margin <= job["expert_tie_margin"]
    agrees &= ok
    run.say(
        f"reference: experts chosen equal in {same:.5f} of the (token, slot) "
        f"pairs of the batch (at least {job['expert_agreement_min']}); where "
        f"a token differs whose earlier choices all agreed, the reference's own "
        f"biased scores are within {margin:.5f} of a tie (at most "
        f"{job['expert_tie_margin']}): "
        f"{'agrees' if ok else 'DISAGREES'}"
    )
    same, off = bias
    ok = same >= job["bias_agreement_min"] and off <= job["bias_count_margin"]
    agrees &= ok
    run.say(
        f"reference: the routers' biases after the first step equal the rule "
        f"on the reference's counts in {same:.5f} of the entries (at least "
        f"{job['bias_agreement_min']}); where they differ the reference's "
        f"count is within {off:.0f} of the mean (at most "
        f"{job['bias_count_margin']}): {'agrees' if ok else 'DISAGREES'}"
    )
    run.say("reference: gradient of the first step, relative, leaf by leaf: " + ", ".join(
        f"{path} {e['gradient']:.1e}" for path, e in errors.items()))
    for limit, leaves in gradient_classes(errors).items():
        path = max(leaves, key=lambda p: errors[p]["gradient"])
        say(f"{limit} of the first step against the reference's, worst of "
            f"{len(leaves)} leaves {path}", errors[path]["gradient"], tolerance[limit])
    for kind, against in (
        ("update", "a plain AdamW step on the gradient it applied"),
        ("second_moment", "that step's"),
    ):
        path = max(errors, key=lambda p: errors[p][kind])
        say(f"{kind} of the first step against {against}, worst of "
            f"{len(errors)} leaves {path}", errors[path][kind], tolerance[kind])
    if "low" in expected:
        low = expected["low"]
        for term in TERMS:
            rel = relative(low[term], term)
            run.say(
                f"teeth: {term} reference in bfloat16 {float(low[term]):.7f} "
                f"(relative {rel:.2e}, tolerance {tolerance[term]}): "
                f"{'would PASS' if rel <= tolerance[term] else 'fails, as it must'}"
            )
        rel = abs(float(low["held_row_share"]) - float(want["held_row_share"]))
        run.say(
            f"teeth: held share of the reference in bfloat16 "
            f"{float(low['held_row_share']):.6f} (off by {rel:.2e}, tolerance "
            f"{tolerance['held_row_share']}): "
            f"{'would PASS' if rel <= tolerance['held_row_share'] else 'fails, as it must'}"
        )
        same, margin = routing_agreement(low["experts"], want)
        passes = (same >= job["expert_agreement_min"]
                  and margin <= job["expert_tie_margin"])
        run.say(
            f"teeth: experts of the reference in bfloat16 equal in {same:.5f}, "
            f"tie margin {margin:.5f}: "
            f"{'would PASS' if passes else 'fails, as it must'}"
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
    traffic, architecture = run.traffic, run.section("architecture")

    batch = make_batch(
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
    # program the window times on the batch it times it on: the gradient it
    # applied, its AdamW arithmetic and the biases it left. Fenced, and the
    # optimizer's moments wait on the host meanwhile: the reference's
    # gradient and its backward take 7.9 GiB beside the parameters, which the
    # chip has not got beside the whole state
    reference = Reference(run, batch)
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
    bias = bias_errors(architecture, counts, jax.device_get(state.batch_stats))
    del before
    # after the warm-up: the program's next terms against the reference on
    # the very same weights and biases
    state = jax.block_until_ready(steps(state, traffic["warmup_steps"] - 1))
    expected = reference.step(
        lowered.model, state.params, state.batch_stats,
        bool(traffic.get("reference_teeth")),
    )
    state, metrics = lowered.train_step(state, batch)
    agrees = compare(run, job, expected, jax.device_get(metrics), errors, bias)
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
    learned = bool(finite.all() and losses[-1] < losses[0])
    dropless = bool((dropped == 0).all())
    counters["moe_dropped"] = float(dropped.max())
    counters["moe_load_max_over_mean"] = float(load.mean())
    counters["moe_held_row_share"] = float(held.mean())
    counters["trace_steps"] = traffic["trace_steps"] if run.trace else 0

    peak, limit = base.device_memory(devices[:chips])
    run.say(f"memory: peak {peak / 2**30:.2f} GiB of {limit / 2**30:.2f} GiB "
            "on the fullest chip")
    return Observation(
        correct=bool(agrees and learned and dropless and not missing),
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
