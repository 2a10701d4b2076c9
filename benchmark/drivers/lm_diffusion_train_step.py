"""Driver ``lm_diffusion_train_step``: ``lm_pattern_train_step``'s shape of run
(one chip's share of an expert-parallel group, a loss of ONE head's
cross-entropy and the mixtures' balancing term, every number compared handed
to the harness beside its limit) for a decoder trained by BLOCK DIFFUSION
(``models/sdar_moe.py``): every step draws its noise from the step's key, the
label of a position is its OWN token (the batch's labels are the inputs,
unshifted), the loss is over the masked positions of the noised copy alone,
and the router carries no bias, so the step leaves no state beside the
parameters and the optimizer's.

Everything it shares is the accepted drivers' own, loaded by name:
``lm_train_step``'s seed key, program (config -> mesh -> topology -> model ->
``lower``), first-step check and traced kernels, and
``lm_share_train_step``'s routing agreement and gradient classes. What
differs is here: the batch (ids over the held rows of the vocabulary but the
one that stands for ``[MASK]``), a reference that is handed the step's key
and draws the noise ITSELF (``reference/sdar_moe.py``), the comparison of the
two sides' draws, and the terms.

* set-up, the comparison: weights and batch from ``--seed``, the weights the
  model's own initialisers' draw; the FIRST step of the fresh state against
  the float32 reference's gradient on the whole batch under that step's key;
  after the ``warmup_steps`` the next step's ``ce``, ``moe_aux``, ``loss``,
  ``diffusion_masked_share`` and ``moe_held_row_share`` against the reference
  on the very same weights and key, the positions the program masked and
  their levels against the reference's own draws (``noise_disagreeing``:
  must be 0), and the experts the model's routers choose there against the
  reference's.
* set-up, the timed state: the SAME draw with the q and k head norms' scales
  at ``train_job.head_norm_scale`` (:func:`sharpened`: what keeps the work of
  a step the same from seed to seed), the same program; after the
  ``warmup_steps`` its next step against the reference again, the numbers
  named ``timed_*`` and held to ``train_job.timed``'s limits. Two states
  because sharp scores that make the routing a checkpoint's also make six
  layers of hard choices follow rounding: there the program and the float32
  reference part by a fifth of the experts chosen, as far as the bfloat16
  control does, and no limit on a gradient says anything (PERF.md section 6,
  PR 47).
* window: chunks of ``chunk_steps`` steps, one always queued behind the one
  that runs, until ``--seconds`` have passed; ends in a fence on the state.
  An item is a DATA token: ``sequences x seq_len`` a step, never the 2 x
  that many rows a block sees.
* traced run: after the window, ``trace_steps`` further steps under the
  profiler; the counters ``moe_held_row_share`` and
  ``diffusion_masked_share`` are then THOSE steps' means.

``attempted`` = steps in the window, ``failed`` = steps with a non-finite
loss. ``correct``: every number of ``compared`` within its limit. The loss of
one step is an estimate over that step's draws (its spread from step to step
is several per cent), so "the loss fell" compares the MEAN of the window's
last third of the steps with the mean of its first third.

``traffic.reference_teeth`` (by hand) also runs the reference in bfloat16
throughout and says, number by number, whether it would pass: the control
that shows the limits part a precision below the configuration's.

A program without this configuration's arch (the parent of the PR that added
it) is refused before the device is touched.
"""

from __future__ import annotations

import json
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distribuuuu_tpu.asyncplane.compile_cache import setup_from_cfg
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu.trainer import create_train_state

from benchmark.harness import profiler, stats, trace
from benchmark.harness.clock import Window, now
from benchmark.harness.observation import Observation

TERMS = ("ce", "load_balance", "loss")
STEP_METRIC = {"ce": "ce", "load_balance": "moe_aux", "loss": "loss"}
SEEN = ("loss", "moe_dropped", "moe_load_max_over_mean", "moe_held_row_share",
        "diffusion_masked_share")
NOISE_STREAM = "diffusion"  # the configuration's assumed.noise names it


def compile_only(run, devices) -> dict:
    return run.catalog.driver("lm_train_step").compile_only(run, devices)


def make_batch(key, avals: dict, first: int, rows: int):
    """Token ids uniform over the ``rows`` rows of the vocabulary from
    ``first``, one jitted call on the device; the labels are the inputs, not
    shifted: a position's label is its own token."""
    shape = avals["image"].shape

    def draw(key):
        ids = first + jax.random.randint(key, shape, 0, rows, jnp.int32)
        return {"image": ids, "label": ids}

    shardings = {k: v.sharding for k, v in avals.items()}
    return jax.jit(draw, out_shardings=shardings)(jax.random.fold_in(key, 1))


def sharpened(params, scale: float):
    """``params`` with every per-head q and k norm's scale times ``scale``:
    the scores' spread times ``scale ** 2``, so that a row reads a few keys
    and not the mean of thousands. At the initialisers' scale of 1 every row
    of a layer leaves attention with nearly the same state (the mean of the
    values it may read), every row of a mixture then takes the same 8
    experts, and whether they are among the 16 this chip holds is a lottery a
    seed draws: the step's time followed it by 2.7 % over 23 seeds (PERF.md
    section 6, PR 47). A checkpoint's attention is sharp and its router
    balanced, which is what the deployment starts from; this changes no
    shape, no operation and no row's cost."""
    def leaf(path, x):
        names = {getattr(k, "key", None) for k in path}
        return x * scale if names & {"q_norm", "k_norm"} else x

    return jax.tree_util.tree_map_with_path(leaf, params)


def step_key(state):
    """The key the step about to run folds for itself
    (``lowering.train_step``)."""
    return jax.random.fold_in(state.key, state.step)


def stream_key(key):
    """What ``make_rng`` of the noise's stream returns at the root of a
    module applied with that stream set to ``key``: flax's fold, asked of
    flax and not of the program's model."""
    class Root(nn.Module):
        def __call__(self):
            return self.make_rng(NOISE_STREAM)

    return Root().apply({}, rngs={NOISE_STREAM: key})


def mixture_names(architecture: dict) -> list:
    return [f"Block_{i}" for i in range(architecture["layers"])]


def reference_terms(reference, architecture, params, tokens, noise_key,
                    precision=jnp.float32):
    """``(terms, routing and draws; gradient of the loss)`` of the reference
    on the whole batch."""

    def total(p):
        terms = reference.loss(
            p, tokens, architecture=architecture, noise_key=noise_key,
            precision=precision)
        return terms["loss"], terms

    (_, terms), grads = jax.value_and_grad(total, has_aux=True)(params)
    return terms, grads


class Reference:
    """The configuration's plain reference on the cell's batch, on one device:
    ``both`` terms and gradient for the first step, ``terms`` alone for the
    step after the warm-up (a forward's size), ``low`` the terms in bfloat16
    throughout: the nearest precision below the configuration's, which must
    NOT pass."""

    def __init__(self, run, batch):
        self.architecture = run.section("architecture")
        fixed = (run.catalog.reference(run.cell.config["reference"]),
                 self.architecture)
        self.device = jax.devices()[0]
        self.tokens = batch["image"]  # where the step has them
        self.batch = jax.device_put(batch["image"], self.device)
        self.both = jax.jit(lambda *a: reference_terms(*fixed, *a))
        self.terms, self.low = (
            jax.jit(lambda p, x, key, dtype=dtype: fixed[0].loss(
                p, x, architecture=fixed[1], noise_key=key, precision=dtype))
            for dtype in (jnp.float32, jnp.bfloat16)
        )

    def first_step(self, params, key) -> tuple:
        """(params, the reference's gradient on them), on the host: the step
        donates the first, and the trees do not fit the chip beside the
        step's temporaries."""
        params = jax.device_put(params, self.device)
        _, grads = self.both(params, self.batch, stream_key(key))
        return jax.device_get((params, grads))

    def program_draws(self, model, params, key):
        """What the program's own modules choose and draw on the whole batch
        under ``key``: the experts ``[mixtures, T, k]``, the positions masked
        and their levels ``[B, S]``; a forward of the model, because the step
        reports its routing as counts and its noise as a mean."""
        _, sown = model.apply(
            {"params": params}, self.tokens, train=True, hidden_only=True,
            rngs={NOISE_STREAM: key}, mutable=["moe_route", "diffusion_noise"])
        experts = jnp.stack([
            sown["moe_route"][name]["moe"]["experts"][0]
            for name in mixture_names(self.architecture)])
        noise = sown["diffusion_noise"]
        return {"experts": experts.reshape(experts.shape[0], -1, experts.shape[-1]),
                "masked": noise["masked"][0], "level": noise["level"][0]}

    def step(self, model, params, key, teeth: bool) -> dict:
        out = {"program": jax.jit(
            lambda p, k: self.program_draws(model, p, k))(params, key)}
        params = jax.device_put(params, self.device)
        out["want"] = self.terms(params, self.batch, stream_key(key))
        if teeth:  # by hand: --set traffic.reference_teeth=true
            out["low"] = self.low(params, self.batch, stream_key(key))
        return jax.device_get(out)


def window_memory(devices, step) -> tuple[int, int]:
    """(peak bytes, limit bytes) of the fullest device for the WINDOW's
    program: what the process holds once the window is over (the state, the
    batch) plus the temporaries the compiled ``step`` plans for itself
    (``memory_analysis``). ``lm_train_step.device_memory`` adds the process's
    largest reservation instead, which in this cell is the reference's
    gradient walk, made while the moments waited on the host: 9.4 GiB over
    the 7.3 GiB held read 106 % of the chip (PERF.md section 6, PR 47)."""
    temporaries = int(step.memory_analysis().temp_size_in_bytes)
    stats = [d.memory_stats() or {} for d in devices]
    peaks = [int(m.get("bytes_in_use", 0)) + temporaries for m in stats]
    fullest = peaks.index(max(peaks))
    return peaks[fullest], int(stats[fullest].get("bytes_limit", 0))


def gradient_classes(share, architecture: dict, errors) -> dict:
    """``lm_share_train_step.gradient_classes`` (routers / routed experts /
    the rest, by how a routing flip reaches a leaf's gradient) with the
    mixtures' ``post_attention_norm`` scales counted among the experts, as
    ``lm_pattern_train_step`` counts LFM2's ``ffn_norm``: this mixture has no
    shared expert, so ALL that passes such a norm goes to routed experts."""
    classes = share.gradient_classes(errors)
    norms = {f"['{name}']['post_attention_norm']['scale']"
             for name in mixture_names(architecture)}
    classes["gradient_experts"] += [p for p in classes["gradient"] if p in norms]
    classes["gradient"] = [p for p in classes["gradient"] if p not in norms]
    return classes


def noise_disagreeing(program: dict, want: dict) -> float:
    """The share of the positions at which the two sides' draws differ: the
    position masked on one side alone, or its block's level apart by more
    than float32 rounding."""
    same = (np.asarray(program["masked"]) == np.asarray(want["masked"])) & np.isclose(
        program["level"], want["level"], rtol=1e-6, atol=0)
    return 1.0 - float(same.mean())


def numbers(run, job, want, metrics, chosen, errors=None, program=None) -> dict:
    """Every number the comparison holds to a limit, name -> {value, limit}
    (within its limit: value <= limit): ``metrics`` (the step's, or another
    reference's terms) and ``chosen`` (experts ``[mixtures, T, k]``) against
    the float32 reference ``want``; with ``errors`` the first step's gradient
    classes, update and second moment, with ``program`` its draws too."""
    share = run.catalog.driver("lm_share_train_step")
    tolerance = job["reference_tolerance"]
    out = {}
    for term in TERMS:
        ref = float(want[term])
        out[term] = {
            "value": abs(float(metrics[term]) - ref) / max(1.0, abs(ref)),
            "limit": tolerance[term]}
    for name in ("held_row_share", "masked_share"):
        out[name] = {"value": abs(float(metrics[name]) - float(want[name])),
                     "limit": tolerance[name]}
    same, margin = share.routing_agreement(np.asarray(chosen), want)
    out["experts_disagreeing"] = {
        "value": 1.0 - same, "limit": 1.0 - job["expert_agreement_min"]}
    out["expert_tie_margin"] = {"value": margin, "limit": job["expert_tie_margin"]}
    if program is not None:
        # noise_agreement must be 1.0
        out["noise_disagreeing"] = {
            "value": noise_disagreeing(program, want), "limit": 0}
    if errors is not None:
        classes = gradient_classes(share, run.section("architecture"), errors)
        for limit, leaves in classes.items():
            out[limit] = {"value": max(errors[p]["gradient"] for p in leaves),
                          "limit": tolerance[limit]}
        for kind in ("update", "second_moment"):
            out[kind] = {"value": max(e[kind] for e in errors.values()),
                         "limit": tolerance[kind]}
    return out


def compare(run, job, expected, metrics, errors=None, prefix="") -> dict:
    """The program against the reference: ``metrics`` of a step on the
    weights and under the key ``expected`` was computed with, ``errors`` of
    its first step where it was the fresh state's. Says every number and
    returns them, named ``prefix`` + the number's name."""
    want, program = expected["want"], expected["program"]
    step = {term: metrics[STEP_METRIC[term]] for term in TERMS}
    step["held_row_share"] = metrics["moe_held_row_share"]
    step["masked_share"] = metrics["diffusion_masked_share"]
    compared = numbers(run, job, want, step, program["experts"], errors, program)
    if errors is not None:
        run.say("reference: gradient of the first step, relative, leaf by leaf: "
                + ", ".join(f"{path} {e['gradient']:.1e}" for path, e in errors.items()))
    for term in (*TERMS, "masked_share", "held_row_share"):
        run.say(f"reference: {prefix}{term} step {float(step[term]):.7f} vs plain "
                f"float32 {float(want[term]):.7f}")
    for name, c in compared.items():
        run.say(f"reference: {prefix}{name} {c['value']:.3e} (limit {c['limit']}): "
                f"{'agrees' if c['value'] <= c['limit'] else 'DISAGREES'}")
    if "low" in expected:
        low = expected["low"]
        teeth = numbers(run, job, want, low, low["experts"])
        failed = [n for n, c in teeth.items() if c["value"] > c["limit"]]
        for name, c in teeth.items():
            run.say(
                f"teeth: {prefix}{name} of the reference in bfloat16 {c['value']:.3e} "
                f"(limit {c['limit']}): "
                f"{'would PASS' if c['value'] <= c['limit'] else 'fails, as it must'}")
        run.say(f"teeth: the reference in bfloat16 throughout fails {len(failed)} "
                f"of {len(teeth)} {prefix}limits: {failed}")
    return {prefix + name: c for name, c in compared.items()}


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

    first = architecture["share_rank"] * architecture["vocab_held"]
    # every held row but the one that stands for [MASK]
    held = [r for r in range(first, first + architecture["vocab_held"])
            if r != architecture["mask_id"]]
    if held != list(range(held[0], held[0] + len(held))):
        raise ValueError("the mask's id splits the held rows: not this traffic")
    batch = make_batch(base.seed_key(run.seed), avals, held[0], len(held))

    def fresh_state():
        return create_train_state(
            lowered.model, base.seed_key(run.seed), lowered.mesh, cfg.TRAIN.IM_SIZE,
            layout=lowered.layout,
        )

    state = fresh_state()
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
        # DATA tokens: a sequence is counted once, not as its two copies
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

    # the FIRST step of the fresh state (zero moments), the program the window
    # times on the batch it times it on, under the key that step folds.
    # Fenced, and the optimizer's moments wait on the host meanwhile, as
    # lm_share_train_step's: the reference's gradient and its backward want
    # the room
    reference = Reference(run, batch)
    moments = jax.tree.map(lambda x: x.sharding, state.opt_state)
    aside = jax.device_get(state.opt_state)
    jax.tree.map(lambda x: x.delete(), state.opt_state)
    before = reference.first_step(state.params, step_key(state))
    state = state.replace(opt_state=jax.device_put(aside, moments))
    del aside
    run.mark("reference gradient")
    state, _ = jax.block_until_ready(lowered.train_step(state, batch))
    layout = jax.tree.map(lambda x: x.sharding, state.params)
    errors = base.first_step_errors(
        job["adamw"], job["lr"], *jax.device_put(tuple(before), (layout, layout)),
        state,
    )
    del before
    # after the warm-up: the program's next terms against the reference on
    # the very same weights, under the very key that step will fold
    state = jax.block_until_ready(steps(state, traffic["warmup_steps"] - 1))
    expected = reference.step(
        lowered.model, state.params, step_key(state),
        bool(traffic.get("reference_teeth")),
    )
    state, metrics = lowered.train_step(state, batch)
    compared = compare(run, job, expected, jax.device_get(metrics), errors)
    del state, expected
    run.mark("step program, warm-up, the step against the reference")

    # the state the window times: the same draw with sharp scores, through
    # the same warm-up, its next step against the reference at its own limits
    state = fresh_state()
    state = state.replace(params=sharpened(state.params, job["head_norm_scale"]))
    state = jax.block_until_ready(steps(state, traffic["warmup_steps"]))
    expected = reference.step(
        lowered.model, state.params, step_key(state),
        bool(traffic.get("reference_teeth")),
    )
    state, metrics = lowered.train_step(state, batch)
    compared.update(compare(run, {**job, **job["timed"]}, expected,
                            jax.device_get(metrics), prefix="timed_"))
    del seen[:], expected, reference
    run.mark("the timed state, warm-up, its step against the reference")

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
    losses, dropped, load, held_share, masked = np.asarray(
        jax.device_get(seen), np.float64).T

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
    # the window's own program again, from the cache: its text names the
    # traced operations' scopes, its plan says what it reserves
    step = lowered.train_step.lower(state, batch).compile()
    if run.trace:
        op_names_path = os.path.join(run.trace_dir, "op_names.json")
        with open(op_names_path, "w") as f:
            json.dump(trace.op_names_from_hlo(step.as_text()), f)

    per_chunk = [c / chunk * 1e3 for c in chunk_s]
    q1, med, q3 = stats.quartiles(per_chunk)
    third = max(1, n_steps // 3)
    early, late = losses[:third].mean(), losses[-third:].mean()
    run.say(
        f"window: {n_steps} steps of {counters['tokens_per_step']} data tokens in "
        f"{window.elapsed:.3f} s; ms/step over {len(chunk_s)} chunks of "
        f"{chunk}: q1 {q1:.3f} median {med:.3f} q3 {q3:.3f}; loss, mean of the "
        f"first {third} steps {early:.4f} -> of the last {third} {late:.4f} "
        f"(a step's own spread {losses.std():.4f}); moe_dropped max "
        f"{dropped.max():.3g}; expert load max/mean {load.mean():.3f}; share "
        f"of the choices on held experts {held_share.mean():.5f} (first step "
        f"{held_share[0]:.5f}, last {held_share[-1]:.5f}); share of the "
        f"positions masked {masked.mean():.5f}"
    )
    finite = np.isfinite(losses)
    # exact: none may be over 0
    compared["losses_not_finite"] = {"value": float((~finite).sum()), "limit": 0}
    compared["loss_did_not_fall"] = {
        "value": float(not (finite.all() and late < early)), "limit": 0}
    compared["rows_dropped"] = {"value": float(np.nanmax(dropped)), "limit": 0}
    compared["traced_kernels_missing"] = {"value": float(len(missing)), "limit": 0}
    counters["moe_dropped"] = float(dropped.max())
    counters["moe_load_max_over_mean"] = float(load.mean())
    counters["moe_held_row_share"] = float(held_share.mean())
    counters["diffusion_masked_share"] = float(masked.mean())
    counters["trace_steps"] = traffic["trace_steps"] if run.trace else 0
    if run.trace:
        # the readers that set the held rows' work against the TRACED steps'
        # time need the share those steps had
        traced = np.asarray(jax.device_get(seen[n_steps:]), np.float64)
        counters["moe_held_row_share"] = float(traced[:, 3].mean())
        counters["diffusion_masked_share"] = float(traced[:, 4].mean())
        run.say(f"trace: over the {len(traced)} traced steps, share of the choices "
                f"on held experts {traced[:, 3].mean():.5f}, of the positions "
                f"masked {traced[:, 4].mean():.5f}")

    peak, limit = window_memory(devices[:chips], step)
    run.say(f"memory: peak {peak / 2**30:.2f} GiB of {limit / 2**30:.2f} GiB "
            "on the fullest chip (held after the window + the step's own "
            "temporaries)")
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
