"""Driver ``lm_train_step``: the trainer's per-step program on a device batch
of tokens. ``train_step``'s shape of run for a decoder LM.

Builds the step exactly as ``trainer.train_model`` does (config -> mesh ->
topology -> model -> ``lower`` -> ``create_train_state``), makes ONE seeded
batch of token ids on the device (uniform over the vocabulary, every sequence
one full context, labels = inputs shifted by one), and dispatches
``lowered.train_step`` step by step. An item is a token.

* set-up: weights and batch from ``--seed``; the program the window times is
  then held, on the batch it times it on, to the configuration's plain
  float32 reference, which walks the WHOLE batch one sequence at a time.
  Its first step, from the fresh state: the gradient it applied (read back
  from AdamW's first moment) against the reference's on the worst leaf, and
  its new parameters and second moment against a plain AdamW step on that
  gradient. After the ``warmup_steps``, as ``train_step`` does: the next
  step's ``ce`` / ``moe_aux`` / ``moe_z`` / ``loss`` against the reference on
  the very same weights (the balancing term from the whole batch's shares
  and probabilities), and the experts the model's router chooses there
  against the reference's.
* window: chunks of ``chunk_steps`` steps, one always queued behind the one
  that runs, until ``--seconds`` have passed; ends in a fence on the state.
* traced run: after the window, ``trace_steps`` further steps under the
  profiler.

``attempted`` = steps in the window, ``failed`` = steps with a non-finite
loss. ``correct``: every loss term, the gradient and the update within their
tolerances of the reference, the experts chosen equal in at least
``expert_agreement_min`` of the (token, slot) pairs, every loss finite, the
loss lower at the window's end than at its start, ``moe_dropped`` 0 in every
step, and, in a traced run, every kernel of ``train_job.trace_kernels`` in
the trace (a program that fell back to another attention is not the one the
cell is for).

A program without this configuration's arch (the parent of the PR that added
it) is refused before the device is touched.
"""

from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

import distribuuuu_tpu.config as program_config
from distribuuuu_tpu import models
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
from benchmark.harness.discovery import DiscoveryError
from benchmark.harness.observation import Observation

TERMS = ("ce", "load_balance", "router_z", "loss")
STEP_METRIC = {"ce": "ce", "load_balance": "moe_aux", "router_z": "moe_z",
               "loss": "loss"}


def seed_key(seed: int):
    """A PRNG key from any whole-number seed (the contract allows a little
    over 2**31, which no int32 holds)."""
    return jax.random.fold_in(
        jax.random.key(seed % (2**31 - 1)), seed // (2**31 - 1)
    )


def refuse_without_arch(run) -> None:
    """A program that lacks this configuration's arch or YAML (the parent of
    the PR that added them) cannot run the cell: say so and stop."""
    program = run.section("program")
    cfg_file = os.path.join(run.root, program["cfg_file"])
    if program["arch"] not in models.available_models() or not os.path.exists(cfg_file):
        raise DiscoveryError(
            f"cell {run.cell.name!r}: the program at {run.root} has no arch "
            f"{program['arch']!r} or no {program['cfg_file']}: it cannot run "
            "this configuration"
        )


def configure(run, chips: int) -> dict:
    """Point the program's global config at this cell's training job."""
    program, job = run.section("program"), run.section("train_job")
    program_config.reset_cfg()
    program_config.merge_from_file(os.path.join(run.root, program["cfg_file"]))
    overrides = {
        **program["overrides"],
        "TRAIN.BATCH_SIZE": job["sequences_per_chip"],
        "LM.SEQ_LEN": job["seq_len"],
        "DEVICE.COMPUTE_DTYPE": job["dtype"],
        "OPTIM.BASE_LR": job["lr"],
        "MESH.DATA": chips,
        "RNG_SEED": run.seed % (2**31 - 1),
    }
    cfg.merge_from_list([str(x) for kv in overrides.items() for x in kv])
    return job


def build(run, chips: int, devices):
    """(lowered, job, batch avals): the step program for ``devices``."""
    job = configure(run, chips)
    mesh = build_mesh(data=chips, devices=devices)
    topology = check_trainer_mesh()
    model = build_model_from_cfg(topology)
    lowered = lower(
        model, construct_optimizer(), min(5, cfg.MODEL.NUM_CLASSES),
        mesh=mesh, topology=topology, im_size=cfg.TRAIN.IM_SIZE,
    )
    shape = (job["sequences_per_chip"] * chips, job["seq_len"])
    # abstract_args sizes a token batch by the model's 8-token init dummy:
    # keep its declared shardings, give the cell's shape
    state, batch = lowered.abstract_args(shape[0])
    batch = {
        k: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=v.sharding)
        for k, v in batch.items()
    }
    return lowered, job, state, batch


def compile_only(run, devices) -> dict:
    """For ``rehearse_compile.py``: the step program compiled for devices
    that are described, not attached."""
    lowered, _job, state, batch = build(run, len(devices), devices)
    return {"train_step": lowered.train_step.lower(state, batch).compile()}


def make_batch(seed: int, avals: dict, vocab: int):
    """Token ids uniform over the vocabulary, one jitted call on the device;
    labels are the inputs shifted by one."""
    batch, seq = avals["image"].shape

    def draw(key):
        ids = jax.random.randint(key, (batch, seq + 1), 0, vocab, jnp.int32)
        return {"image": ids[:, :-1], "label": ids[:, 1:]}

    shardings = {k: v.sharding for k, v in avals.items()}
    return jax.jit(draw, out_shardings=shardings)(
        jax.random.fold_in(seed_key(seed), 1)
    )


# ------------------------------------------------------------- the reference
def program_experts(model, params, tokens):
    """The experts the program's router chooses on the whole batch, ``[layers,
    T, k]``: a forward of the model's own modules, because the step reports
    its routing only as counts."""
    _, sown = model.apply(
        {"params": params}, tokens, hidden_only=True, mutable=["moe_route"]
    )
    experts = jnp.stack(jax.tree.leaves(sown["moe_route"]))
    return experts.reshape(experts.shape[0], -1, experts.shape[-1])


def reference_terms(reference, architecture, weights, params, tokens, labels,
                    precision):
    """The reference over the whole batch, one sequence at a time: the loss
    terms as the step defines them (the balancing term from the whole
    batch's shares ``f`` and probabilities ``P``), their weighted sum, the
    experts chosen and the router logits ``[layers, T, ...]``, and ``f``."""

    def one(sequence):
        return reference.loss(
            params, sequence[0][None], sequence[1][None],
            architecture=architecture, precision=precision,
        )

    per = jax.lax.map(one, (tokens, labels))
    share, probs = per["share"].mean(0), per["probs"].mean(0)
    terms = {
        "ce": per["ce"].mean(), "router_z": per["router_z"].mean(),
        "load_balance":
            (architecture["num_experts"] * (share * probs).sum(-1)).mean(),
    }
    terms["loss"] = terms["ce"] + sum(w * terms[k] for k, w in weights.items())

    def layers_first(x):  # [sequences, layers, S, n] -> [layers, T, n]
        return jnp.moveaxis(x, 0, 1).reshape(x.shape[1], -1, x.shape[-1])

    return {**terms, "share": share, "experts": layers_first(per["experts"]),
            "router_logits": layers_first(per["router_logits"])}


def reference_grads(reference, architecture, weights, params, tokens, labels,
                    share):
    """The float32 gradient of the step's loss on the whole batch: the mean
    of the sequences' gradients, each taken with the batch's ``share``."""

    def total(p, sequence):
        terms = reference.loss(
            p, sequence[0][None], sequence[1][None],
            architecture=architecture, share=share,
        )
        return terms["ce"] + sum(w * terms[k] for k, w in weights.items())

    def add(acc, sequence):
        return jax.tree.map(jnp.add, acc, jax.grad(total)(params, sequence)), None

    acc, _ = jax.lax.scan(
        add, jax.tree.map(jnp.zeros_like, params), (tokens, labels)
    )
    return jax.tree.map(lambda g: g / tokens.shape[0], acc)


class Reference:
    """The configuration's plain reference on the cell's batch, on one
    device: ``terms`` (float32, or ``low`` in bfloat16: the nearest precision
    below the configuration's, which must NOT pass) and ``grads``, each
    compiled once."""

    def __init__(self, run, job, batch):
        fixed = (run.catalog.reference(run.cell.config["reference"]),
                 run.section("architecture"), job["loss_weights"])
        self.device = jax.devices()[0]
        self.tokens = batch["image"]  # where the step has them
        self.batch = jax.device_put((batch["image"], batch["label"]), self.device)
        self.terms, self.low = (
            jax.jit(lambda *a, p=precision: reference_terms(*fixed, *a, p))
            for precision in (jnp.float32, jnp.bfloat16)
        )
        self.grads = jax.jit(lambda *a: reference_grads(*fixed, *a))

    def first_step(self, params) -> tuple:
        """(params, the reference's gradient on them), both on the host: the
        step donates the first, and the two do not fit the chip beside the
        step's temporaries."""
        params = jax.device_put(params, self.device)
        share = self.terms(params, *self.batch)["share"]
        return jax.device_get((params, self.grads(params, *self.batch, share)))

    def step(self, model, params, teeth: bool) -> dict:
        """Terms and routing of the reference on ``params``, and the
        program's own routing there (over the mesh the step runs on), on
        the host."""
        out = {"chosen": jax.jit(lambda *a: program_experts(model, *a))(
            params, self.tokens)}
        params = jax.device_put(params, self.device)
        out["want"] = self.terms(params, *self.batch)
        if teeth:  # by hand: --set traffic.reference_teeth=true
            out["low"] = self.low(params, *self.batch)
        return jax.device_get(out)


SMALL_LEAF = 8  # elements: under it a leaf's gradient is read with its module's


def plain_adamw_step(adamw: dict, lr: float, p0, m1) -> tuple:
    """(the gradient a first step applied, its step, its second moment) from
    the first moment ``m1`` it left (zero moments before, t = 1)."""
    b1, b2, eps, wd = (adamw[k] for k in ("b1", "b2", "eps", "weight_decay"))
    g = m1 / (1 - b1)
    m_hat, v = g, (1 - b2) * jnp.square(g)  # m1 / (1 - b1 ** 1)
    return g, lr * (m_hat / (jnp.sqrt(v / (1 - b2)) + eps) + wd * p0), v


def beyond_one_spacing(p1, want):
    """``p1 - want`` where the two are further apart than neighbouring
    values of the parameter's type, 0 where they are equal or neighbours:
    two orders of the same arithmetic (``p + (u + wd p)(-lr)`` and ``p0 -
    lr (...)``) round an exact result that lies near a midpoint to either
    side, and both are right."""
    want = want.astype(p1.dtype)
    return jnp.where(p1 == jnp.nextafter(want, p1), 0, p1 - want)


def with_its_module(sums: dict, path: str) -> float:
    """The relative error of the gradient over the leaves of ``path``'s
    parent module together: the norm of the joined difference over the joined
    reference's norm. ``sums``: key string -> (parent's key string, squared
    norm of the difference, squared norm of the reference)."""
    parent = sums[path][0]
    diff, ref = (sum(s[i] for s in sums.values() if s[0] == parent) for i in (1, 2))
    return float(np.sqrt(diff) / max(np.sqrt(ref), 1e-30))


def adamw_moments(opt_state):
    """The one state of the optimizer that holds AdamW's ``mu`` and ``nu``."""
    adam = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    if len(adam) != 1:
        raise ValueError("the optimizer state holds no single AdamW moment pair")
    return adam[0]


def sum_of_squares(x):
    return jnp.sum(jnp.square(x.astype(jnp.float32)))


def relative(diff, ref):
    """The norm of ``diff`` over the norm of ``ref``."""
    return jnp.sqrt(sum_of_squares(diff)) / jnp.maximum(
        jnp.sqrt(sum_of_squares(ref)), 1e-30)


@functools.lru_cache(maxsize=None)
def _leaf_errors(adamw: tuple, lr: float):
    """The jitted per-leaf errors of :func:`first_step_errors`, traced once
    for one optimizer (a process that reads many seeds calls it for each)."""
    adamw = dict(adamw)

    def leaf(p0, g_ref, p1, m1, v1):
        g, step, v = plain_adamw_step(adamw, lr, p0, m1)
        return {
            "gradient": relative(g - g_ref, g_ref),
            "update": relative(beyond_one_spacing(p1, p0 - step), step),
            "second_moment": relative(v1 - v, v),
            "gradient_sq": (sum_of_squares(g - g_ref), sum_of_squares(g_ref)),
        }

    return jax.jit(lambda *trees: jax.tree.map(leaf, *trees))


def first_step_errors(adamw: dict, lr: float, before, grads, state) -> dict:
    """Per leaf, of the first step of a fresh state (zero moments, t = 1):
    ``gradient``, the gradient the step applied (AdamW's first moment over 1
    - b1) against the reference's; ``update``, its new parameters against a
    plain AdamW step from ``before`` on the gradient it applied, over the
    length of that step; ``second_moment`` likewise.

    ``update`` is held in units the parameter can represent: an element
    whose new value is the plain step's or a NEIGHBOURING value of its type
    counts as equal (:func:`beyond_one_spacing`); every other element's
    difference is summed whole. A leaf of fewer than ``SMALL_LEAF`` elements
    carries no relative error of its own (one float whose terms nearly cancel
    reads anything): its ``gradient`` is read over its parent module's
    leaves together (:func:`with_its_module`)."""
    adam = adamw_moments(state.opt_state)
    errors = _leaf_errors(tuple(sorted(adamw.items())), lr)(
        before, grads, state.params, adam.mu, adam.nu
    )
    flat, _ = jax.tree_util.tree_flatten_with_path(
        jax.device_get(errors), is_leaf=lambda x: isinstance(x, dict) and "update" in x
    )
    out, sums = {}, {}
    for path, e in flat:
        key = jax.tree_util.keystr(path)
        sums[key] = (jax.tree_util.keystr(path[:-1]), *map(float, e.pop("gradient_sq")))
        out[key] = {k: float(v) for k, v in e.items()}
    for path, x in jax.tree_util.tree_flatten_with_path(state.params)[0]:
        if x.size < SMALL_LEAF:
            key = jax.tree_util.keystr(path)
            out[key]["gradient"] = with_its_module(sums, key)
    return out


def expert_agreement(chosen, want) -> tuple[float, float]:
    """(share of (token, slot) pairs whose expert is also the reference's;
    the largest tie margin of a pair that is not). The margin of a token is
    how far, in the reference's OWN router logits, the worst expert of
    ``chosen`` lies under the reference's k-th: 0 where the sets are equal,
    small where rounding upstream of the router broke a near-tie the other
    way, large where the routing itself is wrong."""
    k = chosen.shape[-1]
    logits = want["router_logits"].reshape(-1, want["router_logits"].shape[-1])
    chosen = chosen.reshape(-1, k)
    same = (chosen[:, :, None] == want["experts"].reshape(-1, 1, k)).any(-1)
    kth = np.sort(logits, -1)[:, -k]
    worst = np.take_along_axis(logits, chosen, -1).min(-1)
    return float(same.mean()), float((kth - worst).max())


def compare(run, job, expected, metrics, errors) -> bool:
    """The timed program against the reference: ``metrics`` of a step on the
    weights ``expected`` was computed on, ``errors`` of its first step."""
    want, tolerance, agrees = expected["want"], job["reference_tolerance"], True

    def relative(got, term):
        return abs(got - float(want[term])) / max(1.0, abs(float(want[term])))

    for term in TERMS:
        got = float(metrics[STEP_METRIC[term]])
        ok = relative(got, term) <= tolerance[term]
        agrees &= ok
        run.say(
            f"reference: {term} step {got:.7f} vs plain float32 "
            f"{float(want[term]):.7f} (relative {relative(got, term):.2e}, "
            f"tolerance {tolerance[term]}): {'agrees' if ok else 'DISAGREES'}"
        )
    same, margin = expert_agreement(expected["chosen"], want)
    ok = same >= job["expert_agreement_min"] and margin <= job["expert_tie_margin"]
    agrees &= ok
    run.say(
        f"reference: experts chosen equal in {same:.5f} of the (token, slot) "
        f"pairs of the batch (at least {job['expert_agreement_min']}); where "
        f"they differ the reference's own router logits are within "
        f"{margin:.4f} of a tie (at most {job['expert_tie_margin']}): "
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
        low = expected["low"]
        for term in TERMS:
            rel = relative(float(low[term]), term)
            run.say(
                f"teeth: {term} reference in bfloat16 {float(low[term]):.7f} vs "
                f"float32 {float(want[term]):.7f} (relative {rel:.2e}, tolerance "
                f"{tolerance[term]}): "
                f"{'would PASS' if rel <= tolerance[term] else 'fails, as it must'}"
            )
        same, margin = expert_agreement(low["experts"], want)
        passes = (same >= job["expert_agreement_min"]
                  and margin <= job["expert_tie_margin"])
        run.say(
            f"teeth: experts of the reference in bfloat16 equal in {same:.5f}, "
            f"tie margin {margin:.4f}: "
            f"{'would PASS' if passes else 'fails, as it must'}"
        )
    return bool(agrees)


def device_memory(devices) -> tuple[int, int]:
    """(peak bytes, limit bytes) of the fullest device for the WINDOW's
    program: what the process holds once the set-up is over (the state, the
    batch) plus the largest temporaries a program reserved while it ran. The
    process-wide ``peak_bytes_in_use`` that ``train_step`` adds instead also
    counts the reference's gradient and the two trees the first step's
    check puts back, which no step of the window holds (with them the sum
    read 104 % of the chip)."""
    stats = [d.memory_stats() or {} for d in devices]
    peaks = [
        int(m.get("bytes_in_use", 0)) + int(m.get("peak_bytes_reserved", 0))
        for m in stats
    ]
    fullest = peaks.index(max(peaks))
    return peaks[fullest], int(stats[fullest].get("bytes_limit", 0))


def kernels_missing(job, trace_path) -> list:
    """The kernels the cell is for (``train_job.trace_kernels``) that no
    device event of the trace is named after."""
    wanted = job.get("trace_kernels", [])
    names = {e["name"] for e in trace.load_events(trace_path)} if wanted else set()
    return [k for k in wanted if not any(n.startswith(k) for n in names)]


def run(run) -> Observation:
    chips = run.cell.chips
    run.mark("imports")
    refuse_without_arch(run)  # before the chip is touched
    devices = jax.devices()
    run.mark("reach the device")
    run.admit_device(devices[0].platform, devices[0].device_kind, len(devices))
    run.compiles.install()
    lowered, job, _state, avals = build(run, chips, devices[:chips])
    setup_from_cfg(cfg)
    traffic = run.traffic

    batch = make_batch(run.seed, avals, cfg.MODEL.NUM_CLASSES)
    state = create_train_state(
        lowered.model, seed_key(run.seed), lowered.mesh, cfg.TRAIN.IM_SIZE,
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
    seen = []  # per step: loss, moe_dropped, moe_load_max_over_mean

    def steps(state, n, annotate=False):
        for _ in range(n):
            if annotate:
                with profiler.span("dispatch"):
                    state, metrics = lowered.train_step(state, batch)
            else:
                state, metrics = lowered.train_step(state, batch)
            seen.append([metrics[k] for k in
                         ("loss", "moe_dropped", "moe_load_max_over_mean")])
        return state

    # the FIRST step of the fresh state (zero moments), the program the
    # window times on the batch it times it on: the gradient it applied and
    # its AdamW arithmetic (fenced: its temporaries and the reference's two
    # trees do not fit the chip together)
    reference = Reference(run, job, batch)
    before = reference.first_step(state.params)
    run.mark("reference gradient")
    state, _ = jax.block_until_ready(lowered.train_step(state, batch))
    layout = jax.tree.map(lambda x: x.sharding, state.params)
    errors = first_step_errors(
        job["adamw"], job["lr"], *jax.device_put(before, (layout, layout)), state
    )
    del before
    # after the warm-up, as train_step does: the program's next loss terms
    # against the reference on the very same weights
    state = jax.block_until_ready(steps(state, traffic["warmup_steps"] - 1))
    expected = reference.step(
        lowered.model, state.params, bool(traffic.get("reference_teeth"))
    )
    state, metrics = lowered.train_step(state, batch)
    agrees = compare(run, job, expected, jax.device_get(metrics), errors)
    del seen[:], expected, reference
    run.mark("step program, warm-up, the step against the reference")

    # ---------------------------------------------------------------- window
    # as train_step: one chunk always queued behind the one that runs; the
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
    in_window = np.asarray(jax.device_get(seen), np.float64)  # [steps, 3]

    trace_path = op_names_path = None
    missing = []
    if run.trace:
        with profiler.capture(run.trace_dir) as captured:
            with profiler.span("window"):
                state = steps(state, traffic["trace_steps"], annotate=True)
                with profiler.span("fence"):
                    state = jax.block_until_ready(state)
        trace_path = captured["path"]
        missing = kernels_missing(job, trace_path)
        if job.get("trace_kernels"):
            run.say(f"trace: kernels {job['trace_kernels']}: "
                    f"{'all there' if not missing else f'MISSING {missing}'}")
    counters["compiles_in_window"] = run.compiles_since_open()
    if run.trace:
        hlo = lowered.train_step.lower(state, batch).compile().as_text()
        op_names_path = os.path.join(run.trace_dir, "op_names.json")
        with open(op_names_path, "w") as f:
            json.dump(trace.op_names_from_hlo(hlo), f)

    losses, dropped, load = in_window.T
    per_chunk = [c / chunk * 1e3 for c in chunk_s]
    q1, med, q3 = stats.quartiles(per_chunk)
    run.say(
        f"window: {n_steps} steps of {counters['tokens_per_step']} tokens in "
        f"{window.elapsed:.3f} s; ms/step over {len(chunk_s)} chunks of "
        f"{chunk}: q1 {q1:.3f} median {med:.3f} q3 {q3:.3f}; loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}; moe_dropped max "
        f"{dropped.max():.3g}; expert load max/mean {load.mean():.3f} "
        f"(first step {load[0]:.3f}, last {load[-1]:.3f})"
    )
    finite = np.isfinite(losses)
    learned = bool(finite.all() and losses[-1] < losses[0])
    dropless = bool((dropped == 0).all())
    counters["moe_dropped"] = float(dropped.max())
    counters["moe_load_max_over_mean"] = float(load.mean())
    counters["trace_steps"] = traffic["trace_steps"] if run.trace else 0

    peak, limit = device_memory(devices[:chips])
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
