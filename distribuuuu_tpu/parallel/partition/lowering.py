"""The one lowering: specs → compiled train/eval steps.

This is where the per-leaf declarations (partition/specs.py) and the
validated topology (partition/topology.py) become executable programs.
There is ONE step body for every point of the mesh space — dp, dp×tp,
PP, ZeRO-1/3, MoE over the model or the dedicated expert axis, and the
compositions that previously had no code path (ZeRO-3 under PP, a
dp×tp×ep mesh with ZeRO-1). A topology changes WHICH constraints the
body applies, never which code runs:

  * the batch rides the declared ``data`` spec (specs.BATCH_TABLE);
  * params/opt/grads rest in the ``state_layout`` trees; with a ZeRO
    stage the gradient is constrained to the sharded layout right before
    the optimizer update (GSPMD satisfies it with a reduce-scatter fused
    with the cross-replica mean) and outputs are pinned back to the rest
    layout so buffer donation stays stable;
  * the ZeRO-3 gather SCHEDULE is gather-once (ISSUE 15): FSDP leaves
    are constrained to their gathered compute layout ONCE at step entry
    (``make_gather_entry`` from ``specs.gather_schedule`` — ~1
    all-gather/leaf/step instead of per-use), each gather/reduce-scatter
    an independent per-leaf op the latency-hiding scheduler can overlap
    with compute (``ZERO.OVERLAP``; False = barrier-joined sync control
    arm, bit-identical), and the fused optimizer update runs per-shard
    (``opt_update.per_shard_update``);
  * every spec-induced collective carries a ``jax.named_scope`` naming
    the mesh axes it runs over (``zero_reduce_scatter@data``, …) so
    trace_report / Perfetto / cost.* records attribute comm per axis on
    this path too (the PP hop scopes live in parallel/pp.py).

The step builders here ARE the trainer's — ``trainer.make_train_step``
et al. re-export them — so the hot-loop math is defined once and the
legacy call sites (tests, tools, serve) keep working unchanged.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any

import flax.struct
import jax
import jax.numpy as jnp
import optax

from distribuuuu_tpu.config import cfg
from distribuuuu_tpu.models.layers import head_dtype
from distribuuuu_tpu.ops import pallas as kernel_tier, token_head
from distribuuuu_tpu.parallel import sharding as sharding_lib, tp, zero
from distribuuuu_tpu.parallel.partition import specs as specs_lib
from distribuuuu_tpu.resilience import supervisor
from distribuuuu_tpu.telemetry import spans as telemetry_spans
from distribuuuu_tpu.utils import faults
from distribuuuu_tpu.utils.metrics import accuracy, cross_entropy


@flax.struct.dataclass
class TrainState:
    params: Any
    batch_stats: Any
    opt_state: Any
    step: Any  # scalar int32 — drives per-step RNG folding (dropout etc.)
    key: Any  # base PRNG key (not checkpointed; re-derived from RNG_SEED)


def make_image_prep():
    """In-graph half of ``DATA.DEVICE_NORMALIZE`` (captured at step-build
    time): the loader ships raw uint8, the step normalizes in fp32 —
    identical formula/order to the host path (data/transforms.py).

    Dtype-gated at trace time (r4, when the flag became default-True):
    only uint8 batches are normalized. Float batches are ALREADY
    normalized — by the host pipeline, or synthetic (bench.py, tests) —
    and must pass through untouched, else flipping the default would have
    silently re-normalized every float-feeding caller."""
    if not cfg.DATA.DEVICE_NORMALIZE:
        return lambda images: images
    from distribuuuu_tpu.data.transforms import normalize_in_graph

    def prep(images):
        if images.dtype == jnp.uint8:
            return normalize_in_graph(images)
        return images

    return prep


def _collective_scopes(layout) -> tuple[str, str, str]:
    """Attribution scope names for the three spec-induced state
    collectives — the gather-once entry all-gather of FSDP leaves, the
    reduce-scatter into the grads layout, and the all-gather back to the
    rest layout — suffixed with the mesh axes they run over (``@data``),
    so trace_report rollups and Perfetto split comm per axis (the
    overlap-fraction rollup measures compute concurrency against exactly
    these names). ``None`` layout never reaches these."""
    axes = ",".join(specs_lib.added_axes(layout)) or "data"
    return (
        f"zero_gather_once@{axes}",
        f"zero_reduce_scatter@{axes}",
        f"zero_rest_layout@{axes}",
    )


def _barrier(tree):
    """optimization_barrier over a pytree: joins every leaf before any
    consumer — the ZERO.OVERLAP=False control arm (collectives complete
    before the consuming compute starts; identity on values, so the
    ON ≡ OFF bit-identity pin holds by construction)."""
    leaves, treedef = jax.tree.flatten(tree)
    return jax.tree.unflatten(treedef, jax.lax.optimization_barrier(leaves))


def make_gather_entry(layout):
    """The gather-once transform (ROADMAP #1, arXiv:2004.13336): a
    function constraining the scheduled FSDP leaves of a param tree to
    their gathered compute layout ONCE at step entry, derived entirely
    from the spec algebra (specs.gather_schedule — no per-model code).

    Returns ``(gather_fn, n_hoisted)``; ``gather_fn`` is identity when
    nothing is scheduled (stage 0/1, or ``ZERO.GATHER_AHEAD=0``). The
    constraint is applied OUTSIDE the differentiated function, so the
    backward reduce-scatters grads exactly as the stage-1 schedule does
    (the explicit grads constraint in ``apply_grads``); the gathered
    value is one program value consumed by forward AND backward — one
    all-gather per leaf per step instead of one per use site (the PR 14
    census: 195 → ~21 on dp8·zero3[resnet18]). Each leaf's gather is an
    independent op with no serializing join under ``ZERO.OVERLAP``, so
    the latency-hiding scheduler can run layer k+1's gather under layer
    k's compute; ``ZERO.OVERLAP=False`` joins them all first (the
    synchronous A/B control arm)."""
    hoist = specs_lib.gather_schedule(layout, int(cfg.ZERO.GATHER_AHEAD))
    n_hoisted = sum(jax.tree.leaves(hoist))
    if not n_hoisted:
        return (lambda params: params), 0
    gather_to = specs_lib.compute_layout(layout)
    go_scope = _collective_scopes(layout)[0]
    overlap = bool(cfg.ZERO.OVERLAP)

    def gather_fn(params):
        with jax.named_scope(go_scope):
            gathered = jax.tree.map(
                lambda x, sh, h: (
                    jax.lax.with_sharding_constraint(x, sh) if h else x
                ),
                params, gather_to, hoist,
            )
        if not overlap:
            gathered = _barrier(gathered)
        return gathered

    return gather_fn, int(n_hoisted)


def value_and_grad_scoped(loss_fn):
    """``jax.value_and_grad(loss_fn, has_aux=True)`` spelled with
    ``jax.vjp``, which leaves a place to stand between the two passes: the
    transposed pass runs under the ``bwd`` named scope, so its operations
    read ``…/bwd/transpose(jvp(fwd))/…`` in HLO metadata and a trace reader
    splits forward from backward by it. Metadata only: the compiled program
    and the trajectory are the ones ``value_and_grad`` gives
    (tests/test_device_scopes.py)."""

    def grad_fn(params, *rest):
        loss, vjp, aux = jax.vjp(
            lambda p: loss_fn(p, *rest), params, has_aux=True
        )
        with jax.named_scope("bwd"):
            (grads,) = vjp(jnp.ones((), loss.dtype))
        return (loss, aux), grads

    return grad_fn


def train_step_body(model, optimizer, topk: int, accum_steps: int = 1,
                    layout=None, rest_layout=None):
    """The pure step function (plain, or accumulating over micro-batches).

    ``layout`` (a ``specs.state_layout`` dict) is required when
    ``MESH.ZERO`` is on: the gradient is constrained to the ZeRO layout
    right before the optimizer update — GSPMD satisfies it with a
    reduce-scatter, fusing the cross-replica grad mean with the shard
    slicing — and the outputs are pinned back to the state's rest layout
    so buffer donation stays stable across steps. ``None`` (the default)
    adds no constraints: GSPMD propagates the replicated DDP layout
    exactly as before. Building a step WITHOUT a layout while
    ``MESH.ZERO`` is set is refused — the state (create_train_state)
    would rest ZeRO-sharded while the step neither reduce-scatters grads
    nor pins outputs back, silently skipping buffer donation and
    measuring a layout that is neither DDP nor ZeRO.

    ``accum_steps > 1`` runs that many sequential micro-batches, summing
    gradients in-graph before ONE optimizer update (config:
    ``TRAIN.GRAD_ACCUM_STEPS``). The batch must arrive pre-split as
    ``(accum, micro_batch, ...)`` with the micro_batch dim sharded on
    ``data`` (sharding.shard_micro_batch) — splitting on the host is a
    zero-copy view, whereas an in-graph reshape of the data-sharded batch
    dim would make GSPMD redistribute the whole batch over ICI every step.
    Gradients are exact (the mean-CE micro-grads average to the full-batch
    grad); BN stats are per-micro-batch — torch-DDP-with-accumulation
    semantics. HBM holds one micro-batch of activations at a time.

    ``rest_layout`` (the full ``state_layout`` dict, passed by
    :func:`lower` at EVERY stage) pins the output state back to the
    DECLARED rest layout when no ZeRO stage does it already. Without the
    pin, GSPMD is free to rest stage-0 outputs wherever propagation
    lands them — on TP/EP meshes it model-shards LayerNorm/bias leaves
    the declaration says are replicated — so the steady-state layout
    silently drifts from the declaration after the first step AND buffer
    donation quietly drops for every drifted leaf (an output cannot
    alias an input resting in a different sharding): state held twice.
    Found by the static analyzer's replication+donation passes
    (ISSUE 14); on all-replicated dp-only meshes the pin collapses to a
    no-op, so legacy stage-0 programs are untouched. ``None`` (legacy
    direct callers of the re-exported step builders) preserves the old
    unpinned behavior.
    """
    if layout is None and cfg.MESH.ZERO:
        raise ValueError(
            f"MESH.ZERO={cfg.MESH.ZERO} requires the step to be built with "
            "the ZeRO state layout (pass layout=state_layout(...)): the "
            "state rests ZeRO-sharded, and a layout-less step would neither "
            "reduce-scatter grads nor pin rest layouts — a silent "
            "neither-DDP-nor-ZeRO configuration."
        )

    # Non-finite loss guard (resilience/supervisor.py), compiled into the
    # step: metrics always carry a ``nonfinite`` flag; under "skip" the
    # poisoned update is discarded in-graph (pre-step state selected).
    nonfinite_policy = supervisor.validate_policy(str(cfg.TRAIN.NONFINITE))

    if layout is not None:
        _, rs_scope, ag_scope = _collective_scopes(layout)
        # gather-once (ROADMAP #1): the scheduled FSDP leaves are
        # all-gathered ONCE at step entry — see make_gather_entry
        gather_entry, _ = make_gather_entry(layout)
        overlap = bool(cfg.ZERO.OVERLAP)
    else:
        gather_entry, overlap = (lambda p: p), True

    # Kernel tier (ops/pallas/, KERNELS.OPT_UPDATE): the fused one-pass
    # optimizer update, resolved ONCE at step-build time. None ⇒ the
    # optax reference chain (the xla escape hatch / unsupported
    # optimizer); non-None is bit-exact vs it (pinned:
    # tests/test_pallas_kernels.py) and elementwise per leaf. On a mesh
    # of several devices the kernel lowers PER-SHARD through shard_map
    # over the state layout (opt_update.per_shard_update): under ZeRO
    # each rank updates only the 1/N slice it owns — the fused per-shard
    # weight update of arXiv:2004.13336, and the fusion point the
    # gather-once schedule feeds — and at stage 0 each rank updates its
    # replica. (The r14 whole-leaf replicated-pin — gather everything,
    # update, re-scatter — is gone; its recognition in the collectives
    # lint went with it.)
    from distribuuuu_tpu.ops.pallas import opt_update as fused_opt

    fused_update = fused_opt.fused_update_for(
        layout=layout if layout is not None else rest_layout
    )

    def apply_grads(state, grads, new_stats, metrics):
        if layout is not None:
            if not overlap:
                # sync control arm: the backward completes before the
                # first reduce-scatter is issued
                grads = _barrier(grads)
            # ZeRO: reduce-scatter the grad into the sharded update
            grads = zero.constrain(grads, layout["grads"], scope=rs_scope)
            if not overlap:
                # ... and every reduce-scatter lands before the update
                grads = _barrier(grads)
        with jax.named_scope("optimizer_update"):
            if fused_update is not None:
                new_params, new_opt_state = fused_update(
                    state.params, grads, state.opt_state
                )
            else:
                updates, new_opt_state = optimizer.update(
                    grads, state.opt_state, state.params
                )
                new_params = optax.apply_updates(state.params, updates)
        if layout is not None:
            # pin rest layouts (stage 1: params re-gathered to replicated;
            # stage 3: params stay data-sharded) — keeps donation stable
            new_params = zero.constrain(
                new_params, layout["params"], scope=ag_scope
            )
            new_opt_state = tp.constrain_like(
                new_opt_state, grads, layout["opt"]
            )
        elif rest_layout is not None:
            # stage 0: same pin, declared base layout (docstring above —
            # no-op on all-replicated meshes, drift+donation fix on
            # TP/EP meshes)
            new_params = zero.constrain(
                new_params, rest_layout["params"], scope="rest_layout"
            )
            new_opt_state = tp.constrain_like(
                new_opt_state, grads, rest_layout["opt"]
            )
        new_state = TrainState(
            params=new_params,
            batch_stats=new_stats,
            opt_state=new_opt_state,
            step=state.step + 1,
            key=state.key,
        )
        return supervisor.guard_nonfinite(
            state, new_state, metrics, nonfinite_policy
        )

    # λ for the MoE load-balancing aux (models/vit.MoeMlp sows per-block
    # values into ``intermediates``); captured at step-build time. Zero
    # overhead for dense archs: the collection stays empty.
    moe_aux_weight = float(cfg.MODEL.MOE.AUX_WEIGHT)
    moe_z_weight = float(cfg.MODEL.MOE.Z_WEIGHT)
    prep_images = make_image_prep()
    # A token model that leaves its head to the step (models/olmoe.py
    # ``head_kernel``, ``head_chunk``): head, loss and hits ``head_chunk``
    # positions at a time (ops/token_head.py), never the [B, S, V] logits.
    # Image models and the gpt_* archs have no such hook and keep the
    # program they had. Where the loss is more than one cross-entropy over
    # one head (models/ouro.py: a head a pass, weighted by an exit
    # distribution the model emits), the model's ``head_loss`` takes what
    # ``hidden_only`` returned and gives (loss, hits, step metrics).
    head_kernel = getattr(model, "head_kernel", None)
    loss_chunk = getattr(model, "head_chunk", 0)

    def one_head_loss(hidden, kernel, labels, *, topk):
        with jax.named_scope("lm_head"):
            loss, hits = token_head.loss_and_accuracy(
                hidden, kernel, labels, topk=topk, chunk=loss_chunk,
            )
        return loss, hits, {"ce": loss}

    head_loss = getattr(model, "head_loss", one_head_loss)
    # the streams of the step's key a model draws from beside dropout's
    # (models/sdar_moe.py: the noise of its diffusion objective); none for
    # every other arch, whose program is what it was
    noise_streams = tuple(getattr(model, "noise_streams", ()))
    # FAULTS.NAN_STEP (utils/faults.py): trace-time gate — None (the
    # common case) compiles nothing in; an int multiplies the loss by
    # where(step==k, NaN, 1), poisoning loss AND grads at exactly step k.
    nan_step = faults.nan_injection_step()

    def loss_fn(params, stats, images, labels, key, step):
        images = prep_images(images)
        # attribution scope: the forward (and, through autodiff's
        # transpose, its backward as transpose(fwd)/...) is nameable in
        # HLO op metadata — trace_report / Perfetto split compute from
        # the collective/update scopes below
        with jax.named_scope("fwd"):
            logits, mutated = model.apply(
                {"params": params, "batch_stats": stats},
                images,
                train=True,
                mutable=["batch_stats", "intermediates", "moe_stats",
                         "moe_z", "moe_load"],
                rngs={"dropout": key, **{name: key for name in noise_streams}},
                **({} if head_kernel is None else {"hidden_only": True}),
            )
            if head_kernel is not None:
                # the hits take the logits' place on the way to step_metrics
                loss, logits, extra = head_loss(
                    logits, head_kernel(params), labels, topk=(1, topk)
                )
        if head_kernel is None:
            loss, extra = cross_entropy(logits, labels), {}
        aux = jax.tree.leaves(mutated.get("intermediates", {}))
        if aux and moe_aux_weight:
            loss = loss + moe_aux_weight * sum(aux) / len(aux)
        z = jax.tree.leaves(mutated.get("moe_z", {}))
        if z:
            extra["moe_aux"], extra["moe_z"] = sum(aux) / len(aux), sum(z) / len(z)
            loss = loss + moe_z_weight * extra["moe_z"]
        load = jax.tree.leaves(mutated.get("moe_load", {}))
        if load:
            extra["moe_load_max_over_mean"] = jnp.stack(load).max()
        if nan_step is not None:
            loss = loss * jnp.where(
                step == nan_step, jnp.float32(jnp.nan), jnp.float32(1.0)
            )
        # dispatch-MoE observability: per-block dropped-assignment
        # fractions (models/vit.MoeMlp sows the sum; empty for dense and
        # partial-MoE models — zero overhead there)
        dstats = jax.tree.leaves(mutated.get("moe_stats", {}))
        if dstats:
            extra["moe_dropped"] = sum(dstats) / len(dstats)
        return loss, (logits, mutated.get("batch_stats", {}), extra)

    grad_fn = value_and_grad_scoped(loss_fn)

    def step_metrics(loss, logits, labels, extra):
        if head_kernel is None:
            acc1, acck = accuracy(logits, labels, topk=(1, topk))
        else:
            acc1, acck = logits  # loss_fn took the hits with the head
        return {"loss": loss, "top1": acc1, "topk": acck, **extra}

    def train_step(state: TrainState, batch):
        step_key = jax.random.fold_in(state.key, state.step)
        # gather-once: FSDP leaves are constrained to their gathered
        # compute layout HERE, outside grad_fn — forward and backward
        # consume the one gathered value, and the explicit grads
        # constraint in apply_grads stays the lone reduce-scatter
        params = gather_entry(state.params)
        (loss, (logits, new_stats, extra)), grads = grad_fn(
            params, state.batch_stats, batch["image"], batch["label"],
            step_key, state.step,
        )
        return apply_grads(
            state, grads, new_stats,
            step_metrics(loss, logits, batch["label"], extra),
        )

    def accum_train_step(state: TrainState, micro):
        step_key = jax.random.fold_in(state.key, state.step)
        # gather-once, OUTSIDE the microbatch scan: every micro-step
        # closes over the same gathered params (one gather per optimizer
        # step, not per microbatch); each micro-backward reduce-scatters
        # into the standing sharded grad-sum
        gathered_params = gather_entry(state.params)
        if micro["image"].shape[0] != accum_steps:
            raise ValueError(
                f"accum train step wants a pre-split (accum={accum_steps}, "
                f"micro_batch, ...) input, got leading dim "
                f"{micro['image'].shape[0]} — use sharding.shard_micro_batch"
            )

        def body(carry, mb):
            stats, gsum, i = carry
            mkey = jax.random.fold_in(step_key, i)
            (loss, (logits, new_stats, extra)), grads = grad_fn(
                gathered_params, stats, mb["image"], mb["label"], mkey,
                state.step,
            )
            gsum = jax.tree.map(jnp.add, gsum, grads)
            return (new_stats, gsum, i + 1), step_metrics(
                loss, logits, mb["label"], extra
            )

        zeros = jax.tree.map(jnp.zeros_like, state.params)
        if layout is not None:
            # sharded accumulation buffer: each micro-grad reduce-scatters
            # into it (ZeRO-2 semantics during accumulation — the standing
            # grad-sum holds 1/N per rank)
            zeros = zero.constrain(zeros, layout["grads"])
        (new_stats, gsum, _), micro_metrics = jax.lax.scan(
            body, (state.batch_stats, zeros, jnp.int32(0)), micro,
            length=accum_steps,
        )
        grads = jax.tree.map(lambda g: g / accum_steps, gsum)
        metrics = jax.tree.map(jnp.mean, micro_metrics)
        return apply_grads(state, grads, new_stats, metrics)

    return accum_train_step if accum_steps > 1 else train_step


def make_train_step(model, optimizer, topk: int, accum_steps: int = 1,
                    layout=None, rest_layout=None, mesh=None):
    """Compile-once train step: fwd + CE loss + bwd + SGD + metrics
    (≙ the hot loop body, ref: trainer.py:37-58). Its trace declares the
    first device of ``mesh`` (passed by :func:`lower`) as the one it is
    compiled for, attached or described (``kernel_tier.lowered_for``): what a
    model plans from the device's size (``models/ouro.plan_kept_proj``) it
    plans for that device, and for none without a mesh."""
    body = train_step_body(model, optimizer, topk, accum_steps, layout=layout,
                           rest_layout=rest_layout)
    device = None if mesh is None else mesh.devices.flat[0]

    @functools.wraps(body)
    def train_step(state, batch):
        with kernel_tier.lowered_for(device):
            return body(state, batch)

    return jax.jit(train_step, donate_argnums=0)


def make_eval_step(model, topk: int, layout=None):
    """Masked eval step: per-batch metric sums + valid count
    (≙ validate body, ref: trainer.py:77-89).

    ``layout`` (passed by :func:`lower` when a ZeRO stage is on) applies
    the same gather-once schedule the train step uses: at stage 3 the
    FSDP leaves are gathered once at eval entry instead of per use site.
    ``None`` (legacy direct callers — serve, tools) keeps the old
    per-use behavior."""
    prep_images = make_image_prep()
    gather_entry = (
        make_gather_entry(layout)[0] if layout is not None else (lambda p: p)
    )

    head_kernel = getattr(model, "head_kernel", None)
    loss_chunk = getattr(model, "head_chunk", 0)
    # of what ``hidden_only`` returned, the state the head evaluates
    # (models/ouro.py: the last pass's)
    eval_hidden = getattr(model, "eval_hidden", lambda hidden: hidden)
    # ... and the head's column for a token id, where the head holds a slice
    # of the vocabulary's rows (models/glm_moe.py; its ``head_loss`` does the
    # same in training)
    head_labels = getattr(model, "head_labels", lambda labels: labels)
    # ... or, where the objective is not next-token cross-entropy
    # (models/sdar_moe.py: a position's own token, weighted by its noise),
    # the labels and a weight a position from what ``hidden_only`` returned
    eval_targets = getattr(model, "eval_targets", None)

    def eval_step(state: TrainState, batch):
        params = gather_entry(state.params)
        with jax.named_scope("eval_fwd"):
            logits = model.apply(
                {"params": params, "batch_stats": state.batch_stats},
                prep_images(batch["image"]),
                train=False,
                **({} if head_kernel is None else {"hidden_only": True}),
            )
            if head_kernel is not None:
                outputs, logits = logits, eval_hidden(logits)
        mask = batch["mask"]
        labels = batch["label"]
        if head_kernel is not None:
            weights = None
            if eval_targets is None:
                labels = head_labels(labels)
            else:
                labels, weights = eval_targets(outputs, labels)
            # the head in chunks, as in the train step (ops/token_head.py)
            nll, rank = token_head.head_stats(
                logits, head_kernel(params), labels, chunk=loss_chunk
            )
            mask = jnp.broadcast_to(mask[:, None], labels.shape)
            weighted = mask if weights is None else mask * weights
            return {
                "loss_sum": (nll * weighted).sum(),
                "correct1": ((rank < 1) * weighted).sum(),
                "correctk": ((rank < topk) * weighted).sum(),
                "count": mask.sum(),
            }
        if logits.ndim == 3:
            # per-token logits (the LM's [B, S, V]): every token of a
            # masked-in sequence is one example — flatten the token dim
            # and broadcast the per-sequence mask over it. The image path
            # ([B, C]) is byte-identical to before; this is the same
            # one-eval-step generalization utils/metrics.py applies.
            mask = jnp.broadcast_to(mask[:, None], labels.shape).reshape(-1)
            logits = logits.reshape(-1, logits.shape[-1])
            labels = labels.reshape(-1)
        logp = jax.nn.log_softmax(
            logits.astype(head_dtype(logits.dtype)), axis=-1
        )
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
        _, pred = jax.lax.top_k(logits, topk)  # topk pre-clamped (effective_topk)
        hits = pred == labels[:, None]
        c1 = (hits[:, :1].any(axis=1) * mask).sum()
        ck = (hits.any(axis=1) * mask).sum()
        return {
            "loss_sum": (nll * mask).sum(),
            "correct1": c1,
            "correctk": ck,
            "count": mask.sum(),
        }

    return jax.jit(eval_step)


# ------------------------------------------------------------- the entry


@dataclass
class Lowered:
    """Everything the epoch loop needs for one validated topology — built
    from specs alone, no topology case analysis left at the call site."""

    mesh: Any
    topology: Any
    layout: dict           # {"params","opt","grads"} NamedSharding trees
    step_layout: dict | None  # layout when a ZeRO stage is on, else None
    train_step: Any
    eval_step: Any
    accum: int = 1
    model: Any = None
    optimizer: Any = None  # kept so abstract_args can shape the opt state
    im_size: int = 32

    def init_state(self, key, im_size: int):
        """Fresh TrainState resting in this topology's layout."""
        from distribuuuu_tpu import trainer

        return trainer.create_train_state(
            self.model, key, self.mesh, im_size, layout=self.layout
        )

    def put_batch(self, host_batch):
        """Place one host batch per the declared batch specs (accum-aware)."""
        if self.accum > 1:
            return sharding_lib.shard_micro_batch(
                self.mesh, host_batch, self.accum
            )
        return sharding_lib.shard_batch(self.mesh, host_batch)

    def abstract_args(self, batch_size: int | None = None, *,
                      with_mask: bool = False):
        """``(state_sds, batch_sds)`` — ShapeDtypeStructs carrying the
        DECLARED shardings for this topology's step arguments.

        The static analyzer (distribuuuu_tpu/analysis/) lowers and
        compiles the step against these to read GSPMD's verdict (compiled
        shardings, donation aliasing, the collective schedule) without
        ever materializing state or data — and without a second compile:
        every program pass shares the one lowered/compiled bundle. The
        placement mirrors ``trainer.create_train_state`` exactly: params
        per the declared layout, batch_stats replicated, optimizer state
        per the opt layout on param-structured subtrees (the abstract
        twin of ``tp.constrain_like``) and replicated elsewhere, batch
        leaves per ``specs.BATCH_TABLE``. ``batch_size`` defaults to two
        samples per data rank (shape-only — placement does not depend on
        batch geometry).
        """
        import flax
        import numpy as np

        from jax.sharding import NamedSharding, PartitionSpec as P

        repl = NamedSharding(self.mesh, P())

        def sds(leaf, sh):
            return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=sh)

        abstract = specs_lib.abstract_state(self.model, self.im_size)
        unboxed = flax.linen.meta.unbox(abstract)
        params = jax.tree.map(sds, unboxed["params"], self.layout["params"])
        stats = jax.tree.map(
            lambda l: sds(l, repl), unboxed.get("batch_stats", {})
        )
        opt_abs = jax.eval_shape(self.optimizer.init, unboxed["params"])
        tdef = jax.tree.structure(unboxed["params"])

        def is_param_shaped(node):
            try:
                return jax.tree.structure(node) == tdef
            except (TypeError, ValueError):
                return False

        def place_opt(node):
            if is_param_shaped(node):
                return jax.tree.map(sds, node, self.layout["opt"])
            return jax.tree.map(lambda l: sds(l, repl), node)

        opt = jax.tree.map(place_opt, opt_abs, is_leaf=is_param_shaped)
        state = TrainState(
            params=params, batch_stats=stats, opt_state=opt,
            step=sds(jax.ShapeDtypeStruct((), np.int32), repl),
            key=sds(jax.eval_shape(lambda: jax.random.key(0)), repl),
        )

        data = int(dict(self.mesh.shape).get("data", 1))
        B = int(batch_size) if batch_size else max(8, 2 * data)
        dummy = specs_lib.model_dummy_input(self.model, self.im_size)
        image = jax.ShapeDtypeStruct((B,) + dummy.shape[1:], dummy.dtype)
        # token models label per token ([B, S]); image models per sample
        label_shape = (B,) + (dummy.shape[1:] if image.ndim == 2 else ())
        batch = {
            "image": image,
            "label": jax.ShapeDtypeStruct(label_shape, np.int32),
        }
        if with_mask:
            batch["mask"] = jax.ShapeDtypeStruct((B,), np.float32)
        # token models (a batch_spec_table hook) shard [B, S] leaves over
        # (data, seq); image models keep the blanket data-only layout
        table = specs_lib.batch_table_for(self.model)
        batch = {
            k: sds(v, NamedSharding(self.mesh, table.spec_for(k)))
            for k, v in batch.items()
        }
        return state, batch


@telemetry_spans.setup_timer("lower")
def lower(model, optimizer, topk: int, *, mesh, topology, im_size: int,
          accum: int = 1) -> Lowered:
    """Build the train/eval step for ANY validated topology from
    the declared specs — the single code path the trainer's per-topology
    case analysis collapsed into.

    The layout comes from ``specs.state_layout`` (base declarations +
    ZeRO transform per ``topology.zero``); the step body applies the
    layout constraints exactly when a stage is on, so stage-0 programs
    are bit-identical to the pre-partition trainer's.
    """
    layout = specs_lib.state_layout(model, mesh, im_size, topology.zero)
    step_layout = layout if topology.zero else None
    if step_layout is not None:
        _log_zero_schedule(step_layout, topology)
    train_step = make_train_step(
        model, optimizer, topk, accum_steps=accum, layout=step_layout,
        rest_layout=layout, mesh=mesh,
    )
    return Lowered(
        mesh=mesh, topology=topology, layout=layout, step_layout=step_layout,
        train_step=train_step,
        eval_step=make_eval_step(model, topk, layout=step_layout),
        accum=max(1, accum),
        model=model, optimizer=optimizer, im_size=im_size,
    )


_logged_schedules: set = set()


def _log_zero_schedule(layout, topology) -> None:
    """Record the derived ZeRO collective schedule ONCE per distinct
    shape at lowering time (kind="zero.schedule", telemetry/schema.py):
    how many leaves rest ZeRO-sharded, how many entry gathers the
    gather-once transform hoisted, and the overlap knobs — so a run's
    telemetry states the schedule it trained under (the same facts the
    static analyzer's census referees post-hoc)."""
    hoist = specs_lib.gather_schedule(layout, int(cfg.ZERO.GATHER_AHEAD))
    sharded = sum(
        1 for sh in jax.tree.leaves(layout["grads"])
        if "data" in specs_lib.spec_axes(sh.spec)
    )
    key = (
        int(topology.zero), sharded, sum(jax.tree.leaves(hoist)),
        bool(cfg.ZERO.OVERLAP), int(cfg.ZERO.GATHER_AHEAD),
    )
    if key in _logged_schedules:
        return
    _logged_schedules.add(key)
    from distribuuuu_tpu.utils.jsonlog import metrics_log

    metrics_log(
        "zero.schedule", stage=key[0], leaves=len(jax.tree.leaves(layout["params"])),
        sharded=key[1], hoisted=key[2], overlap=key[3], gather_ahead=key[4],
    )
