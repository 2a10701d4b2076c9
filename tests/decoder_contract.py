"""What a decoder on the normal path must satisfy, written once.

Six architectures train through ``lowering.lower`` and ``train_net.py``:
OLMoE, Ouro, GLM-4.7-Flash, LFM2-24B-A2B, Trinity-Mini and SDAR-30B-A3B-Chat
(the one that is not trained by next-token cross-entropy under a causal mask:
``tests/test_sdar_moe.py``). Each is a ``Row``
of ``ROWS`` (its names, its YAML, its plain reference under
``benchmark/reference/``, and the values its tests expect) and ONE collected
file, ``tests/test_<arch>.py``, whose ``Test...`` class lists the contracts
below that the architecture answers and sets ``row``. pytest collects nothing
from this module (no ``test_`` in its name, no ``Test`` in its classes'), and
the driver's ``--dist loadfile`` keeps each architecture's file on a worker of
its own, which is why the six rows are not one parametrised file.

The contracts:

* ``Decoder``: registered at both sizes with the published widths; loss terms
  and every gradient equal the reference's; every leaf placed by the spec
  table; the traits shared code asks for; serving's one-sentence refusal;
  ``train_net.py`` on the YAML at the tiny size.
* ``ThroughLower``: the step ``lowering.lower`` builds from the YAML reports
  the reference's terms, and holds no ``while`` and one walk of the head.
* ``Recomputes``: the recomputed step is the step that keeps everything, and
  keeps the branches the backward reads (``models/ouro.branch_out``).
* ``RecomputesNothingInItsCell``: the cell that keeps every activation lowers
  to the step without the branches' names.
* ``KeepsTheFlashKernels``: what a recomputed block keeps of the flash
  kernels changes no bit, and the plan record says it.
* ``ComputesInBfloat16``: the bfloat16 program stays near the float32
  reference.
* ``HoldsAShare``: the shares of a mixture add up to the whole layer.

A hook named ``..._of_its_own`` is where an architecture's file adds what only
it asserts of the same program. A new decoder is a row here, its reference,
its class, and the tests of what is new in it; its tiny preset is sized by
what these contracts compile (ROADMAP D13).
"""

import dataclasses
import functools
import importlib.util
import json
import os
import re
import types
from typing import Any, Callable, NamedTuple

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import distribuuuu_tpu.config as config
from distribuuuu_tpu import models, trainer
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu.models import glm_moe, ouro, sdar_moe, share
from distribuuuu_tpu.ops import moe as moe_ops
from distribuuuu_tpu.ops import token_head
from distribuuuu_tpu.parallel import mesh as mesh_lib
from distribuuuu_tpu.parallel.partition import lowering, specs, topology

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, CHUNK = 512, 48
SLIDING, FULL = "sliding_attention", "full_attention"


def _reference(name):
    """``benchmark/reference/<name>.py``, imported and never edited."""
    spec = importlib.util.spec_from_file_location(
        f"{name}_reference", os.path.join(REPO, "benchmark", "reference", f"{name}.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ------------------------------------------------------------------ helpers
def walk(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from walk(inner)


def forward_matmuls(jaxpr, kernels) -> int:
    """``x [B, S, in] . W [in, out]`` with W's shape among ``kernels``, in a
    jaxpr and the jaxprs inside it: a projection's FORWARD matmul, wherever
    it runs (its dx contracts W's other dimension, its dW no W at all)."""
    return sum(
        eqn.primitive.name == "dot_general"
        and tuple(eqn.invars[1].aval.shape) in kernels
        and eqn.params["dimension_numbers"][0] == ((2,), (0,))
        for eqn in walk(jaxpr))


def wide_matmuls(jaxpr, columns) -> list:
    """The ``dot_general``s with ``columns`` (the head's) among their
    dimensions."""
    return [
        eqn for eqn in walk(jaxpr)
        if eqn.primitive.name == "dot_general" and any(
            columns in getattr(v.aval, "shape", ())
            for v in list(eqn.invars) + list(eqn.outvars))]


def assert_trees_close(got, want, tolerance):
    flat = jax.tree_util.tree_leaves_with_path(got)
    for (path, g), w in zip(flat, jax.tree.leaves(want), strict=True):
        norm = float(jnp.linalg.norm(w))
        assert norm > 0, jax.tree_util.keystr(path)
        assert float(jnp.linalg.norm(g - w)) <= tolerance * norm, jax.tree_util.keystr(path)


def build(row, **kw):
    return models.build_model(row.tiny, num_classes=VOCAB, dtype=jnp.float32, **kw)


def variables(params, biases) -> dict:
    return {"params": params} if biases is None else {
        "params": params, "batch_stats": biases}


def vocabulary(model) -> tuple:
    """(first row, rows) of the vocabulary the model's embedding and head
    hold: a share's rank holds its own rows."""
    held = getattr(model, "vocab_held", model.vocab_size)
    return getattr(model, "share_rank", 0) * held, held


def seeded(model, batch=2, seq=100, seed=0):
    """(params, biases, tokens, labels): weights from the program's
    initialiser with the norm scales moved off 1, so that a dropped or
    misplaced scale would show; LFM2's filters made large, so that a shifted
    tap would; Ouro's gate wide enough for its distribution to leave 1/2;
    the routers' biases off 0, so that a router that ignored them would show
    (None where the architecture has none: OLMoE's, Ouro's and SDAR's state
    holds no ``batch_stats``); and ids from the rows of the vocabulary the
    rank holds."""
    shares = hasattr(model, "share_rank")
    k_init, k_tok, k_scale, *k_bias = jax.random.split(
        jax.random.key(seed), 4 if shares else 3)
    state = flax.linen.meta.unbox(model.init(k_init, model.dummy_input()))
    flat, tree = jax.tree_util.tree_flatten_with_path(state["params"])
    keys = jax.random.split(k_scale, len(flat))

    def moved(path, leaf, key):
        name = jax.tree_util.keystr(path)
        if "scale" in name:
            return leaf * (1 + 0.2 * jax.random.normal(key, leaf.shape))
        return jax.random.normal(key, leaf.shape) if "filter" in name else leaf

    params = jax.tree.unflatten(
        tree, [moved(path, leaf, k) for (path, leaf), k in zip(flat, keys)])
    if "exit_gate" in params:
        params["exit_gate"] = {"kernel": params["exit_gate"]["kernel"] * 10,
                               "bias": jnp.asarray([0.3])}
    biases = jax.tree.map(
        lambda b: 0.02 * jax.random.normal(k_bias[0], b.shape),
        state["batch_stats"]) if "batch_stats" in state else None
    first, held = vocabulary(model)
    ids = first + jax.random.randint(k_tok, (batch, seq + 1), 0, held, jnp.int32)
    return params, biases, ids[:, :-1], ids[:, 1:]


class Aux(NamedTuple):
    extra: dict   # the step's metrics
    after: Any    # the biases the step leaves (None where there are none)
    outputs: Any  # what ``hidden_only`` returned
    hits: Any


def program_loss(model, params, biases, tokens, labels):
    """(loss, Aux): the two calls the step's ``loss_fn`` makes."""
    if biases is None:
        outputs, after = model.apply(
            {"params": params}, tokens, train=True, hidden_only=True), None
    else:
        outputs, mutated = model.apply(
            variables(params, biases), tokens, train=True, hidden_only=True,
            mutable=["batch_stats"])
        after = mutated["batch_stats"]
    loss, hits, extra = model.head_loss(
        outputs, model.head_kernel(params), labels, topk=(1, 5))
    return loss, Aux(extra, after, outputs, hits)


OLMOE_AUX_W, OLMOE_Z_W = 0.01, 0.001


def olmoe_terms(model, params, tokens, labels, chunk=CHUNK):
    """OLMoE's three loss terms and the experts chosen, from the modules the
    step's ``loss_fn`` calls (its terms are sown, not returned by a
    ``head_loss``)."""
    hidden, sown = model.apply(
        {"params": params}, tokens, train=True, hidden_only=True,
        mutable=["intermediates", "moe_z", "moe_stats", "moe_load", "moe_route"],
    )
    ce, _ = token_head.loss_and_accuracy(
        hidden, model.head_kernel(params), labels, topk=(1,), chunk=chunk
    )

    def mean(name):
        leaves = jax.tree.leaves(sown[name])
        return sum(leaves) / len(leaves)

    return {
        "ce": ce, "load_balance": mean("intermediates"),
        "router_z": mean("moe_z"), "dropped": mean("moe_stats"),
        "experts": jnp.stack(jax.tree.leaves(sown["moe_route"])),
    }


def olmoe_total(terms):
    return terms["ce"] + OLMOE_AUX_W * terms["load_balance"] + OLMOE_Z_W * terms["router_z"]


def _olmoe_loss(model, params, biases, tokens, labels):
    terms = olmoe_terms(model, params, tokens, labels)
    return olmoe_total(terms), Aux(terms, None, None, None)


def reference_loss(row, params, biases, tokens, labels, arch, **kw):
    """The reference's terms, ``"loss"`` among them."""
    args = (params, tokens, labels) if biases is None else (params, biases, tokens, labels)
    terms = row.reference.loss(*args, architecture=arch, **kw)
    return terms if row.total is None else {**terms, "loss": row.total(terms)}


def reference_value_and_grad(row, biases, tokens, labels, arch):
    """``params -> ((loss, terms), gradients)`` of the reference."""
    def total(p):
        terms = reference_loss(row, p, biases, tokens, labels, arch)
        return terms["loss"], terms

    return jax.value_and_grad(total, has_aux=True)


def mixture_biases(model, biases):
    """``[mixtures, E]`` in the reference's order: the trunk's mixtures, then
    GLM's MTP module's."""
    layers = len(model.layer_kinds) if hasattr(model, "layer_kinds") else model.depth
    dense = model.dense_here if hasattr(model, "dense_here") else model.dense_layers
    names = [f"Block_{i}" for i in range(dense, layers)]
    names += ["mtp_block"] * getattr(model, "mtp_layers", 0)
    return jnp.stack([biases[n]["moe"]["router_bias"] for n in names])


def set_cfg(values: dict) -> None:
    """``{"LM.SHARE_CHIPS": 2}`` onto the global cfg."""
    for key, value in values.items():
        *nodes, leaf = key.split(".")
        setattr(functools.reduce(getattr, nodes, cfg), leaf, value)


def lowered(row, chunk=CHUNK, **overrides):
    """The step ``lowering.lower`` builds on the 8-device data mesh from the
    row's recipe at the tiny size, 100 tokens a sequence, the head in
    ``chunk``s; ``overrides`` are ``LM`` keys in lower case. Leaves the
    global cfg set: the caller resets it."""
    from distribuuuu_tpu.utils.optim import construct_optimizer

    config.reset_cfg()
    if row.step_from_yaml:
        config.merge_from_file(row.yaml)
    cfg.MODEL.ARCH = row.tiny
    cfg.MODEL.NUM_CLASSES = VOCAB
    cfg.LM.SEQ_LEN = 100
    cfg.DEVICE.COMPUTE_DTYPE = "float32"
    cfg.MESH.DATA = 8
    set_cfg({**row.step_cfg, **{f"LM.{k.upper()}": v for k, v in overrides.items()}})
    layout = trainer.check_trainer_mesh()
    model = trainer.build_model_from_cfg(layout).clone(head_chunk=chunk)
    return lowering.lower(
        model, construct_optimizer(), 5, mesh=mesh_lib.build_mesh(data=8),
        topology=layout, im_size=32,
    )


def one_step(row, **overrides):
    """The row's ``lowered`` step, run once on 8 x 100 tokens of the rank's
    rows of the vocabulary, as host copies: the fresh state, what evaluation
    and the step returned, the state after, and the step's compiled text
    and jaxpr."""
    low = lowered(row, **overrides)
    try:
        first, held = vocabulary(low.model)
        ids = first + np.random.default_rng(1).integers(0, held, (8, 101)).astype(np.int32)
        host = {"image": ids[:, :-1], "label": ids[:, 1:], "mask": np.ones(8, np.float32)}
        state = low.init_state(jax.random.key(0), 32)
        params, biases = jax.device_get((state.params, state.batch_stats))
        moments = [s for s in jax.tree.leaves(
            state.opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
        abstract, avals = low.abstract_args(8)
        avals = {k: jax.ShapeDtypeStruct((8, 100), jnp.int32, sharding=v.sharding)
                 for k, v in avals.items()}
        batch = low.put_batch(host)
        evaluated = jax.device_get(low.eval_step(state, batch))
        state, metrics = low.train_step(state, {k: batch[k] for k in ("image", "label")})
        return types.SimpleNamespace(
            model=low.model, overrides=overrides, tokens=host["image"],
            labels=host["label"],
            params=params, biases=biases if row.biases else None,
            moment_leaves=len(jax.tree.leaves(moments[0].mu)),
            evaluated=evaluated, metrics=jax.device_get(metrics),
            params_after=jax.device_get(state.params),
            biases_after=jax.device_get(state.batch_stats),
            lr=float(cfg.OPTIM.BASE_LR), wd=float(cfg.OPTIM.WEIGHT_DECAY),
            text=low.train_step.lower(abstract, avals).compile().as_text(),
            jaxpr=jax.make_jaxpr(low.train_step)(abstract, avals).jaxpr,
        )
    finally:
        config.reset_cfg()


def read_only(tree):
    """Host copies no test can write to."""
    def frozen(leaf):
        leaf = np.asarray(leaf)
        leaf.setflags(write=False)
        return leaf

    return jax.tree.map(frozen, tree)


def room_for_kept_products(monkeypatch, model, params, batch: tuple, kept: int) -> dict:
    """Patch the capacity source of Ouro's planner (``models/ouro._capacity_bytes``:
    no model field, no config key) with the number at which exactly ``kept``
    block applications of ``model`` on ``batch`` (sequences, length) keep the
    MLP's two products; returns the plan at no capacity."""
    monkeypatch.setattr(ouro, "_capacity_bytes", lambda: None)
    size = sum(p.size * p.dtype.itemsize for p in jax.tree.leaves(params))
    base = ouro.loop_plan(model, *batch, size)
    assert base["kept_proj_applications"] == 0 and base["capacity_bytes"] is None
    proj = 2 * batch[0] * batch[1] * model.mlp_hidden * jnp.dtype(model.dtype).itemsize
    capacity = base["planned_bytes"] + ouro.RESERVE_BYTES + kept * proj + proj // 2
    monkeypatch.setattr(ouro, "_capacity_bytes", lambda: capacity)
    return base


def records(directory, kind) -> list:
    """The telemetry records of one kind under ``directory``, file by file."""
    return [r for name in sorted(os.listdir(directory))
            for r in map(json.loads, open(os.path.join(directory, name)))
            if r.get("kind") == kind]


# ---------------------------------------------------------------- the rows
def _olmoe_architecture(model) -> dict:
    return {
        "layers": model.depth, "hidden_size": model.dim,
        "intermediate_size": model.expert_hidden,
        "num_attention_heads": model.num_heads, "num_experts": model.num_experts,
        "num_experts_per_tok": model.top_k, "rms_norm_eps": model.rms_norm_eps,
        "rope_theta": model.rope_theta, "vocab_size": model.vocab_size,
        "max_position_embeddings": model.seq_len,
    }


def _ouro_architecture(model) -> dict:
    return {
        "layers": model.depth, "total_ut_steps": model.passes,
        "hidden_size": model.dim, "intermediate_size": model.mlp_hidden,
        "num_attention_heads": model.num_heads, "rms_norm_eps": model.rms_norm_eps,
        "rope_theta": model.rope_theta, "vocab_size": model.vocab_size,
        "exit_entropy_weight": model.exit_beta,
    }


def _share_architecture(model) -> dict:
    """What the three shares' references read alike."""
    return {
        "hidden_size": model.dim, "num_attention_heads": model.num_heads,
        "intermediate_size": model.mlp_hidden,
        "moe_intermediate_size": model.expert_hidden,
        "num_experts_per_tok": model.top_k, "rope_theta": model.rope_theta,
        "vocab_size": model.vocab_size, "share_chips": model.share_chips,
        "share_rank": model.share_rank, "experts_held": model.held[1],
        "vocab_held": model.vocab_held, "bias_update_rate": model.bias_rate,
        "balance_loss_weight": model.aux_weight,
    }


def _glm_architecture(model) -> dict:
    return {
        **_share_architecture(model),
        "layers": model.depth, "first_k_dense_replace": model.dense_layers,
        "num_nextn_predict_layers": model.mtp_layers,
        "q_lora_rank": model.q_lora_rank, "kv_lora_rank": model.kv_lora_rank,
        "qk_nope_head_dim": model.qk_nope_head_dim,
        "qk_rope_head_dim": model.qk_rope_head_dim, "v_head_dim": model.v_head_dim,
        "n_routed_experts": model.num_experts,
        "n_shared_experts": model.shared_experts,
        "routed_scaling_factor": model.routed_scale,
        "rms_norm_eps": model.rms_norm_eps, "mtp_loss_weight": model.mtp_weight,
    }


def _lfm2_architecture(model) -> dict:
    return {
        **_share_architecture(model),
        "layer_types": list(model.layer_kinds), "num_dense_layers": model.dense_here,
        "num_key_value_heads": model.kv_heads, "conv_L_cache": model.conv_taps,
        "num_experts": model.num_experts,
        "routed_scaling_factor": model.routed_scale,
        "route_norm_eps": model.route_norm_eps, "norm_eps": model.norm_eps,
    }


def _afmoe_architecture(model) -> dict:
    return {
        **_share_architecture(model),
        "layer_types": list(model.layer_kinds), "num_dense_layers": model.dense_here,
        "num_key_value_heads": model.kv_heads, "head_dim": model.head_dim,
        "sliding_window": model.sliding_window,
        "num_experts": model.num_experts,
        "num_shared_experts": model.shared_experts,
        "route_scale": model.routed_scale, "route_norm_eps": 1e-20,
        "mup_enabled": model.mup, "rms_norm_eps": model.norm_eps,
    }


def _sdar_architecture(model) -> dict:
    return {
        "layers": len(model.layer_kinds), "hidden_size": model.dim,
        "num_attention_heads": model.num_heads, "num_key_value_heads": model.kv_heads,
        "head_dim": model.head_dim, "moe_intermediate_size": model.expert_hidden,
        "num_experts": model.num_experts, "num_experts_per_tok": model.top_k,
        "rms_norm_eps": model.norm_eps, "rope_theta": model.rope_theta,
        "vocab_size": model.vocab_size, "share_chips": model.share_chips,
        "share_rank": model.share_rank, "experts_held": model.held[1],
        "vocab_held": model.vocab_held, "balance_loss_weight": model.aux_weight,
        "block_length": model.block_length, "noise_eps": model.noise_eps,
        "mask_id": model.mask_token,
    }


def stream_key(key, stream=sdar_moe.NOISE_STREAM):
    """What ``make_rng(stream)`` returns at the root of a module applied with
    ``rngs={stream: key}``: the key SDAR's reference draws its noise from,
    which flax says, not the model."""
    class Root(flax.linen.Module):
        def __call__(self):
            return self.make_rng(stream)

    return Root().apply({}, rngs={stream: key})


def sdar_step_key():
    """SDAR's step key where a state starts from ``jax.random.key(0)``
    (``one_step``): the loss cases hand the model the same, so that one
    reference serves both."""
    return jax.random.fold_in(jax.random.key(0), 0)


def _sdar_loss(model, params, biases, tokens, labels):
    """(loss, Aux) with the step's key as the noise's stream, as the step's
    ``loss_fn`` hands it over; no state beside the parameters."""
    assert biases is None
    outputs = model.apply(
        {"params": params}, tokens, train=True, hidden_only=True,
        rngs={sdar_moe.NOISE_STREAM: sdar_step_key()})
    loss, hits, extra = model.head_loss(
        outputs, model.head_kernel(params), labels, topk=(1, 5))
    return loss, Aux(extra, None, outputs, hits)


def _sdar_reference():
    """``benchmark/reference/sdar_moe.py`` with the noise's key bound to the
    step's (``module``: the file itself)."""
    module = _reference("sdar_moe")

    def keyed(name):
        return lambda *args, **kw: getattr(module, name)(
            *args, noise_key=stream_key(sdar_step_key()), **kw)

    return types.SimpleNamespace(
        module=module, _mixture=module._mixture, loss=keyed("loss"),
        logits=keyed("logits"))


def _sdar_evaluated(want, ran):
    """Evaluation is the training objective on the draws of a call without
    the stream: the reference's at ``jax.random.key(0)``."""
    return ROWS["sdar"].reference.module.loss(
        ran.params, ran.tokens, architecture=_sdar_architecture(ran.model),
        noise_key=jax.random.key(0))["ce"]


@dataclasses.dataclass(frozen=True)
class Row:
    tiny: str                 # the arch at the size the CPU runs
    full: str                 # the published arch, which the YAML names
    reference: Any            # benchmark/reference/<name>.py
    architecture: Callable    # model -> the reference's ``architecture``
    published: dict           # attribute -> value of the full arch
    states: tuple             # the hidden states' shape for 2 x 40 tokens
    # name -> (build keywords, seed) of the loss-and-gradient cases
    gradient_cases: dict
    terms: dict               # step metric -> the reference's term
    loss_rtol: float
    term_rtol: float
    gradient_tolerance: float
    specs: dict               # leaf path -> PartitionSpec
    declared_cfg: dict        # what the traits test sets ...
    declared: dict            # ... and reads back off the model built from it
    refusal: str              # serving's sentence, as a pattern
    train_argv: tuple         # the CPU-size overrides of ``train_net.py``
    shares: bool = True       # one chip's share of an expert-parallel group: a rank
    #                           holds its experts and its rows of the vocabulary
    biased: bool = True       # ... whose routers balance through a bias that rides
    #                           ``batch_stats`` (SDAR's softmax router has none)
    aux_weight: float = 1e-4  # the YAML's MODEL.MOE.AUX_WEIGHT
    program_loss: Callable = program_loss
    total: Callable | None = None      # terms -> loss, where the reference has none
    gradient_leaves: Callable | None = None  # model -> leaves of the gradient
    logits_too: bool = False  # the loss cases hold the logits to the reference
    jitted: bool = False      # ... with each side one compiled function
    spec_table: Callable = lambda model: model.param_spec_table()
    epochs: int = 2           # 2: the second run resumes from the first's checkpoint
    # ThroughLower
    step_from_yaml: bool = True
    step_cfg: dict = dataclasses.field(default_factory=dict)
    step_cases: dict = dataclasses.field(default_factory=lambda: {"whole": {}})
    step_metrics: frozenset = frozenset()
    step_metrics_absent: frozenset = frozenset()
    # the term evaluation reads (``ran``: ``one_step``'s host copies)
    evaluated: Callable = lambda want, ran: want["ce"]
    head_walks: int = 1       # the states that share ONE walk of the head
    # the step draws from its key: the CPU lowers threefry's rounds as a loop
    # (the TPU unrolls them), so loops are looked for in the jaxpr, not the text
    draws: bool = False
    # tokens' shape -> the shape of the rows a block sees
    rows: Callable = lambda shape: shape
    # the entries of a gradient whose SIGN AdamW's first step is held to
    firm: Callable = lambda g: jnp.ones(g.shape, bool)
    # Recomputes: the model of the recompute tests, the blocks a step applies
    # (model -> count) and the branches a block keeps
    small: dict = dataclasses.field(default_factory=dict)
    blocks: Callable | None = None
    branches: int = 0
    # RecomputesNothingInItsCell: (the cell's ``LM`` overrides, its batch, the
    # ``branch_out`` names in its jaxpr)
    cell: tuple = ()
    # KeepsTheFlashKernels: the cell whose plan is read
    plan: dict = dataclasses.field(default_factory=dict)
    # ComputesInBfloat16: (build keywords, batch, seq, [(term, limit, relative, teeth)])
    bfloat16: tuple = ()
    # HoldsAShare: the mixture of the share test
    mixture: dict = dataclasses.field(default_factory=dict)

    @property
    def yaml(self) -> str:
        return os.path.join(REPO, "config", f"{self.full}.yaml")

    @property
    def biases(self) -> bool:
        return self.shares and self.biased


_TRAIN = ("MODEL.NUM_CLASSES", "512", "DEVICE.COMPUTE_DTYPE", "float32")
_SHARE_METRICS = frozenset({
    "loss", "top1", "topk", "ce", "moe_aux", "moe_dropped", "moe_load_max_over_mean",
    "moe_held_row_share", "router_bias_abs_max", "nonfinite"})
_SHARE_TERMS = {"ce": "ce", "moe_aux": "load_balance", "moe_held_row_share": "held_row_share"}
_PATTERNED = dict(  # LFM2's and Trinity-Mini's stage of a published pattern
    declared_cfg={"LM.FIRST_LAYER": 1, "LM.LAYERS": 5, "LM.RECOMPUTE": False,
                  "LM.SHARE_CHIPS": 4, "LM.SHARE_RANK": 3, "MODEL.MOE.AUX_WEIGHT": 0.001},
    declared={"seq_len": 64, "first_layer": 1, "depth": 5, "share_chips": 4,
              "share_rank": 3, "aux_weight": 0.001, "recompute": False, "dense_here": 1},
    step_metrics=_SHARE_METRICS, step_metrics_absent=frozenset({"ce_mtp"}),
    terms=_SHARE_TERMS, loss_rtol=1e-6, term_rtol=2e-6, gradient_tolerance=2e-5,
    logits_too=True, blocks=lambda m: len(m.layer_kinds),
)

ROWS = {
    "olmoe": Row(
        tiny="olmoe_tiny", full="olmoe_1b_7b", reference=_reference("olmoe"),
        architecture=_olmoe_architecture, shares=False,
        published=dict(dim=2048, depth=16, num_heads=16, num_experts=64, top_k=8,
                       expert_hidden=1024, vocab_size=50304, seq_len=4096),
        states=(2, 40, 64),
        program_loss=_olmoe_loss, total=olmoe_total,
        # (experts, per token)
        gradient_cases={"top2of8": ({}, 0), "top8of16": (dict(num_experts=16, top_k=8), 0)},
        terms={"ce": "ce", "load_balance": "load_balance", "router_z": "router_z"},
        loss_rtol=1e-5, term_rtol=1e-5, gradient_tolerance=1e-4,
        gradient_leaves=lambda m: 3 + 12 * m.depth,
        spec_table=lambda model: specs.lm_spec_table(moe_axis="expert"),
        specs={"Block_0/moe/w_gate": P("expert"), "Block_0/moe/w_down": P("expert"),
               "Block_0/attn/q_proj/kernel": P(None, "model"),
               "Block_0/attn/o_proj/kernel": P("model"),
               "Block_0/attn/k_norm/scale": P(), "Block_0/moe/router": P(),
               "head": P(None, "model")},
        declared_cfg={"LM.LAYERS": 3}, declared={"seq_len": 64, "depth": 3},
        refusal="olmoe.*ROADMAP R1",
        train_argv=_TRAIN, epochs=1,
        # its own step test's recipe (with and without chunks): not the YAML's
        cell=({"LAYERS": 1}, (4, 4096), 0),  # no checkpoint, no named branch
        step_from_yaml=False,
        step_cfg={"MODEL.MOE.AUX_WEIGHT": OLMOE_AUX_W, "MODEL.MOE.Z_WEIGHT": OLMOE_Z_W,
                  "OPTIM.OPTIMIZER": "adamw", "OPTIM.BASE_LR": 1e-3},
        bfloat16=({}, 4, 128, [("ce", 2e-4, True, True)]),
    ),
    "ouro": Row(
        tiny="ouro_tiny", full="ouro_2_6b", reference=_reference("ouro"),
        architecture=_ouro_architecture, shares=False,
        published=dict(dim=2048, depth=48, passes=4, num_heads=16, mlp_hidden=5632,
                       vocab_size=49152, seq_len=4096, rms_norm_eps=1e-6, rope_theta=1e6),
        states=(2, 4, 40, 64),
        gradient_cases={"passes4": ({}, 0)},
        terms={"ce": "ce", "exit_entropy": "exit_entropy",
               "exit_step_mean": "exit_step_mean"},
        loss_rtol=1e-5, term_rtol=1e-5, gradient_tolerance=1e-4,
        gradient_leaves=lambda m: 5 + 11 * m.depth,
        specs={"Block_0/mlp/gate_proj/kernel": P(None, "model"),
               "Block_0/mlp/up_proj/kernel": P(None, "model"),
               "Block_0/mlp/down_proj/kernel": P("model"),
               "Block_2/attn/q_proj/kernel": P(None, "model"),
               **{f"Block_1/{norm}/scale": P() for norm in (
                   "attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm")},
               "final_norm/scale": P(), "exit_gate/kernel": P(), "exit_gate/bias": P(),
               "head": P(None, "model")},
        declared_cfg={"LM.LAYERS": 2, "MODEL.EXIT_ENTROPY_WEIGHT": 0.1},
        declared={"seq_len": 64, "depth": 2, "passes": 4, "exit_beta": 0.1},
        refusal="'ouro_2_6b' trains only.*cache a pass",
        train_argv=(*_TRAIN, "LM.LAYERS", "1"),  # one layer, four passes
        blocks=lambda m: m.depth * m.passes,
        step_metrics=frozenset({
            "loss", "top1", "topk", "ce", "ce_pass_0", "ce_pass_1", "ce_pass_2",
            "ce_pass_3", "exit_entropy", "exit_step_mean", "nonfinite"}),
        evaluated=lambda want, ran: want["ce_pass"][-1],  # evaluation reads the last pass
        head_walks=4,
        small=dict(depth=2), branches=2,
        plan=dict(
            module=ouro, kind="loop.plan", build=dict(num_classes=49152, depth=8),
            tokens=(1, 4096), fields={"block_applications": 32},
            inputs=32 * 4096 * 2048 * 4, branches=2 * 32 * 4096 * 2048 * 2,
            flash=2_155_872_256,
            said="every block application, from its float32 input, the outputs of "
                 "its branches that are read again (whose last matmuls run once)"),
        bfloat16=(dict(depth=2), 4, 128,
                  [("ce", 2e-4, True, True), ("exit_step_mean", 1e-3, True, True)]),
    ),
    "glm": Row(
        tiny="glm_moe_tiny", full="glm_4_7_flash", reference=_reference("glm_moe"),
        architecture=_glm_architecture,
        published=dict(dim=2048, depth=47, num_heads=20, num_experts=64, top_k=4,
                       vocab_size=154880, share_chips=1),
        states=(2, 2, 40, 64),
        gradient_cases={"rank0": (dict(share_rank=0), 0), "rank1": (dict(share_rank=1), 1)},
        terms={**_SHARE_TERMS, "ce_mtp": "ce_mtp"},
        loss_rtol=1e-6, term_rtol=2e-6, gradient_tolerance=2e-5,
        # latent attention: down-projections and latents' norms replicated, the
        # up-projections split by head, the output projection by its rows
        specs={"Block_1/attn/q_a_proj/kernel": P(), "Block_1/attn/kv_a_proj/kernel": P(),
               "Block_1/attn/q_b_proj/kernel": P(None, "model"),
               "Block_1/attn/kv_b_proj/kernel": P(None, "model"),
               "mtp_block/attn/o_proj/kernel": P("model"),
               **{f"Block_2/{norm}/scale": P() for norm in (
                   "attn/q_a_norm", "attn/kv_a_norm", "attn_norm", "moe_norm")},
               "Block_0/mlp/gate_proj/kernel": P(None, "model"),
               "Block_1/moe/shared/down_proj/kernel": P("model"),
               "Block_1/moe/router": P(), "mtp_proj/kernel": P(),
               **{f"{norm}/scale": P() for norm in (
                   "final_norm", "mtp_embed_norm", "mtp_hidden_norm", "mtp_final_norm")},
               "head": P(None, "model")},
        declared_cfg={"LM.LAYERS": 2, "LM.SHARE_CHIPS": 4, "LM.SHARE_RANK": 3,
                      "MODEL.MOE.AUX_WEIGHT": 0.001},
        declared={"seq_len": 64, "depth": 2, "share_chips": 4, "share_rank": 3,
                  "aux_weight": 0.001},
        refusal="'glm_4_7_flash' trains only.*latents",
        # 1 + 1 layers and the MTP module; the whole model: the shards' ids
        # range over the whole vocabulary
        train_argv=(*_TRAIN, "LM.LAYERS", "2", "LM.SHARE_CHIPS", "1"),
        blocks=lambda m: m.depth + m.mtp_layers,
        step_cfg={"LM.SHARE_CHIPS": 2}, step_cases={"rank1": dict(share_rank=1)},
        step_metrics=_SHARE_METRICS | {"ce_mtp"},
        head_walks=2,  # trunk and MTP module: 2 x B rows a chunk
        small={}, branches=1,
        plan=dict(
            module=glm_moe, kind="share.plan",
            build=dict(num_classes=154880, depth=5, share_chips=8),
            tokens=(1, 8192), fields={"experts_held": 8, "vocab_held": 19360},
            inputs=6 * 8192 * 2048 * 4, branches=6 * 8192 * 2048 * 2,
            flash=2_017_198_080,
            said="every block, the MTP module's too, from its float32 input, the "
                 "outputs of its branches that are read again (whose last matmuls "
                 "run once)"),
        bfloat16=({}, 2, 64, [("ce", 5e-3, False, False), ("ce_mtp", 5e-3, False, False)]),
        mixture=dict(experts=8, top_k=2, shared=1, scale=1.8, chips=(4,), keywords={},
                     reference={"routed_scaling_factor": 1.8},
                     share_atol=None, sum_atol=2e-6),
    ),
    "lfm2": Row(
        tiny="lfm2_moe_tiny", full="lfm2_24b_a2b", reference=_reference("lfm2_moe"),
        architecture=_lfm2_architecture,
        refusal="'lfm2_24b_a2b' trains only.*typed by layer",
        published=dict(dim=2048, num_heads=32, kv_heads=8, num_experts=64, top_k=4,
                       vocab_size=65536, share_chips=1, dense_here=2, conv_taps=3),
        states=(2, 40, 64),
        gradient_cases={
            f"{rank}-{name}": (dict(share_rank=rank, recompute=recompute), rank)
            for name, recompute in (("recomputed", True), ("kept", False))
            for rank in (0, 1)},
        specs={"Block_0/short_conv/in_proj/kernel": P(None, "model"),
               "Block_0/short_conv/out_proj/kernel": P("model"),
               "Block_0/short_conv/filter": P("model"),
               **{f"Block_2/attn/{name}/kernel": P(None, "model")
                  for name in ("q_proj", "k_proj", "v_proj")},
               "Block_2/attn/o_proj/kernel": P("model"),
               **{f"Block_2/{norm}/scale": P() for norm in (
                   "attn/q_norm", "attn/k_norm", "operator_norm", "ffn_norm")},
               "Block_0/mlp/gate_proj/kernel": P(None, "model"),
               "Block_2/moe/router": P(), "final_norm/scale": P(),
               "tok_embed/embedding": P(None, "model")},
        # layers 1..3 of the tiny pattern: a dense conv layer, an attention
        # mixture, a conv mixture; the whole model
        train_argv=(*_TRAIN, "LM.FIRST_LAYER", "1", "LM.LAYERS", "3", "LM.SHARE_CHIPS", "1"),
        step_cfg={"LM.SHARE_CHIPS": 2},
        step_cases={"rank1-recomputed": dict(share_rank=1, recompute=True),
                    "rank1-kept": dict(share_rank=1, recompute=False)},
        small=dict(recompute=True), branches=1,
        # the same ``share.Block`` as the rows that recompute, every activation kept
        cell=({"FIRST_LAYER": 1, "LAYERS": 5, "SHARE_CHIPS": 8, "RECOMPUTE": False},
              (2, 8192), 2 * 5),
        mixture=dict(experts=8, top_k=2, shared=0, scale=1.0, chips=(2, 4),
                     keywords={"norm_eps": 1e-6},
                     reference={"routed_scaling_factor": 1.0, "route_norm_eps": 1e-6},
                     share_atol=2e-6, sum_atol=2e-6),
        **_PATTERNED,
    ),
    "afmoe": Row(
        tiny="afmoe_tiny", full="trinity_mini", reference=_reference("afmoe"),
        architecture=_afmoe_architecture,
        refusal="'trinity_mini' trains only.*typed by layer",
        published=dict(dim=2048, num_heads=32, kv_heads=4, head_dim=128,
                       sliding_window=2048, num_experts=128, top_k=8, shared_experts=1,
                       vocab_size=200192, share_chips=1, dense_here=2,
                       routed_scale=2.826, rope_theta=1e4, norm_eps=1e-5),
        states=(2, 40, 64),
        # 0, 1 and 2 leading dense layers under the pattern sliding x 3, full
        # (100 positions: four windows long), for either of the two chips,
        # recomputed as the cell runs them and, once, with nothing recomputed
        gradient_cases={
            f"{name}-{dense}": (dict(share_rank=dense % 2, recompute=recompute,
                                     dense_layers=dense, depth=4), dense)
            for name, recompute, dense in (
                ("recomputed", True, 0), ("recomputed", True, 1),
                ("recomputed", True, 2), ("kept", False, 1))},
        jitted=True,
        specs={**{f"Block_2/attn/{name}/kernel": P(None, "model")
                  for name in ("q_proj", "k_proj", "v_proj", "gate_proj")},
               "Block_2/attn/o_proj/kernel": P("model"),
               **{f"Block_2/{norm}/scale": P() for norm in (
                   "attn/q_norm", "attn/k_norm", "input_norm", "post_attn_norm",
                   "pre_mlp_norm", "post_mlp_norm")},
               "Block_2/moe/shared/down_proj/kernel": P("model"),
               "head": P(None, "model"),
               "Block_0/mlp/gate_proj/kernel": P(None, "model"),
               "Block_2/moe/router": P(), "final_norm/scale": P(),
               "tok_embed/embedding": P(None, "model")},
        # layers 1..3 of the tiny pattern: a dense sliding layer, a sliding
        # mixture, a full-attention mixture; the whole model. One epoch:
        # resuming into a second is the trainer's, and GLM's and LFM2's rows
        # run it on the same kind of state
        train_argv=(*_TRAIN, "LM.FIRST_LAYER", "1", "LM.LAYERS", "3", "LM.SHARE_CHIPS", "1"),
        epochs=1,
        # sliding x 3, full: 2 dense layers, 2 mixtures
        step_cfg={"LM.SHARE_CHIPS": 2, "LM.LAYERS": 4},
        # where an entry is within rounding of 0 (one of v_proj's 2048 reads
        # 5e-9 here) the sign says nothing; 0: an embedding row no token drew
        firm=lambda g: (jnp.abs(g) > 1e-6) | (g == 0),
        step_cases={"rank1": dict(share_rank=1)},
        small=dict(depth=4), branches=2,
        mixture=dict(experts=16, top_k=4, shared=1, scale=2.826, chips=(2, 4), keywords={},
                     reference={"route_scale": 2.826, "route_norm_eps": 1e-20},
                     share_atol=3e-6, sum_atol=5e-6),
        **_PATTERNED,
    ),
    "sdar": Row(
        tiny="sdar_moe_tiny", full="sdar_30b_a3b", reference=_sdar_reference(),
        architecture=_sdar_architecture, biased=False, aux_weight=1e-3,
        program_loss=_sdar_loss, evaluated=_sdar_evaluated, draws=True,
        rows=lambda shape: (shape[0], 2 * shape[1]),  # the noised copy, the clean copy
        refusal="'sdar_30b_a3b' trains only.*one token a sequence a step",
        published=dict(dim=2048, num_heads=32, kv_heads=4, head_dim=128, num_experts=128,
                       top_k=8, expert_hidden=768, vocab_size=151936, share_chips=1,
                       dense_here=0, norm_eps=1e-6, rope_theta=1e6, block_length=4,
                       noise_eps=1e-3, aux_weight=1e-3),
        states=(2, 40, 64),
        # either of the two chips, recomputed as the cell runs them and with
        # nothing recomputed, and blocks of 20 tokens (no power of two)
        gradient_cases={
            "0-recomputed": (dict(share_rank=0), 0),
            "1-recomputed": (dict(share_rank=1), 1),
            "1-kept": (dict(share_rank=1, recompute=False), 1),
            "0-blocks-of-20": (dict(share_rank=0, block_length=20), 2)},
        jitted=True,
        terms={**_SHARE_TERMS, "diffusion_masked_share": "masked_share"},
        # the logits of a plain call are another key's (``tests/test_sdar_moe.py``
        # holds them to the reference under the step's)
        loss_rtol=1e-6, term_rtol=2e-6, gradient_tolerance=2e-5,
        specs={**{f"Block_2/attn/{name}/kernel": P(None, "model")
                  for name in ("q_proj", "k_proj", "v_proj")},
               "Block_2/attn/o_proj/kernel": P("model"),
               **{f"Block_2/{norm}/scale": P() for norm in (
                   "attn/q_norm", "attn/k_norm", "input_norm", "post_attention_norm")},
               "Block_2/moe/router": P(),
               "final_norm/scale": P(), "head": P(None, "model"),
               "tok_embed/embedding": P(None, "model")},
        declared_cfg={"LM.FIRST_LAYER": 1, "LM.LAYERS": 3, "LM.RECOMPUTE": False,
                      "LM.SHARE_CHIPS": 4, "LM.SHARE_RANK": 3,
                      "MODEL.MOE.AUX_WEIGHT": 0.01},
        declared={"seq_len": 64, "first_layer": 1, "depth": 3, "share_chips": 4,
                  "share_rank": 3, "aux_weight": 0.01, "recompute": False,
                  "dense_here": 0, "block_length": 4},
        # two layers; the whole model: the shards' ids range over the whole
        # vocabulary (its last row stands for [MASK], which no byte is)
        train_argv=(*_TRAIN, "LM.LAYERS", "2", "LM.SHARE_CHIPS", "1"), epochs=1,
        step_cfg={"LM.SHARE_CHIPS": 2}, step_cases={"rank1": dict(share_rank=1)},
        step_metrics=(_SHARE_METRICS - {"router_bias_abs_max"}) | {"diffusion_masked_share"},
        step_metrics_absent=frozenset({"ce_mtp", "router_bias_abs_max"}),
        # the routers' gradients are small here (renormalised weights); 0: an
        # embedding row no token drew, an expert no row chose
        firm=lambda g: (jnp.abs(g) > 1e-7) | (g == 0),
        blocks=lambda m: len(m.layer_kinds), small=dict(depth=2), branches=1,
        plan=dict(
            module=types.SimpleNamespace(
                _planned=share._planned,
                _say_plan=lambda model, batch, rows: share.say_plan(
                    model, batch, rows, (None, None))),
            kind="share.plan", build=dict(num_classes=151936, depth=6, share_chips=8),
            tokens=(1, 16384),  # rows: one sequence's noised and clean copy
            fields={"experts_held": 16, "vocab_held": 18992,
                    "layer_kinds": [sdar_moe.KIND] * 6, "dense_layers": 0},
            inputs=6 * 16384 * 2048 * 4, branches=6 * 16384 * 2048 * 2,
            flash=1_824_522_240,
            said="every block of either kind, from its float32 input, the outputs "
                 "of its branches that are read again (whose last matmuls run once)"),
        bfloat16=({}, 2, 64, [("ce", 5e-3, False, False)]),
        mixture=dict(experts=16, top_k=4, shared=0, scale=1.0, chips=(2, 4),
                     keywords={"route": functools.partial(
                         moe_ops.softmax_route, renormalise=True)},
                     reference={}, share_atol=3e-6, sum_atol=5e-6),
    ),
}


# ----------------------------------------------------------- the contracts
# test -> (its argument, row -> the argument's values): the tests that run once
# a case of their row
CASES = {
    "test_loss_terms_and_every_gradient_equal_the_reference":
        ("case", lambda row: row.gradient_cases),
    "test_the_arch_declares_what_shared_code_asks_of_it":
        ("arch", lambda row: (row.full, row.tiny)),
    "test_the_step_through_lower_reports_the_references_terms":
        ("case", lambda row: row.step_cases),
    "test_the_shares_of_a_layer_add_up_to_the_whole_layer":
        ("chips", lambda row: row.mixture["chips"]),
}


class _Rowed:
    row: Row

    def pytest_generate_tests(self, metafunc):
        if metafunc.function.__name__ in CASES:
            name, of_row = CASES[metafunc.function.__name__]
            values = list(of_row(self.row))
            metafunc.parametrize(name, values, ids=[str(v) for v in values])


class Decoder(_Rowed):
    def test_registry_and_shapes(self):
        """Both sizes are registered, the full one with the published widths;
        the tiny one's logits are float32 over the vocabulary's rows it
        holds. Shapes alone: nothing here is compiled or run."""
        row = self.row
        assert {row.full, row.tiny} <= set(models.available_models())
        full = models.build_model(row.full)
        assert {name: getattr(full, name) for name in row.published} == row.published
        model = build(row)
        state = jax.eval_shape(lambda: flax.linen.meta.unbox(
            model.init(jax.random.key(0), model.dummy_input())))
        tokens = jax.ShapeDtypeStruct((2, 40), jnp.int32)
        logits = jax.eval_shape(model.apply, state, tokens)
        assert logits.shape == (2, 40, vocabulary(model)[1]) and logits.dtype == jnp.float32
        hidden = jax.eval_shape(
            lambda v, t: model.apply(v, t, hidden_only=True), state, tokens)
        states = hidden[0] if isinstance(hidden, tuple) else hidden
        assert states.shape == row.states
        if row.shares:  # two chips share each layer of the tiny model
            assert (model.held, model.vocab_held) == ((0, 4), 256)
            assert build(row, share_rank=1).held == (4, 4)
            with pytest.raises(ValueError, match="LM.SHARE_CHIPS=3"):
                jax.eval_shape(build(row, share_chips=3).init, jax.random.key(0),
                               jax.ShapeDtypeStruct((1, 8), jnp.int32))
            with pytest.raises(ValueError, match="exceeds the context"):
                jax.eval_shape(
                    model.apply, state, jax.ShapeDtypeStruct((1, 129), jnp.int32))
        self.shapes_of_its_own(full, model, state, hidden)

    def shapes_of_its_own(self, full, model, state, hidden):
        """``state``: the tiny model's variables as shapes; ``hidden``: what
        ``hidden_only`` returns for 2 x 40 tokens, as shapes."""

    def test_loss_terms_and_every_gradient_equal_the_reference(self, case):
        """The loss and its terms and the gradient on every leaf, for 2 x 100
        tokens (the head in chunks of 48), in each of the row's cases."""
        row = self.row
        kw, seed = row.gradient_cases[case]
        model = build(row, **kw)
        params, biases, tokens, labels = seeded(model, seed=seed)
        arch = row.architecture(model)
        plain_terms = reference_value_and_grad(row, biases, tokens, labels, arch)

        def program(p):
            logits = model.apply(variables(p, biases), tokens) if row.logits_too else None
            return logits, jax.value_and_grad(
                lambda p: row.program_loss(model, p, biases, tokens, labels),
                has_aux=True)(p)

        def plain(p):
            args = (p, tokens) if biases is None else (p, biases, tokens)
            logits = row.reference.logits(
                *args, architecture=arch) if row.logits_too else None
            return logits, plain_terms(p)

        if row.jitted:  # what the CPU would otherwise compile operation by operation
            program, plain = jax.jit(program), jax.jit(plain)
        logits, ((loss, aux), grads) = program(params)
        want_logits, ((_, want), want_grads) = plain(params)
        if row.logits_too:
            np.testing.assert_allclose(logits, want_logits, atol=2e-5)
        np.testing.assert_allclose(loss, want["loss"], rtol=row.loss_rtol)
        for got, term in row.terms.items():
            np.testing.assert_allclose(
                aux.extra[got], want[term], rtol=row.term_rtol, err_msg=got)
        if row.gradient_leaves is not None:
            assert len(jax.tree.leaves(grads)) == row.gradient_leaves(model)
        assert_trees_close(grads, want_grads, row.gradient_tolerance)
        if row.shares:
            # nothing is dropped, about half the choices land on held experts
            assert float(aux.extra["moe_dropped"]) == 0.0
            assert 0.3 < float(aux.extra["moe_held_row_share"]) < 0.7
        if row.biases:
            # the biases one step leaves are the rule's on the reference's counts
            np.testing.assert_array_equal(
                mixture_biases(model, aux.after),
                row.reference.bias_after(
                    mixture_biases(model, biases), want["counts"], 0.001))
            np.testing.assert_allclose(  # of the biases the step leaves
                aux.extra["router_bias_abs_max"],
                jnp.abs(mixture_biases(model, aux.after)).max())
        self.loss_of_its_own(model, loss, aux, want)

    def loss_of_its_own(self, model, loss, aux, want):
        """``aux``: the program's ``Aux``; ``want``: the reference's terms."""

    def test_lm_spec_table_places_every_leaf(self):
        row = self.row
        model = build(row)
        table = row.spec_table(model)
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
        )["params"]
        for path, _ in jax.tree_util.tree_leaves_with_path(flax.linen.meta.unbox(shapes)):
            assert table.spec_for(specs.leaf_path(path)) is not None, specs.leaf_path(path)
        assert {path: table.spec_for(path) for path in row.specs} == row.specs
        cfg.MODEL.ARCH = row.tiny
        cfg.MESH.DATA, cfg.MESH.MODEL = 4, 2
        with pytest.raises(topology.TopologyError,
                           match="MESH.DATA=n meshes only, got model=2"):
            topology.from_cfg(cfg, n_devices=8)

    def test_the_arch_declares_what_shared_code_asks_of_it(self, arch):
        """``models.traits``: topology, specs, trainer and serve_net read the
        arch's own declaration (models/traits.py), not its name; and the
        model built from the cfg carries what the cfg sizes."""
        row = self.row
        got = models.traits(arch)
        assert (got.token_batch, got.batch_norm, got.mesh_axes) == (True, False, ("data",))
        assert specs.is_token_arch(arch)
        assert got.serve_refusal and ". " not in got.serve_refusal  # one sentence
        assert got.kwargs_from_cfg is not None
        cfg.MODEL.ARCH, cfg.LM.SEQ_LEN, cfg.MESH.DATA = arch, 64, 8
        set_cfg(row.declared_cfg)
        model = trainer.build_model_from_cfg(trainer.check_trainer_mesh())
        assert {name: getattr(model, name) for name in row.declared} == row.declared
        if row.shares:
            assert model.held == (3 * model.num_experts // 4, model.num_experts // 4)
            cfg.LM.SHARE_CHIPS = 0  # the arch's own
            own = trainer.build_model_from_cfg(trainer.check_trainer_mesh())
            assert own.share_chips == (1 if arch == row.full else 2)
        self.declared_of_its_own(arch, model)

    def declared_of_its_own(self, arch, model):
        """``model``: built from the row's ``declared_cfg``."""

    def test_serving_refuses_the_arch_in_one_sentence(self):
        import serve_net

        with pytest.raises(SystemExit, match=self.row.refusal):
            serve_net.main(["--cfg", self.row.yaml])

    def test_train_net_trains_the_yaml_at_a_tiny_size_and_validates(
            self, tmp_path, monkeypatch):
        """``train_net.py --cfg config/<full>.yaml`` with the row's CPU-size
        override, through ``trainer.train_model``: one epoch on packed token
        shards with its evaluation and its checkpoint (which holds the
        routers' biases, where there are any); where the row says two epochs,
        a second run resumes from that checkpoint into epoch 2;
        ``test_net.py`` validates what was saved."""
        import test_net
        import train_net
        from distribuuuu_tpu.data.shards import tokens as token_shards
        from distribuuuu_tpu.telemetry import spans
        from distribuuuu_tpu.utils import logger

        row, S = self.row, 16
        rng = np.random.default_rng(0)
        docs = [bytes(rng.integers(32, 120, (400,)).astype(np.uint8)) for _ in range(12)]
        for split in ("train", "val"):
            token_shards.write_token_shards(
                str(tmp_path / split), token_shards.pack_token_stream(docs, S), S,
            )
        out_dir = tmp_path / "out"
        argv = [
            "--cfg", row.yaml, "MODEL.ARCH", row.tiny, "LM.SEQ_LEN", str(S),
            *row.train_argv,
            "TRAIN.BATCH_SIZE", "1", "TEST.BATCH_SIZE", "1", "TRAIN.WORKERS", "0",
            "TRAIN.DATASET", str(tmp_path), "TEST.DATASET", str(tmp_path),
            "TRAIN.PRINT_FREQ", "2", "OUT_DIR", str(out_dir),
        ]
        # the log file of THIS run's OUT_DIR, whichever test of this worker
        # process set the logger up first (it is set up once a process)
        monkeypatch.setattr(logger, "_configured", False)
        try:
            for epochs in range(1, row.epochs + 1):
                config.reset_cfg()
                monkeypatch.setattr(
                    "sys.argv", ["train_net.py", *argv, "OPTIM.MAX_EPOCH", str(epochs)])
                train_net.main()
        finally:
            spans.close_telemetry()  # train_model leaves its sink open
        logs = "".join(open(out_dir / name).read()
                       for name in os.listdir(out_dir) if name.endswith(".log"))
        assert re.search(r"epoch 1 done: Acc@1 \d", logs), logs[-2000:]
        if row.epochs == 2:
            assert re.search(r"resumed from .*ckpt_ep_000 \(epoch 1\)", logs), logs[-2000:]
        saved = [f"ckpt_ep_{epoch:03d}" for epoch in range(row.epochs)]
        assert set(saved) <= set(os.listdir(out_dir / "checkpoints"))
        config.reset_cfg()
        monkeypatch.setattr("sys.argv", [
            "test_net.py", *argv, "MODEL.WEIGHTS", str(out_dir / "checkpoints" / saved[-1])])
        try:
            test_net.main()
        finally:
            spans.close_telemetry()


class ThroughLower(_Rowed):
    @pytest.fixture(scope="class")
    def stepped(self):
        """``one_step`` of the row, run once a case and read by both tests."""
        return functools.cache(lambda case: one_step(self.row, **self.row.step_cases[case]))

    def test_the_step_through_lower_reports_the_references_terms(self, stepped, case):
        """Through ``lowering.lower`` on the 8-device data mesh, the yaml's
        recipe, the head in chunks of 48 of a 100-token sequence (for a
        share: rank 1 of the two chips that share the layers): the step's
        metrics are the reference's terms; evaluation reads the term the row
        names, a token a count."""
        row, ran = self.row, stepped(case)
        assert set(ran.metrics) >= row.step_metrics
        assert not set(ran.metrics) & row.step_metrics_absent
        batch = (ran.biases, ran.tokens, ran.labels, row.architecture(ran.model))
        if row.shares:  # the update is held to the reference's gradient
            reference = reference_value_and_grad(row, *batch)
            (_, want), grads = (jax.jit(reference) if row.jitted else reference)(ran.params)
        else:
            want, grads = reference_loss(row, ran.params, *batch), None
        for got, term in {"loss": "loss", **row.terms}.items():
            np.testing.assert_allclose(ran.metrics[got], want[term], rtol=1e-5, err_msg=got)
        assert float(ran.evaluated["count"]) == 8 * 100
        np.testing.assert_allclose(
            ran.evaluated["loss_sum"] / ran.evaluated["count"],
            row.evaluated(want, ran), rtol=1e-5)
        if row.shares:
            self._the_step_moves_the_bias_and_takes_adamws_first_step(ran, want, grads)
        self.step_of_its_own(ran, want)

    def step_of_its_own(self, ran, want):
        """``ran``: ``one_step``'s host copies; ``want``: the reference's
        terms."""

    def _the_step_moves_the_bias_and_takes_adamws_first_step(self, ran, want, grads):
        """A share's step: the biases it leaves are the rule's on the
        reference's counts, the first AdamW update a plain one on the
        reference's gradient; the optimizer holds no bias."""
        row, model = self.row, ran.model
        assert (model.share_chips, model.share_rank, model.aux_weight) == (
            2, 1, row.aux_weight)
        assert ran.moment_leaves == len(jax.tree.leaves(ran.params))  # no leaf for a bias
        assert float(ran.metrics["moe_dropped"]) == 0.0
        if row.biases:
            fresh = mixture_biases(model, ran.biases)
            assert not float(jnp.abs(fresh).max())
            assert float(ran.metrics["router_bias_abs_max"]) == pytest.approx(0.001)
            # the biases rode the state the step returns, by the rule and no gradient
            np.testing.assert_array_equal(
                mixture_biases(model, ran.biases_after),
                row.reference.bias_after(jnp.zeros(fresh.shape), want["counts"], 0.001))
        else:  # a router without a bias leaves the state nothing to carry
            assert not jax.tree.leaves(ran.biases_after)
        # the first AdamW step (zero moments): lr * (g / (|g| + eps) + wd p), over
        # the length of the step (an element whose gradient is near eps is free)
        for (path, p0), g, p1 in zip(jax.tree_util.tree_leaves_with_path(ran.params),
                                     jax.tree.leaves(grads),
                                     jax.tree.leaves(ran.params_after)):
            step = ran.lr * (g / (jnp.abs(g) + 1e-8) + ran.wd * p0)
            firm = row.firm(g)
            assert float(firm.mean()) > 0.96, jax.tree_util.keystr(path)
            assert float(jnp.linalg.norm((p1 - (p0 - step)) * firm)) <= 2e-3 * float(
                jnp.linalg.norm(step)), jax.tree_util.keystr(path)

    def test_the_lowered_step_holds_no_while_and_one_headwalk(self, stepped):
        """Layers, passes and the head's chunks are Python loops (a ``while``
        in a device trace is one operation AND its body's); the states that
        reach the head share ONE walk of it: three matmuls as wide as the
        held vocabulary a chunk, over ``head_walks`` x B rows."""
        row = self.row
        ran = stepped(next(iter(row.step_cases)))
        if row.draws:
            assert not {"while", "scan", "cond"} & {
                eqn.primitive.name for eqn in walk(ran.jaxpr)}
        else:
            assert " while(" not in ran.text and " conditional(" not in ran.text
        held = vocabulary(ran.model)[1]
        wide = wide_matmuls(ran.jaxpr, held)
        assert len(wide) == 3 * -(-100 // CHUNK)
        assert any(tuple(e.outvars[0].aval.shape) == (8 * row.head_walks, CHUNK, held)
                   for e in wide)


class Recomputes(_Rowed):
    """What a recomputed block keeps of its branches (``models/ouro.recomputed``,
    ``branch_out``): the output of each branch that the backward reads again,
    so the second forward stops short of that branch's last matmul. One policy
    for the four decoders that recompute: Ouro's and Trinity-Mini's
    sandwich-normed blocks keep both branches' outputs (the norm after a
    branch reads it), GLM's and LFM2's pre-norm blocks the mixer's alone (the
    sum the second norm reads is made of it; nothing reads the FFN's). The
    compiled steps for the v5e are ``tests/test_tpu_lowering.py``'s."""

    @pytest.fixture(scope="class")
    def small(self):
        """(model, params, biases, tokens, labels): the row's ``small`` model
        and its seeded weights for 2 x 40 tokens, read-only host copies."""
        model = build(self.row, **self.row.small)
        assert model.recompute
        return model, *read_only(seeded(model, seq=40))

    def _loss(self, small):
        _, _, biases, tokens, labels = small
        return lambda model, p: self.row.program_loss(model, p, biases, tokens, labels)[0]

    def test_the_recomputing_step_equals_the_step_that_keeps_everything(self, small):
        """Loss and every gradient leaf with every block recomputed (its
        branches' outputs kept) against the same model with
        ``recompute=False``."""
        model, params, loss = small[0], small[1], self._loss(small)
        got, want = (jax.jit(jax.value_and_grad(lambda p, m=m: loss(m, p)))(params)
                     for m in (model, model.clone(recompute=False)))
        np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
        assert_trees_close(got[1], want[1], 1e-5)

    def test_a_recomputed_block_keeps_the_branches_the_backward_reads_and_no_other(
            self, small):
        """What ``jax.checkpoint`` holds under the name, read off the
        residuals of the loss: ``[B, S, dim]`` a kept branch, both of a
        sandwich-normed block and the mixer's alone of a pre-norm block,
        whose FFN output is named too and kept by nothing (no reader). With
        the policy keeping nothing, none."""
        from jax._src.ad_checkpoint import saved_residuals

        row = self.row
        model, params, _, tokens, _ = small
        loss = self._loss(small)

        def named(policy=None):
            with pytest.MonkeyPatch.context() as patch:
                if policy is not None:
                    patch.setattr(jax.checkpoint_policies, "save_only_these_names",
                                  lambda *names: policy)
                return [tuple(aval.shape) for aval, why in
                        saved_residuals(lambda p: loss(model, p), params)
                        if f"({ouro.branch_out.__name__})" in why]

        assert named() == [(*row.rows(tokens.shape), model.dim)] * (
            row.branches * row.blocks(model))
        assert named(jax.checkpoint_policies.nothing_saveable) == []

    def test_the_plan_counts_the_bytes_of_the_branches_kept(self, small, tmp_path):
        """``kept_branch_bytes`` of the plan record = branches x tokens x dim
        x the compute dtype's size, inside ``kept_bytes``; None with nothing
        recomputed."""
        from distribuuuu_tpu.telemetry import schema, spans

        row = self.row
        model, params, _, tokens, _ = small
        loss = self._loss(small)
        for module in (ouro, glm_moe, share):
            module._planned.clear()
        spans.setup_telemetry(str(tmp_path), 0)
        try:
            for m in (model, model.clone(recompute=False)):
                jax.eval_shape(lambda p, m=m: loss(m, p), params)
        finally:
            spans.close_telemetry()
        plans = records(tmp_path, "loop.plan") + records(tmp_path, "share.plan")
        assert len(plans) == 2
        for plan in plans:
            schema.validate_record(plan)
        kept, nothing = plans
        rows = int(np.prod(row.rows(tokens.shape)))
        size = rows * model.dim * jnp.dtype(model.dtype).itemsize
        assert kept["kept_branch_bytes"] == row.branches * row.blocks(model) * size
        assert kept["kept_bytes"] == (  # the CPU's scan path names nothing of flash
            row.blocks(model) * rows * model.dim * 4 + kept["kept_branch_bytes"])
        assert "branches that are read again" in kept["recomputed"]
        assert (nothing["kept_branch_bytes"], nothing["kept_bytes"],
                nothing["recomputed"]) == (None, None, "nothing")


class RecomputesNothingInItsCell(_Rowed):
    def test_the_cell_that_recomputes_nothing_lowers_to_the_step_without_the_names(
            self, monkeypatch):
        """The row's benchmark cell at its own size (``row.cell``), under no
        checkpoint: ``checkpoint_name`` leaves a ``name`` equation in the
        jaxpr and NOTHING in the lowered program, which is, character for
        character, the one lowered with the naming taken out."""
        from distribuuuu_tpu.utils.optim import construct_optimizer

        row = self.row
        overrides, batch, named = row.cell

        def lowered_text():
            config.reset_cfg()
            config.merge_from_file(row.yaml)
            set_cfg({f"LM.{key}": value for key, value in overrides.items()})
            cfg.MESH.DATA = 1
            try:
                layout = topology.from_cfg(cfg, n_devices=1)
                low = lowering.lower(
                    trainer.build_model_from_cfg(layout), construct_optimizer(), 5,
                    mesh=mesh_lib.build_mesh(data=1, devices=jax.devices()[:1]),
                    topology=layout, im_size=cfg.TRAIN.IM_SIZE)
                state, avals = low.abstract_args(batch[0])
            finally:
                config.reset_cfg()
            avals = {k: jax.ShapeDtypeStruct(batch, v.dtype, sharding=v.sharding)
                     for k, v in avals.items()}
            traced = low.train_step.trace(state, avals)
            return str(traced.jaxpr).count("name=branch_out"), traced.lower().as_text()

        names, text = lowered_text()
        assert names == named
        for module in (ouro, glm_moe, share):
            monkeypatch.setattr(module, "branch_out", lambda x: x)
        bare_names, bare = lowered_text()
        assert bare_names == 0 and "branch_out" not in text
        assert text == bare


class KeepsTheFlashKernels(_Rowed):
    def test_what_the_recomputed_blocks_keep_of_the_flash_kernel_changes_no_bit(
            self, monkeypatch):
        """With the kernels run (the interpreter, forced, where ``auto`` runs
        them compiled on the chip) a recomputed block keeps what the backward
        kernel reads, the forward kernel's output and log-sum-exp and its q,
        k and v, and each branch's output that is read again: a block runs
        the forward kernel and the projections ``run_once`` names once, where
        a plain ``nn.remat`` (the policy keeping nothing) runs all of it
        twice, and the loss and every gradient leaf are that step's bit for
        bit: what is kept is what was recomputed. Against the step that
        recomputes nothing the loss is the same bits and the gradients are as
        near as they were before anything was kept (jax sums a value's several
        cotangents in another order under a checkpoint)."""
        from distribuuuu_tpu.ops import flash_attention as fa

        row = self.row
        monkeypatch.setattr(
            fa, "flash_attention", functools.partial(fa.flash_attention, interpret=True))
        model = build(row, **row.small, attn_impl="flash")
        params, biases, tokens, labels = seeded(model, batch=1, seq=40)
        blocks = row.blocks(model)
        once = self.run_once(model, params)

        def run(variant, twice: bool):
            def loss(p):
                return row.program_loss(variant, p, biases, tokens, labels)[0]

            traced = jax.jit(jax.value_and_grad(loss)).trace(params)
            text = str(traced.jaxpr)
            assert text.count("name=dtpu_flash_fwd") == blocks * (1 + twice)
            assert text.count("name=dtpu_flash_bwd") == blocks
            for kernels, a_block, beside in once:
                assert forward_matmuls(traced.jaxpr.jaxpr, kernels) == (
                    a_block * blocks * (1 + twice) + beside), kernels
            return traced.lower().compile()(params)

        kept = run(model, False)
        nothing_recomputed = run(model.clone(recompute=False), False)
        monkeypatch.setattr(
            jax.checkpoint_policies, "save_only_these_names",
            lambda *names: jax.checkpoint_policies.nothing_saveable)
        plain = run(model, True)
        assert float(kept[0]) == float(plain[0]) == float(nothing_recomputed[0])
        flat = jax.tree_util.tree_leaves_with_path(kept[1])
        for (path, got), want in zip(flat, jax.tree.leaves(plain[1]), strict=True):
            assert float(jnp.abs(want).max()) > 0, jax.tree_util.keystr(path)
            np.testing.assert_array_equal(got, want, err_msg=jax.tree_util.keystr(path))
        assert_trees_close(kept[1], nothing_recomputed[1], 1e-5)

    def run_once(self, model, params) -> list:
        """``[(kernel shapes, forward matmuls a block, matmuls of those shapes
        outside the blocks)]``: the projections a recomputed block runs once."""
        raise NotImplementedError

    @pytest.mark.parametrize("engaged", [True, False], ids=["kernel", "scan"])
    def test_the_plan_says_what_the_cells_blocks_keep(self, tmp_path, monkeypatch, engaged):
        """The plan record at the cell's shape (``row.plan``): the blocks'
        float32 inputs, the branches' outputs that are read again (bfloat16)
        and, where the flash kernel runs, a block's output, log-sum-exp, q, k
        and v; where the scan runs in its place the kernel names nothing and
        nothing of it is kept."""
        from distribuuuu_tpu.ops import pallas as tier
        from distribuuuu_tpu.telemetry import schema, spans

        row, cell = self.row, self.row.plan
        if engaged:  # what the tier answers on one chip
            monkeypatch.setattr(tier, "interpret_mode", lambda: False)
            monkeypatch.setattr(tier, "compiled_across_devices", lambda: False)
        model = models.build_model(row.full, **cell["build"])
        cell["module"]._planned.clear()
        spans.setup_telemetry(str(tmp_path), rank=0)
        try:
            for _ in range(2):  # once a shape
                cell["module"]._say_plan(model, *cell["tokens"])
        finally:
            spans.close_telemetry()
            cell["module"]._planned.clear()
        plans = records(tmp_path, cell["kind"])
        assert len(plans) == 1
        plan = plans[0]
        schema.validate_record(plan)
        assert {name: plan[name] for name in cell["fields"]} == cell["fields"]
        assert plan["kept_flash_bytes"] == (cell["flash"] if engaged else 0)
        assert plan["kept_branch_bytes"] == cell["branches"]
        assert plan["kept_bytes"] == (
            cell["inputs"] + cell["branches"] + plan["kept_flash_bytes"])
        assert plan["recomputed"] == cell["said"] + (
            " and the flash kernel's output, log-sum-exp, q, k and v" if engaged else "")


class ComputesInBfloat16(_Rowed):
    def test_bfloat16_program_stays_near_the_reference_because_its_float32_parts_do(
            self, monkeypatch):
        """bfloat16 matmul inputs; residual stream, norms, router, softmaxes
        and loss in float32: each term the row names lies within its limit of
        the float32 reference's, relative where the row says so; where the
        limit has teeth, the reference run in bfloat16 THROUGHOUT lies
        outside it and the float32 program 100x inside."""
        row = self.row
        kw, batch, seq, limits = row.bfloat16
        model32 = build(row, **kw)
        model16 = model32.clone(dtype=jnp.bfloat16)
        params, biases, tokens, labels = seeded(model32, batch=batch, seq=seq)
        arch = row.architecture(model32)
        want = reference_loss(row, params, biases, tokens, labels, arch)
        _, got = row.program_loss(model16, params, biases, tokens, labels)

        def off(terms, key, relative):
            return abs(float(terms[key]) - float(want[key])) / (
                float(want[key]) if relative else 1.0)

        for term, limit, relative, teeth in limits:
            assert off(got.extra, term, relative) < limit, term
        if any(teeth for *_, teeth in limits):
            low = reference_loss(
                row, params, biases, tokens, labels, arch, precision=jnp.bfloat16)
            for term, limit, relative, teeth in limits:
                assert not teeth or limit < off(low, term, relative), term
            _, exact = row.program_loss(model32, params, biases, tokens, labels)
            assert off(exact.extra, "ce", True) < 2e-6
        self.bfloat16_of_its_own(
            model16, params, tokens, labels, got, want, arch, monkeypatch)

    def bfloat16_of_its_own(self, model16, params, tokens, labels, got, want, arch,
                            monkeypatch):
        """``got``: the bfloat16 program's ``Aux``; ``want``: the float32
        reference's terms."""


class HoldsAShare(_Rowed):
    def test_the_shares_of_a_layer_add_up_to_the_whole_layer(self, chips):
        """The guide's share test: with the experts split over ``chips``
        ranks, the ranks' partial mixture outputs, the shared expert (which
        every chip computes alike) counted ONCE, add up to what the UNCUT
        reference gives for the whole layer; and each is the reference's
        share, where the reference cuts one."""
        row, mix = self.row, self.row.mixture
        E, k, d, f = mix["experts"], mix["top_k"], 64, 32

        def mixture(held):
            return glm_moe.Mixture(d, f, E, k, mix["shared"], mix["scale"], 0.001, held,
                                   jnp.float32, **mix["keywords"])

        x = jax.random.normal(jax.random.key(0), (2, 24, d))
        p = flax.linen.meta.unbox(mixture((0, E)).init(jax.random.key(1), x))["params"]
        bias = 0.05 * jax.random.normal(jax.random.key(2), (E,))
        assert set(p) == {"router", "w_gate", "w_up", "w_down"} | (
            {"shared"} if mix["shared"] else set())
        arch = {"num_experts_per_tok": k, "share_rank": 0, "experts_held": E,
                **mix["reference"]}
        with jax.default_matmul_precision("highest"):
            want = row.reference._mixture(x, p, bias, arch)[0]
            shared = row.reference._mlp(x, p["shared"]) if mix["shared"] else 0.0
        parts, count = [], E // chips
        for rank in range(chips):
            held = slice(rank * count, (rank + 1) * count)
            mine = {**p, **{n: p[n][held] for n in ("w_gate", "w_up", "w_down")}}
            out, stats = mixture((rank * count, count)).apply(
                {"params": mine, "batch_stats": {"router_bias": bias}}, x)
            parts.append(out)
            assert 0 < float(stats["held_row_share"]) < 1
            if mix["share_atol"] is not None:
                with jax.default_matmul_precision("highest"):  # the reference's share
                    np.testing.assert_allclose(out, row.reference._mixture(
                        x, mine, bias, arch, held=(rank * count, count))[0],
                        atol=mix["share_atol"])
        np.testing.assert_allclose(
            sum(parts) - (chips - 1) * shared, want, atol=mix["sum_atol"])
        assert float(jnp.abs(parts[0] - want).max()) > 1e-3  # no share is the layer
        if mix["shared"]:  # counted every time it is not the layer
            assert float(jnp.abs(sum(parts) - want).max()) > 1e-3
