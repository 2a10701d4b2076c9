"""The spec layer: per-leaf PartitionSpecs, declared and transformed.

Every parallelism form this framework ships reduces to a per-leaf
``PartitionSpec`` over the one device mesh (axes
``data/model/seq/pipe/expert`` — parallel/mesh.MESH_AXES):

  * TP / PP / EP placement is DECLARED at the parameter: flax
    ``nn.with_partitioning`` metadata names the mesh axes per dim
    (models/*.py, models/vit.PipelinedViT ``init_stages``). ``base_specs``
    reads those annotations back as the base spec tree.
  * ZeRO stage 1/3 is a spec TRANSFORM over the base: ``data`` added on
    the best divisible free dim per leaf (parallel/zero.add_data_axis) —
    optimizer state + grads at stage 1, params too at stage 3.
  * batch / activation placement comes from a path-pattern rules table
    (``BATCH_TABLE``): leading dim over ``data``, the layout every
    topology shares.

``state_layout`` is the single resolver the lowering and the trainer
place state with; the spec algebra below (validate / collapse /
canonicalize) is what the stanza gate (tests/test_mesh_stanzas.py)
compares declared layouts against compiled shardings with — a spec that
names a size-1 axis collapses to replication, so dp-only meshes and
dp×tp meshes flow through identical declarations.

The collective SCHEDULE is derived here too (ISSUE 15): ``gather_schedule``
decides per leaf — from the spec algebra alone — which ZeRO-3 all-gathers
the lowering hoists to one step-entry gather (gather-once, ~1 gather/leaf
vs the ~9.3/leaf per-use storm the analyzer priced), ``compute_layout`` is
the gathered target, and ``collective_expectations`` is the referee table
the static analyzer's collective lint scores compiled programs against.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class UnknownLeafError(KeyError):
    """A strict spec table was asked for a leaf no rule covers."""


class SpecConflictError(ValueError):
    """A per-leaf spec names the same mesh axis on more than one dim (or
    more axes than the leaf has dims)."""


# ----------------------------------------------------------- spec algebra


def _entry_names(entry) -> tuple[str, ...]:
    """Axis names of one spec entry: None → (), 'x' → ('x',), tuples pass."""
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def spec_axes(spec: P | None) -> tuple[str, ...]:
    """Every mesh axis named anywhere in ``spec`` (order of appearance)."""
    out: list[str] = []
    for entry in tuple(spec) if spec is not None else ():
        for name in _entry_names(entry):
            if name not in out:
                out.append(name)
    return tuple(out)


def validate_leaf_spec(
    path: str, spec: P | None, shape: tuple[int, ...],
    axis_sizes: dict[str, int],
) -> None:
    """Refuse malformed per-leaf specs BEFORE they reach GSPMD.

    Checks: (a) no mesh axis appears on more than one dim (GSPMD's
    error for that is a cryptic HLO dump); (b) the spec does not name
    more dims than the leaf has; (c) every named axis exists on the
    mesh. Raises :class:`SpecConflictError` with the leaf path.

    Deliberately NOT checked: per-dim divisibility — GSPMD pads a dim
    that does not divide evenly (e.g. a 10-class head kernel on a
    4-way model axis), which is legal and was always accepted; the ZeRO
    transform separately adds ``data`` only where it divides
    (parallel/zero.add_data_axis).
    """
    entries = tuple(spec) if spec is not None else ()
    if len(entries) > len(shape):
        raise SpecConflictError(
            f"leaf {path}: spec {spec} names {len(entries)} dims but the "
            f"leaf has rank {len(shape)}"
        )
    seen: dict[str, int] = {}
    for dim, entry in enumerate(entries):
        for name in _entry_names(entry):
            if name not in axis_sizes:
                raise SpecConflictError(
                    f"leaf {path}: spec {spec} names mesh axis {name!r} "
                    f"which does not exist on the mesh "
                    f"(axes: {sorted(axis_sizes)})"
                )
            if name in seen:
                raise SpecConflictError(
                    f"leaf {path}: spec {spec} names mesh axis {name!r} on "
                    f"both dim {seen[name]} and dim {dim} — an axis may "
                    "shard at most one dim of a leaf"
                )
            seen[name] = dim


def collapse_unit_axes(spec: P | None, axis_sizes: dict[str, int]) -> P:
    """Drop axes of size 1 from ``spec`` — a size-1 axis shards nothing,
    so the canonical form of its spec is replication on that dim. This is
    what lets ONE declaration serve every mesh: the TP annotation
    ``P(None, 'model')`` IS replication on a dp-only mesh."""
    entries = []
    for entry in tuple(spec) if spec is not None else ():
        names = tuple(
            n for n in _entry_names(entry) if axis_sizes.get(n, 1) > 1
        )
        if not names:
            entries.append(None)
        elif len(names) == 1:
            entries.append(names[0])
        else:
            entries.append(names)
    return P(*entries)


def canonicalize(spec: P | None, axis_sizes: dict[str, int]) -> P:
    """Canonical spec: unit axes collapsed, trailing ``None`` stripped —
    the equality the stanza gate compares declared vs compiled specs
    under (``P('data')`` ≡ ``P('data', None)`` ≡ ``P(('data',), None)``)."""
    entries = list(tuple(collapse_unit_axes(spec, axis_sizes)))
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


# ------------------------------------------------------------ rules table


@dataclass(frozen=True)
class SpecRule:
    """One path-pattern rule: leaves whose path matches ``pattern``
    (``re.search``) get ``spec``."""

    pattern: str
    spec: P


class SpecTable:
    """Ordered path-pattern → PartitionSpec rules covering a tree.

    ``strict=True`` refuses unknown leaves (:class:`UnknownLeafError`)
    instead of defaulting — the mode the stanza gate runs in, so a new
    batch key or renamed param cannot silently fall back to replication.
    """

    def __init__(self, rules=(), default: P | None = P(), strict: bool = False):
        self.rules = tuple(rules)
        self.default = default
        self.strict = strict

    def spec_for(self, path: str, shape: tuple[int, ...] | None = None) -> P:
        for rule in self.rules:
            if re.search(rule.pattern, path):
                return rule.spec
        if self.strict:
            raise UnknownLeafError(
                f"no spec rule covers leaf {path!r} (strict table; rules: "
                f"{[r.pattern for r in self.rules]})"
            )
        return self.default

    def tree_specs(self, tree: Any) -> Any:
        """Spec tree for ``tree``: one ``spec_for`` per leaf path."""
        flat = jax.tree_util.tree_flatten_with_path(tree)
        return jax.tree.unflatten(
            flat[1],
            [
                self.spec_for(jax.tree_util.keystr(path), getattr(leaf, "shape", None))
                for path, leaf in flat[0]
            ],
        )


# The batch layout every topology shares: the leading (batch) dim of every
# loader key is split over ``data``; everything else about a batch leaf is
# replicated. Declared here (not hard-coded at the device_put site) so the
# lowering, the sweep, and the stanza gate all read the same table.
BATCH_TABLE = SpecTable(
    rules=(
        SpecRule(r"(^|[/'\[\.])image", P("data")),
        SpecRule(r"(^|[/'\[\.])label", P("data")),
        SpecRule(r"(^|[/'\[\.])mask", P("data")),
    ),
    default=None,  # unknown batch keys are refused in strict mode
    strict=True,
)

# Token batches (the LM's ``[B, S]`` input/target leaves) additionally
# shard the TOKEN dim over ``seq`` — the declaration that makes a dp×sp
# stanza's batch arrive pre-split for the ring-attention shard_map instead
# of resting replicated over the seq axis (which this jax line would do
# silently). The per-sequence ``mask`` has no token dim and stays on
# ``data`` alone. On a seq=1 mesh the extra axis collapses to replication
# (collapse_unit_axes), so ONE declaration serves every LM topology.
TOKEN_BATCH_TABLE = SpecTable(
    rules=(
        SpecRule(r"(^|[/'\[\.])image", P("data", "seq")),
        SpecRule(r"(^|[/'\[\.])label", P("data", "seq")),
        SpecRule(r"(^|[/'\[\.])mask", P("data")),
    ),
    default=None,  # unknown batch keys are refused in strict mode
    strict=True,
)


def is_token_arch(arch: str) -> bool:
    """Archs that declare ``[B, S]`` token-id batches (models/traits.py):
    no image transforms, TOKEN_BATCH_TABLE."""
    from distribuuuu_tpu import models

    return models.traits(arch).token_batch


def batch_table_for(model=None, arch: str | None = None) -> SpecTable:
    """The batch spec table for a model (or a config arch name): token
    models declare their own via a ``batch_spec_table`` hook (models/gpt.py
    → :data:`TOKEN_BATCH_TABLE`); every other arch rides
    :data:`BATCH_TABLE`. The single selector the lowering, the trainer and
    the host-placement layer (parallel/sharding.py) share."""
    if model is not None:
        fn = getattr(model, "batch_spec_table", None)
        if fn is not None:
            return fn()
        return BATCH_TABLE
    if arch is not None and is_token_arch(arch):
        return TOKEN_BATCH_TABLE
    return BATCH_TABLE


# Activations between layers: batch dim over ``data`` (GSPMD propagates it
# through the whole program from the batch placement; this constant is the
# declaration tools and docs reference).
ACTIVATION_SPEC = P("data")


def leaf_path(path) -> str:
    """A tree_flatten_with_path key path as the slash form the spec-table
    rules are written against (``tok_embed/embedding`` — readable in error
    messages, stable across jax keystr cosmetics)."""
    parts = []
    for k in path:
        name = getattr(k, "key", None)
        if name is None:
            name = getattr(k, "name", None)
        if name is None:
            name = getattr(k, "idx", None)
        parts.append(str(name) if name is not None else str(k))
    return "/".join(parts)


def lm_spec_table(moe_axis: str = "model") -> SpecTable:
    """The decoder-only LM's per-leaf placement rules (ISSUE 12): one
    path-pattern declaration per LM parameter family, applied by
    :func:`state_layout` on top of the flax annotations — which is ALL the
    new placement machinery an LM needs (zero new lowering code).

    Three leaf families are LM-specific and carry no flax annotation:

      * ``tok_embed/embedding`` ``[V, D]`` — feature-sharded over
        ``model`` (the same column family every Dense kernel uses, so the
        embedded activation arrives in the layout the first block's qkv
        matmul wants);
      * ``pos_embed`` ``[1, S, D]`` — replicated (tiny, read every step);
      * ``head/kernel`` ``[D, V]`` — column-parallel over ``model``:
        vocab-parallel logits, the transpose-consistent layout to the
        embedding (``head`` itself for the olmoe_* archs, whose head has
        no bias and no wrapper module).

    The attention/MLP kernel rules RESTATE what the shared modules already
    annotate (``tp.column_init``) — ``state_layout`` cross-checks rule
    against annotation and refuses on drift, so a renamed module or a
    silently-dropped annotation fails at layout derivation, not as a wrong
    compiled sharding. Expert tensors keep their ``MoeMlp`` annotations on
    ``moe_axis`` (restated here so the table documents the full LM family).
    """
    return SpecTable(
        rules=(
            SpecRule(r"tok_embed/embedding$", P(None, "model")),
            SpecRule(r"pos_embed$", P()),
            # head is a models/layers.Dense (wraps nn.Dense as Dense_0)
            SpecRule(r"head/Dense_0/kernel$", P(None, "model")),
            SpecRule(r"head/Dense_0/bias$", P("model")),
            # restatements of the flax annotations (cross-checked):
            SpecRule(r"Attention_0/Dense_\d+/Dense_0/kernel$",
                     P(None, "model")),
            SpecRule(r"Mlp_0/Dense_\d+/Dense_0/kernel$", P(None, "model")),
            SpecRule(r"MoeMlp_0/(w_in|w_out)$", P(moe_axis)),
            SpecRule(r"MoeMlp_0/(b_in|b_out)$", P(moe_axis)),
            # the olmoe_* family (models/olmoe.py): bias-free projections,
            # column-parallel in and row-parallel out; the three gated
            # expert tensors by the rule w_in/w_out have; the untied head
            # vocab-parallel like gpt's; norm scales and router replicated
            SpecRule(r"attn/[qkv]_proj/kernel$", P(None, "model")),
            SpecRule(r"attn/o_proj/kernel$", P("model")),
            SpecRule(r"moe/(w_gate|w_up|w_down)$", P(moe_axis)),
            SpecRule(r"moe/router$", P()),
            SpecRule(r"(_norm|/[qk]_norm)/scale$", P()),
            SpecRule(r"^head$", P(None, "model")),
            # the ouro_* family (models/ouro.py): attention, norm scales
            # and head by the rules above; the dense gated MLP column-
            # parallel in and row-parallel out; the exit gate replicated
            SpecRule(r"mlp/(gate|up)_proj/kernel$", P(None, "model")),
            SpecRule(r"mlp/down_proj/kernel$", P("model")),
            SpecRule(r"exit_gate/(kernel|bias)$", P()),
            # the glm_* family (models/glm_moe.py): norm scales, output
            # projection, dense MLP, router, held experts and head by the
            # rules above; latent attention's down-projections replicated
            # (a latent is shared by all heads) and its up-projections split
            # by head; the shared expert as a dense MLP; the MTP module's
            # projection replicated
            SpecRule(r"attn/(q_a|kv_a)_proj/kernel$", P()),
            SpecRule(r"attn/(q_b|kv_b)_proj/kernel$", P(None, "model")),
            SpecRule(r"moe/shared/(gate|up)_proj/kernel$", P(None, "model")),
            SpecRule(r"moe/shared/down_proj/kernel$", P("model")),
            SpecRule(r"mtp_proj/kernel$", P()),
            # the lfm2_* family (models/lfm2_moe.py): grouped-query
            # attention, norm scales, dense MLP, router, held experts and the
            # tied embedding by the rules above; the short convolution's
            # projections as a dense pair (in by columns: the gates and the
            # filter are per channel; out by rows), its filter by channel
            SpecRule(r"short_conv/in_proj/kernel$", P(None, "model")),
            SpecRule(r"short_conv/out_proj/kernel$", P("model")),
            SpecRule(r"short_conv/filter$", P("model")),
            # the afmoe family (models/afmoe.py): everything by the rules
            # above (its four block norms end in ``_norm``); the attention
            # output's gate by columns, as the query projection it multiplies
            SpecRule(r"attn/gate_proj/kernel$", P(None, "model")),
        ),
        default=None,  # unmatched leaves keep their annotation/replication
        strict=False,
    )


def lm_cache_spec() -> P:
    """Placement of the paged KV cache ``[L, B, H, C, Dh]`` under TP
    decode (ISSUE 17): heads sharded over ``model`` — the axis the qkv
    column-parallel kernels already split heads on, so each model shard
    writes and reads ONLY its own heads' pages and the cache never moves
    between shards. Every other dim (layers, slots, positions, head dim)
    is replicated."""
    return P(None, None, "model", None, None)


def lm_decode_shardings(mesh: Mesh, params) -> Any:
    """NamedSharding tree for a PLAIN (unboxed) GPTDecoder param tree:
    the :func:`lm_spec_table` path rules applied leaf-by-leaf, unmatched
    leaves replicated. The decoder mirrors the training GPT module names
    exactly (lm/generate.GPTDecoder), so the SAME declaration that places
    training state places decode state — zero decode-specific rules.
    Every derived spec is validated before it can reach GSPMD."""
    table = lm_spec_table()
    axis_sizes = {k: int(v) for k, v in dict(mesh.shape).items()}
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        pstr = leaf_path(path)
        spec = table.spec_for(pstr)
        if spec is None:
            spec = P()
        validate_leaf_spec(
            pstr, spec, tuple(jax.numpy.shape(leaf)), axis_sizes
        )
        out.append(NamedSharding(mesh, spec))
    return jax.tree.unflatten(treedef, out)


def apply_spec_table(base, table: SpecTable, mesh: Mesh):
    """Overlay a path-pattern table onto a NamedSharding tree (the
    annotation-derived base): a leaf a rule matches gets the rule's spec;
    a leaf whose ANNOTATION disagrees with a matching rule raises
    :class:`SpecConflictError` — the table is a declaration, and a
    declaration that contradicts the module annotations is drift, not an
    override. Unmatched leaves pass through untouched."""

    def _stripped(spec) -> tuple:
        entries = list(tuple(spec) if spec is not None else ())
        while entries and entries[-1] is None:
            entries.pop()
        return tuple(entries)

    flat, treedef = jax.tree_util.tree_flatten_with_path(base)
    out = []
    for path, sh in flat:
        pstr = leaf_path(path)
        spec = table.spec_for(pstr)
        if spec is None:
            out.append(sh)
            continue
        annotated = _stripped(sh.spec)
        if annotated and annotated != _stripped(spec):
            raise SpecConflictError(
                f"leaf {pstr}: spec-table rule declares {spec} but the "
                f"module annotation says {sh.spec} — fix the rule or the "
                "annotation; they are one declaration"
            )
        out.append(NamedSharding(mesh, spec))
    return jax.tree.unflatten(treedef, out)


def batch_spec(key: str, *, leading_dims: int = 0) -> P:
    """Spec for batch leaf ``key`` with ``leading_dims`` extra leading
    dims (accum stacking) before the batch dim."""
    base = BATCH_TABLE.spec_for(key)
    return P(*([None] * leading_dims + list(tuple(base))))


# --------------------------------------------------------- state layouts


def base_specs(abstract_variables) -> Any:
    """The DECLARED base spec tree of a (possibly flax-boxed) variables
    tree: the ``nn.with_partitioning`` annotation for boxed leaves,
    ``P()`` (replicated) for plain ones. This is the per-leaf declaration
    every transform below starts from."""
    import flax.linen as nn

    return nn.get_partition_spec(abstract_variables)


def model_dummy_input(model, im_size: int):
    """The init-time dummy for a model: the model's own declaration
    (``model.dummy_input()`` — token models can't eat images, models/gpt.py)
    when present, the standard image dummy otherwise. The ONE place init
    shape assumptions live (abstract_state + trainer.create_train_state)."""
    import jax.numpy as jnp

    fn = getattr(model, "dummy_input", None)
    if fn is not None:
        return fn()
    return jnp.ones((2, im_size, im_size, 3), jnp.float32)


def abstract_state(model, im_size: int):
    """``jax.eval_shape`` of ``model.init`` on the standard dummy input —
    the shape/annotation source for every layout derivation (never runs
    compute)."""
    import functools

    dummy = model_dummy_input(model, im_size)
    return jax.eval_shape(
        functools.partial(model.init, train=False), jax.random.key(0), dummy
    )


def state_layout(model, mesh: Mesh, im_size: int, zero_stage: int) -> dict:
    """Resolved NamedSharding trees for the full train state:
    ``{"params", "opt", "grads"}`` — param-shaped trees.

    The single source the lowering AND the trainer place state with:
      stage 0  all three are the declared base layout (params replicated
               over ``data``, TP/PP annotations where present — the DDP
               topology);
      stage 1  ``opt``/``grads`` move to the ZeRO layout (``data`` added
               per leaf where divisible — parallel/zero.add_data_axis);
      stage 3  ``params`` too (FSDP): rest-sharded, gathered at use. On a
               pipelined model the gather happens at the stage shard_map
               boundary (GSPMD derives it from the in_specs), which is
               what makes ZeRO-3 × PP a layout, not a refusal.

    Every derived leaf spec is validated (:func:`validate_leaf_spec`)
    before it can reach GSPMD.
    """
    import flax

    from distribuuuu_tpu.parallel import tp, zero

    abstract = abstract_state(model, im_size)
    base = tp.param_shardings(mesh, abstract)["params"]
    # models carrying a path-pattern spec table (the LM — models/gpt.py
    # ``param_spec_table``) overlay it here: unannotated LM leaves
    # (embedding/positions/head) get their declared placement, annotated
    # leaves are cross-checked against the matching rule. The transforms
    # and validation below are untouched — this is declaration input, not
    # a new lowering path.
    table_fn = getattr(model, "param_spec_table", None)
    if table_fn is not None:
        base = apply_spec_table(base, table_fn(), mesh)
    axis_sizes = {k: int(v) for k, v in dict(mesh.shape).items()}
    stage = int(zero_stage)
    if not stage:
        layout = {"params": base, "opt": base, "grads": base}
    else:
        abstract_params = flax.linen.meta.unbox(abstract)["params"]
        zsh = zero.zero_shardings(mesh, base, abstract_params)
        layout = {
            "params": zsh if stage == 3 else base,
            "opt": zsh,
            "grads": zsh,
        }
    # refuse malformed derivations before GSPMD sees them
    shapes = flax.linen.meta.unbox(abstract)["params"]
    for key in ("params", "opt", "grads"):
        flat = jax.tree_util.tree_flatten_with_path(layout[key])[0]
        shape_flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
        for (path, sh), (_, leaf) in zip(flat, shape_flat):
            validate_leaf_spec(
                jax.tree_util.keystr(path), sh.spec, tuple(leaf.shape),
                axis_sizes,
            )
    return layout


# ------------------------------------------------- gather scheduling


def compute_layout(layout: dict) -> Any:
    """The params layout DURING compute: the rest layout with the ZeRO
    ``data`` axis stripped per leaf (zero.strip_data_axis — the exact
    inverse of the transform that added it). At stage 0/1 this equals the
    rest layout (identity); at stage 3 it is the gathered form the
    gather-once schedule constrains FSDP leaves to at step entry."""
    from distribuuuu_tpu.parallel import zero

    return jax.tree.map(
        lambda sh: NamedSharding(sh.mesh, zero.strip_data_axis(sh.spec)),
        layout["params"],
    )


def gather_groups(layout: dict) -> Any:
    """Per-leaf block-group index for gather scheduling, derived from the
    SAME path naming the spec-table rules match against: the first
    integer appearing in the leaf path (flax's numbered modules —
    ``ResNetStage_2/...``, ``blocks_5/...``, ``Dense_1/...``) names the
    leaf's group; un-numbered leaves (stem, embeddings, final norm) are
    group 0. Purely a scheduling coordinate — no effect on values — used
    by :func:`gather_schedule` to bound how many groups the gather-once
    transform hoists to step entry (``ZERO.GATHER_AHEAD``)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(layout["params"])
    out = []
    for path, _ in flat:
        m = re.search(r"(\d+)", leaf_path(path))
        out.append(int(m.group(1)) if m else 0)
    return jax.tree.unflatten(treedef, out)


def gather_schedule(layout: dict, ahead: int = -1) -> Any:
    """Per-leaf bool tree: True = this leaf's ZeRO all-gather is hoisted
    to step entry (gathered ONCE per step), False = the leaf keeps its
    rest layout into the step and GSPMD gathers at use sites.

    Derived from the spec algebra alone — a leaf qualifies iff the ZeRO
    transform added ``data`` to its rest spec (stage 3 FSDP leaves; at
    stage 0/1 params rest in the base layout and the schedule is empty).
    ``ahead`` is ``ZERO.GATHER_AHEAD``: -1 hoists every qualifying leaf
    (the default — ~1 gather/leaf/step, full gathered footprint), 0
    hoists none (the legacy per-use schedule), N >= 1 hoists only the
    leaves of the first N block-groups in :func:`gather_groups` order
    (bounds the gathered-live footprint)."""
    ahead = int(ahead)
    if ahead < -1:
        raise ValueError(
            f"ZERO.GATHER_AHEAD={ahead}: must be -1 (hoist the whole "
            "tree), 0 (legacy per-use gathers), or N >= 1 (hoist the "
            "first N block-groups)"
        )
    needs = jax.tree.map(
        lambda sh: "data" in spec_axes(sh.spec), layout["params"]
    )
    if ahead == -1:
        return needs
    if ahead == 0:
        return jax.tree.map(lambda _: False, needs)
    groups = gather_groups(layout)
    ordered = sorted({
        g for g, n in zip(jax.tree.leaves(groups), jax.tree.leaves(needs))
        if n
    })
    hoisted = set(ordered[:ahead])
    return jax.tree.map(lambda n, g: bool(n and g in hoisted), needs, groups)


def collective_expectations(layout: dict, topology,
                            gather_ahead: int | None = None) -> dict:
    """What the spec algebra predicts about the collective schedule of a
    step program lowered from ``layout`` under ``topology`` — the
    referee table the static analyzer's collective lint compares the
    compiled program's per-axis collective census against
    (analysis/passes/collectives.py), and the before/after ledger the
    ZeRO-overlap work (ROADMAP #1) scores itself with.

    Returns ``{"leaves", "zero_sharded", "tp_sharded", "ep_sharded",
    "allowed", "gather_bound", "ring"}``:

      * ``allowed`` maps each collective kind to the mesh-axis sets it
        may legitimately run over. Reductions (``all-reduce``) are
        unconstrained over populated axes — grad means, BN/loss
        reductions. Gather-class ops are the dangerous ones: an
        ``all-gather`` over ``data`` is only predicted when a ZeRO stage
        re-gathers rest layouts; in a plain-DDP program it means
        something rests sharded that the declaration says is replicated,
        i.e. a silent re-gather.
      * ``gather_bound`` bounds the non-metric all-gather count over the
        ``data`` axis. Stage 1: ~2 per rest-resharded leaf (the
        post-update re-gather plus slack for XLA splitting one). Stage 3
        under the gather-once schedule (``ZERO.GATHER_AHEAD`` -1, the
        default): ~1 per leaf — every FSDP leaf is gathered once at step
        entry and never again (the r16 model; the PR 14 census priced
        the per-use schedule at ~9.3/leaf and this bound is what makes a
        schedule regression a finding, not a waiver). With hoisting
        disabled or partial (``GATHER_AHEAD`` >= 0) the per-use ceiling
        (10×/leaf) applies — the escape hatch is priced, not flagged.
        Exceeding the bound is a gather storm even when gathers are
        expected at all.
      * ``ring`` (sp topologies only, else ``None``) is the ring-attention
        collective-permute census band: every attention layer routed over
        the seq axis contributes one ``lax.scan`` ring (2 ppermutes per
        body — the k and v hops, ops/ring_attention.py), the body appears
        ONCE in HLO text regardless of trip count, and autodiff transposes
        each ppermute to another ppermute in the backward scan. So a
        program with N attention layers must census at least N seq-axis
        permutes (a lower count means a ring lost its hops — the attention
        silently stopped rotating K/V and each shard attends only its
        local block) and at most ~8N + slack (an overshoot means extra
        seq-axis traffic the declaration does not predict — e.g. an
        activation bouncing between seq layouts). The analyzer's
        collective lint referees the band (analysis/passes/collectives.py).

    ``gather_ahead`` defaults to the live ``cfg.ZERO.GATHER_AHEAD`` (the
    knob the analyzed program was lowered under).
    """
    leaves = jax.tree.leaves(layout["params"])
    grads = jax.tree.leaves(layout["grads"])
    zero_sharded = sum(
        1 for g in grads if "data" in spec_axes(g.spec)
    )
    tp_sharded = sum(1 for p in leaves if "model" in spec_axes(p.spec))
    ep_sharded = sum(1 for p in leaves if "expert" in spec_axes(p.spec))
    zero = int(getattr(topology, "zero", 0))
    feats = topology.features() if hasattr(topology, "features") else set()
    if gather_ahead is None:
        from distribuuuu_tpu.config import cfg

        gather_ahead = int(cfg.ZERO.GATHER_AHEAD)

    gather_axes = set()
    if tp_sharded or "tp" in feats:
        gather_axes.add("model")
    if ep_sharded or "ep" in feats:
        gather_axes.add("expert")
    if "pp" in feats:
        gather_axes.add("pipe")
    if "sp" in feats:
        gather_axes.add("seq")
    if zero:
        gather_axes.add("data")

    gather_bound = None
    if zero == 1:
        gather_bound = 2 * zero_sharded
    elif zero == 3:
        # gather-once (the default schedule): one entry gather per FSDP
        # leaf + slack for metric/loss-adjacent gathers. Per-use (the
        # GATHER_AHEAD >= 0 escape hatch / partial hoisting): the
        # measured ~9.3-gathers/leaf legacy ceiling, rounded to 10.
        if gather_ahead == -1:
            gather_bound = zero_sharded + 4
        else:
            gather_bound = 10 * zero_sharded

    ring = None
    if "sp" in feats:
        n_attn = sum(
            1
            for path, _ in jax.tree_util.tree_flatten_with_path(
                layout["params"]
            )[0]
            if re.search(
                r"Attention_\d+/Dense_0/Dense_0/kernel$", leaf_path(path)
            )
        )
        if n_attn:
            ring = {
                "axis": "seq",
                "attn_layers": n_attn,
                # >= 1 permute per ring layer must survive compilation
                # (fwd k+v hops may fuse but cannot vanish); <= fwd+bwd
                # k/v pairs per layer doubled for XLA splitting, + slack
                # for layout moves at the shard_map boundary
                "min_permutes": n_attn,
                "max_permutes": 8 * n_attn + 4,
            }

    a2a_axes = set()
    if ep_sharded or "ep" in feats or "tp" in feats:
        a2a_axes |= {"expert", "model"}
    if zero:
        # resharding between two data-sharded layouts that shard
        # DIFFERENT dims (grads vs rest after a reshape) lowers to an
        # all-to-all over data — legitimate whenever a stage is on
        a2a_axes.add("data")
    allowed = {
        "all-reduce": None,  # reductions are always legitimate
        "all-gather": gather_axes,
        "reduce-scatter": (
            {"data"} if zero else set()) | (gather_axes - {"data"}),
        "all-to-all": a2a_axes,
        # point-to-point moves are the lowering's workhorse (GPipe hops,
        # ring decompositions of reduce/gather, MoE rotations, halo
        # exchanges) — censused in the ledger, never bounded here
        "collective-permute": None,
    }
    return {
        "leaves": len(leaves),
        "zero_sharded": zero_sharded,
        "tp_sharded": tp_sharded,
        "ep_sharded": ep_sharded,
        "allowed": allowed,
        "gather_bound": gather_bound,
        "ring": ring,
    }


def added_axes(layout: dict) -> tuple[str, ...]:
    """Mesh axes the ZeRO transform ADDED to the grads layout relative to
    the params-base declaration — the axes the spec-induced
    reduce-scatter/all-gather collectives run over (attribution scope
    names and cost records carry them)."""
    grads = {
        ax
        for leaf in jax.tree.leaves(layout["grads"])
        for ax in spec_axes(leaf.spec)
    }
    params = {
        ax
        for leaf in jax.tree.leaves(layout["params"])
        for ax in spec_axes(leaf.spec)
    }
    return tuple(sorted(grads - params)) or tuple(sorted(grads & {"data"}))
