"""Expert parallelism: mixture-of-experts FFN over a mesh axis.

Beyond the reference's capability set (DDP-only, SURVEY.md §2.3) — expert
parallelism completes the framework's parallelism matrix (DP/TP/SP/PP/EP)
because distributed scale is a first-class goal here.

Three execution paths and three gating conventions.

Paths:

- ``moe_ffn_partial``: every rank runs its LOCAL experts over all tokens and
  the gate-weighted partial outputs are summed with one ``psum`` over the
  expert axis. Exact (no token dropping, no capacity), communication = one
  allreduce of the output, cost O(E/n) expert rows a token — the right
  choice when tokens-per-expert is dense (small expert counts, top-k close
  to E). ``moe_ffn_reference`` is its one-device form and the oracle.
- ``moe_ffn_dispatch``: classic switch-style routing. Tokens are dispatched
  to their top-k experts' ranks with ``all_to_all``, processed by the local
  experts at a fixed capacity, and combined back. Communication = 2
  all_to_alls of the routed tokens — the scalable path when the experts are
  sharded, E is large and top-k small. Over-capacity tokens are dropped
  (standard switch semantics), so it matches the exact path only when
  capacity is ample.
- ``moe_ffn_sorted``: dropless, O(top_k) expert rows a token, for experts
  that no mesh axis shards (one chip, or every data rank holding the same
  ones): the (token, slot) assignments are sorted by expert, the gated
  three-matrix bias-free expert (``silu(x W_gate) * (x W_up)) W_down``) runs
  on each expert's group of rows, and the rows are gathered back to their
  tokens, weighted and summed. No capacity, nothing dropped. On the TPU
  every group starts on a row-tile boundary and the expert is the repo's own
  Pallas grouped matmuls (``ops/pallas/moe_gmm.py``: six calls forward and
  backward, the activation and the float32 dW in their epilogues); off the
  TPU, and where the widths or the rows an expert leave no tile, each
  projection is one ``jax.lax.ragged_dot`` over the rows back to back,
  which is also the oracle the kernels are tested against. The experts may
  be ALL the router ranges over (``models/olmoe.py``) or one chip's share
  of an expert-parallel layer (``held=(first, total)``, ``models/
  glm_moe.py``): the router still scores all ``total`` and takes its
  ``top_k``, only the rows whose expert is held enter the sorted buffer
  (sized for all T * k: how many land here is the data's to say), and the
  result is that chip's PART of the layer's sum. The exchange that would
  bring the other chips' parts is not here (ROADMAP R2).
  What moves the rows (:func:`_row_mover`): with every expert held every
  row of the buffer is live, and XLA moves them: one gather of ``[T * k, d]``
  into the buffer (``_take_sorted``) and one out of it (``_rows_back``),
  a ``[T, k, d]`` product with the weights and a sum over the slots, and
  their transposes. With a share held most row tiles of the buffer are
  dead, and on the kernel path ``ops/pallas/moe_rows.py`` follows the tile
  table as the grouped matmuls do: ``take`` copies the tokens' rows into
  the LIVE tiles (a dead tile is never written, and holds whatever the
  memory held: nothing outside the kernels may read it), ``combine`` sums
  each token's PRESENT slots in float32 with the weights, and each is the
  other's backward; no ``[T * k, d]`` or ``[T, k, d]`` array is read or
  written whole. What stays in XLA is arithmetic on ``[T * k]`` integers
  and floats (router, the two sorts, the tile table, each group's shifted
  copy into the buffer's order, one gather of T * k floats in the backward).

Gating: ``top_k_from_probs`` renormalizes the selected probabilities to sum
to 1 (the switch/mixtral convention; ``gpt_nano_moe``, ``vit_tiny_moe``).
``top_k_as_is`` uses them as they come out of the softmax (OLMoE,
``norm_topk_prob`` false). ``top_k_biased`` takes sigmoid scores
(``gating_scores``), chooses by ``score + bias`` and weighs by the unbiased
scores normalised over the chosen, times a scale (DeepSeek-V3's and
GLM-4.x's ``noaux_tc``); ``bias_after`` is the rule that moves the bias.

Parameters (functional, like ops/ring_attention.py):
  gate  [d, E]              (replicated)
  w_in  [E, d, f], b_in  [E, f]   (sharded over the expert axis, dim 0)
  w_out [E, f, d], b_out [E, d]   (sharded over the expert axis, dim 0)
and for the sorted path ``w_gate``/``w_up`` [E, d, f], ``w_down`` [E, f, d].
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from distribuuuu_tpu.ops import pallas as kernel_tier
from distribuuuu_tpu.ops.pallas import moe_gmm, moe_rows


def init_moe_params(key, d_model: int, d_ff: int, num_experts: int):
    """Reference initializer: returns the param dict described above."""
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = 1.0 / np.sqrt(d_model)
    scale_out = 1.0 / np.sqrt(d_ff)
    return {
        "gate": jax.random.normal(k1, (d_model, num_experts), jnp.float32)
        * scale_in,
        "w_in": jax.random.normal(k2, (num_experts, d_model, d_ff), jnp.float32)
        * scale_in,
        "b_in": jnp.zeros((num_experts, d_ff), jnp.float32),
        "w_out": jax.random.normal(k3, (num_experts, d_ff, d_model), jnp.float32)
        * scale_out,
        "b_out": jnp.zeros((num_experts, d_model), jnp.float32),
    }


def moe_params_sharding(mesh, params, axis: str = "model"):
    """Expert-dim-0 sharding for the expert tensors; gate replicated."""

    def spec(path_leaf, x):
        if path_leaf == "gate":
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(axis, *([None] * (np.ndim(x) - 1))))

    return {k: spec(k, v) for k, v in params.items()}


def gating_probs(x, gate_w):
    """Router probabilities: softmax(x @ gate) in fp32, [T, E]. The single
    source of routing — compute once, feed both the expert paths and the
    load-balancing aux."""
    logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    return jax.nn.softmax(logits, axis=-1)


def top_k_from_probs(probs, top_k: int):
    """Softmax-renormalized top-k gate from precomputed probabilities.

    Returns (weights [T, k] f32, indices [T, k] i32).
    """
    weights, indices = jax.lax.top_k(probs, top_k)
    weights = weights / jnp.maximum(
        weights.sum(axis=-1, keepdims=True), 1e-9
    )
    return weights, indices.astype(jnp.int32)


def top_k_as_is(probs, top_k: int):
    """Top-k gate whose weights are the selected probabilities as the
    softmax gave them: no renormalization (OLMoE's ``norm_topk_prob``
    false). Returns (weights [T, k] f32, indices [T, k] i32)."""
    weights, indices = jax.lax.top_k(probs, top_k)
    return weights, indices.astype(jnp.int32)


def gating_scores(x, gate_w):
    """Router scores ``sigmoid(x @ gate)`` in float32, [T, E]: each expert's
    affinity on its own, not a distribution over the experts (DeepSeek-V3's
    and GLM-4.x's ``noaux_tc`` router)."""
    return jax.nn.sigmoid(x.astype(jnp.float32) @ gate_w.astype(jnp.float32))


def top_k_biased(scores, bias, top_k: int, scale: float = 1.0,
                 eps: float = 1e-20):
    """The verdict of a router that balances by a bias (arXiv:2412.19437
    section 2.1.2): the ``top_k`` experts by ``scores + bias``, each weighted
    by its UNBIASED score over the chosen ones' sum plus ``eps`` (1e-20:
    GLM's; LFM2's modeling file adds 1e-6), times ``scale``
    (``norm_topk_prob`` with ``routed_scaling_factor``). The bias decides who
    is chosen and nothing else: no gradient reaches it. Returns (weights
    [T, k] f32, indices [T, k] i32)."""
    _, indices = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    # scores[t, indices[t]] as a compare and a sum, as :func:`_pick`: XLA:TPU
    # runs a gather of T * k floats (and its scatter back) an element at a
    # time, 0.67 ms at 16,384 tokens (PERF.md section 6, PR 42)
    hot = indices[..., None] == jnp.arange(scores.shape[-1], dtype=indices.dtype)
    chosen = jnp.where(hot, scores[..., None, :], 0).sum(axis=-1)
    weights = scale * chosen / (chosen.sum(axis=-1, keepdims=True) + eps)
    return weights, indices.astype(jnp.int32)


def bias_after(bias, counts, rate: float):
    """The balancing bias after a step that routed ``counts`` [E] (token,
    slot) choices: up by ``rate`` for an expert under the mean load, down
    for one over it. A rule, not a gradient step."""
    counts = counts.astype(jnp.float32)
    return bias + rate * jnp.sign(counts.mean() - counts)


def softmax_route(x, gate_w, top_k: int, *, renormalise: bool = False):
    """``(probs, weights, indices)`` of the softmax router, a float32 softmax
    over ALL experts and its ``top_k`` largest, under either setting of the
    published ``norm_topk_prob``: false (the default, :func:`moe_ffn_sorted`'s
    and OLMoE's) leaves the weights the probabilities as they are;
    ``renormalise`` (true: Qwen3-MoE's and SDAR's) divides them by the chosen
    ones' sum, so a token's weights sum to 1."""
    probs = gating_probs(x, gate_w)
    top = top_k_from_probs if renormalise else top_k_as_is
    return (probs, *top(probs, top_k))


def sigmoid_route(x, gate_w, top_k: int, *, bias, scale: float,
                  eps: float = 1e-20):
    """``(probs, weights, indices)`` of the sigmoid router with a balancing
    bias; ``probs`` are the scores normalised over ALL experts, what the
    balancing loss reads (:func:`balance_stats`' ``p``); ``eps`` is
    :func:`top_k_biased`'s."""
    scores = gating_scores(x, gate_w)
    probs = scores / (scores.sum(axis=-1, keepdims=True) + 1e-20)
    return (probs, *top_k_biased(scores, bias, top_k, scale, eps))


def top_k_gating(x, gate_w, top_k: int):
    """Softmax-renormalized top-k gate (gating_probs ∘ top_k_from_probs)."""
    return top_k_from_probs(gating_probs(x, gate_w), top_k)


def balance_stats(probs, top_k: int):
    """The two token-mean vectors the balancing aux is bilinear in:
    ``f`` [E] — fraction of (token, k) assignments per expert (Σf = 1),
    ``p`` [E] — mean router probability per expert.

    Exposed separately because both are MEANS over tokens: stats computed
    over disjoint equal-size token subsets (pipeline microbatches, data
    shards) AVERAGE to the full-batch stats exactly — so the full-batch
    aux can be reconstructed exactly from accumulated (f, p), which a
    mean of per-subset aux scalars cannot (f·p is nonlinear). This is how
    parallel/pp.py collects the aux under PP (VERDICT r3 #2).
    """
    E = probs.shape[-1]
    _, indices = jax.lax.top_k(probs, top_k)
    assigned = jax.nn.one_hot(indices, E).sum(axis=1)          # [T, E] 0/1
    f = assigned.mean(axis=0) / top_k                          # Σf = 1
    p = probs.mean(axis=0)
    return f, p


def aux_from_balance_stats(f, p):
    """``E · Σ_e f_e · P_e`` from :func:`balance_stats` vectors."""
    return f.shape[-1] * jnp.sum(f * p)


def load_balancing_loss_from_probs(probs, top_k: int):
    """Switch-transformer auxiliary loss (arXiv:2101.03961 eq. 4-6).

    ``E · Σ_e f_e · P_e`` where ``f_e`` is the fraction of tokens whose
    top-k includes expert e and ``P_e`` the mean router probability of e.
    Minimized (=1.0) at a uniform assignment; add ``λ·aux`` (λ≈0.01) to the
    task loss to keep routed experts balanced — without it top-k routing
    collapses onto a few experts and the dispatch path drops tokens.
    """
    return aux_from_balance_stats(*balance_stats(probs, top_k))


def load_balancing_loss(x, gate_w, top_k: int):
    """`load_balancing_loss_from_probs` with the router computed here."""
    return load_balancing_loss_from_probs(gating_probs(x, gate_w), top_k)


def _expert_ffn(w_in, b_in, w_out, b_out, x):
    """One expert's FFN on [T, d] tokens: gelu(x@w_in+b)@w_out+b."""
    h = jax.nn.gelu(x @ w_in.astype(x.dtype) + b_in.astype(x.dtype))
    return h @ w_out.astype(x.dtype) + b_out.astype(x.dtype)


def moe_ffn_reference(params, x, top_k: int = 2):
    """Dense single-device reference: loop over ALL experts, weighted sum.
    The oracle the parallel paths are tested against."""
    T = x.shape[0]
    weights, indices = top_k_gating(x, params["gate"], top_k)
    E = params["gate"].shape[-1]
    out = jnp.zeros_like(x)
    for e in range(E):
        y = _expert_ffn(
            params["w_in"][e], params["b_in"][e],
            params["w_out"][e], params["b_out"][e], x,
        )
        # weight of expert e for each token (0 when not in its top-k)
        w_e = (weights * (indices == e)).sum(axis=-1)  # [T]
        out = out + y * w_e[:, None].astype(x.dtype)
    return out


def _rank_partials(params, tokens, axis: str, top_k: int):
    """The shared per-rank body of the partial strategy: route the [T, d]
    tokens, run the LOCAL experts, psum the partials over ``axis``. Call
    inside shard_map with ``axis`` bound."""
    r = jax.lax.axis_index(axis)
    local_E = params["w_in"].shape[0]  # E / n
    weights, indices = top_k_from_probs(
        gating_probs(tokens, params["gate"]), top_k
    )
    out = jnp.zeros_like(tokens)
    for le in range(local_E):
        ge = r * local_E + le  # global expert id
        y = _expert_ffn(
            params["w_in"][le], params["b_in"][le],
            params["w_out"][le], params["b_out"][le], tokens,
        )
        w_e = (weights * (indices == ge)).sum(axis=-1)
        out = out + y * w_e[:, None].astype(tokens.dtype)
    return jax.lax.psum(out, axis)


def _moe_param_specs(axis: str):
    """shard_map specs shared by ALL strategies: expert tensors on ``axis``
    dim 0, gate replicated."""
    return {
        "gate": P(),
        "w_in": P(axis), "b_in": P(axis),
        "w_out": P(axis), "b_out": P(axis),
    }


def moe_ffn_partial(params, x, *, mesh, axis: str = "model", top_k: int = 2):
    """Exact expert-parallel MoE: local experts over all tokens + one psum.

    ``x``: [T, d] tokens (replicated over ``axis``; shard T over ``data``
    outside if desired). Expert params sharded over ``axis`` dim 0.
    """
    n = mesh.shape[axis]
    E = params["gate"].shape[-1]
    assert E % n == 0, f"expert-axis size {n} must divide num_experts {E}"

    def per_rank(params, x):
        return _rank_partials(params, x, axis, top_k)

    return jax.shard_map(
        per_rank,
        mesh=mesh,
        in_specs=(_moe_param_specs(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(params, x)


def moe_ffn_partial_batched(
    params,
    x,
    *,
    mesh,
    axis: str = "model",
    data_axis: str | None = "data",
    top_k: int = 2,
):
    """`moe_ffn_partial` for batched activations inside a larger SPMD program.

    ``x``: [B, S, d] with B sharded over ``data_axis`` (the trainer's layout).
    Tokens stay on their data shard — each data rank routes and combines its
    own B_local·S tokens; the only communication is the expert-partials psum
    over ``axis``. This is the trainer-facing EP entry point (DP × EP
    composition); ``moe_ffn_partial`` is the flat-token primitive.
    """
    n = mesh.shape[axis]
    E = params["gate"].shape[-1]
    if E % n:
        raise ValueError(f"expert-axis size {n} must divide num_experts {E}")

    def per_rank(params, x):
        b, s, d = x.shape
        out = _rank_partials(params, x.reshape(b * s, d), axis, top_k)
        return out.reshape(b, s, d)

    data_sharded = bool(data_axis) and mesh.shape.get(data_axis, 1) > 1
    x_spec = P(data_axis) if data_sharded else P()
    return jax.shard_map(
        per_rank,
        mesh=mesh,
        in_specs=(_moe_param_specs(axis), x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(params, x)


def _rank_dispatch(params, x, *, axis: str, top_k: int, C: int, valid=None):
    """The per-rank switch-dispatch body (call inside shard_map, ``axis``
    bound; tokens sharded over ``axis``). ``x``: [T_local, d] — this rank's
    token shard; ``valid``: optional [T_local] bool marking real (non-pad)
    tokens. Returns ``(out [T_local, d], kept, total)`` where kept/total
    count this rank's surviving vs valid (token, k) assignments — psum and
    divide for the global dropped fraction.
    """
    E = params["gate"].shape[-1]
    n = jax.lax.psum(1, axis)
    local_E = E // n
    T_local, d = x.shape
    weights, indices = top_k_gating(x, params["gate"], top_k)  # [Tl,k]
    flat_e = indices.reshape(-1)          # [Tl*k] global expert ids
    flat_w = weights.reshape(-1)          # [Tl*k]
    flat_tok = jnp.repeat(jnp.arange(T_local), top_k)
    if valid is None:
        flat_valid = jnp.ones((T_local * top_k,), bool)
    else:
        flat_valid = jnp.repeat(valid, top_k)

    # slot of each assignment within its expert's per-source capacity
    # (pad tokens take no slot: their one_hot row is zeroed)
    one_hot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)      # [Tl*k, E]
    one_hot = one_hot * flat_valid[:, None].astype(jnp.int32)
    pos_in_e = jnp.cumsum(one_hot, axis=0) * one_hot - 1      # [Tl*k, E]
    pos = pos_in_e.max(axis=-1)                               # [Tl*k]
    keep = (pos >= 0) & (pos < C)

    # dispatch buffer [E, C, d]: my tokens, slotted per target expert
    disp = jnp.zeros((E, C, d), x.dtype)
    disp = disp.at[
        jnp.where(keep, flat_e, 0),
        jnp.where(keep, pos, 0),
    ].add(jnp.where(keep[:, None], x[flat_tok], 0), mode="drop")

    # all_to_all #1: chunk p (= experts owned by rank p) goes to rank p;
    # I receive, from every source rank s, the slots for MY experts.
    disp = disp.reshape(n, local_E, C, d)
    recv = jax.lax.all_to_all(disp, axis, split_axis=0, concat_axis=0)
    # recv: [n, local_E, C, d], recv[s, le] = rank s's tokens for my
    # local expert le → flatten source into the slot dim per expert
    recv = jnp.moveaxis(recv, 0, 1).reshape(local_E, n * C, d)

    # local expert compute
    y = jnp.stack(
        [
            _expert_ffn(
                params["w_in"][le], params["b_in"][le],
                params["w_out"][le], params["b_out"][le], recv[le],
            )
            for le in range(local_E)
        ]
    )  # [local_E, n*C, d]

    # all_to_all #2 (return trip): chunk s goes back to source rank s
    y = jnp.moveaxis(y.reshape(local_E, n, C, d), 1, 0)  # [n, local_E, C, d]
    back = jax.lax.all_to_all(y, axis, split_axis=0, concat_axis=0)
    # back: [n, local_E, C, d], back[p, le] = output of global expert
    # (p*local_E + le) for MY tokens' slots → [E, C, d]
    back = back.reshape(E, C, d)

    # combine: weighted gather of each kept assignment's output
    gathered = back[
        jnp.where(keep, flat_e, 0), jnp.where(keep, pos, 0)
    ]  # [Tl*k, d]
    contrib = gathered * jnp.where(keep, flat_w, 0.0)[:, None].astype(x.dtype)
    out = jnp.zeros_like(x).at[flat_tok].add(contrib)
    kept = keep.sum().astype(jnp.float32)
    total = flat_valid.sum().astype(jnp.float32)
    return out, kept, total


def moe_ffn_dispatch(
    params,
    x,
    *,
    mesh,
    axis: str = "model",
    top_k: int = 2,
    capacity_factor: float = 2.0,
):
    """Switch-style routed MoE: all_to_all dispatch → local experts → return.

    Tokens are SHARDED over ``axis`` (each rank routes its own T/n tokens),
    experts are sharded over the same axis — the DeepSpeed-MoE layout where
    the expert group doubles as the token group. Per (token, k) assignment
    the token rides an ``all_to_all`` to the rank owning that expert; each
    expert processes at most C = ceil(T_local·k/E × capacity_factor) slots
    per source rank (assignments beyond C are dropped — standard switch
    semantics). Matches ``moe_ffn_partial`` exactly when nothing drops.
    """
    n = mesh.shape[axis]
    E = params["gate"].shape[-1]
    assert E % n == 0, f"expert-axis size {n} must divide num_experts {E}"
    T = x.shape[0]
    assert T % n == 0, f"expert-axis size {n} must divide token count {T}"
    C = max(1, int(np.ceil(T // n * top_k / E * capacity_factor)))

    def per_rank(params, x):
        out, _, _ = _rank_dispatch(
            params, x, axis=axis, top_k=top_k, C=C
        )
        return out

    return jax.shard_map(
        per_rank,
        mesh=mesh,
        in_specs=(_moe_param_specs(axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )(params, x)


def moe_ffn_dispatch_batched(
    params,
    x,
    *,
    mesh,
    axis: str = "model",
    data_axis: str | None = "data",
    top_k: int = 2,
    capacity_factor: float = 2.0,
):
    """`moe_ffn_dispatch` for batched activations inside a larger SPMD
    program — the trainer-facing scalable-EP entry point (DP × EP).

    ``x``: [B, S, d] with B sharded over ``data_axis`` and the activations
    replicated over ``axis`` (the trainer's layout between blocks). Each
    data shard's B_local·S tokens are split across the ``axis`` ranks
    (padded up to a multiple — pad tokens take no capacity slots), routed
    through the two all_to_alls, then all_gathered back to the replicated
    layout. Returns ``(out [B, S, d], dropped)`` where ``dropped`` is the
    global fraction of (token, k) assignments lost to the capacity bound —
    0.0 when capacity is ample, at which point the result matches
    ``moe_ffn_partial_batched`` exactly.
    """
    n = mesh.shape[axis]
    E = params["gate"].shape[-1]
    if E % n:
        raise ValueError(f"expert-axis size {n} must divide num_experts {E}")
    B, S, d = x.shape
    data_sharded = bool(data_axis) and mesh.shape.get(data_axis, 1) > 1
    data_size = mesh.shape.get(data_axis, 1) if data_sharded else 1
    if B % data_size:
        raise ValueError(
            f"batch {B} does not shard over data axis of size {data_size}"
        )
    reduce_axes = (axis, data_axis) if data_sharded else (axis,)

    def per_rank(params, xl):
        return dispatch_inline(
            params, xl, axis=axis, top_k=top_k,
            capacity_factor=capacity_factor, reduce_axes=reduce_axes,
        )

    x_spec = P(data_axis) if data_sharded else P()
    return jax.shard_map(
        per_rank,
        mesh=mesh,
        in_specs=(_moe_param_specs(axis), x_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(params, x)


def dispatch_inline(
    params_local,
    xl,
    *,
    axis: str = "model",
    top_k: int = 2,
    capacity_factor: float = 2.0,
    reduce_axes=None,
):
    """The per-device switch-dispatch body — call with ``axis`` BOUND (inside
    any enclosing shard_map: the trainer's, or a pipeline stage's).

    ``params_local``: this rank's expert shard (``w_in`` [E/n, d, f], ...;
    gate full). ``xl``: [B_local, S, d] activations replicated over ``axis``
    (the layout between transformer blocks). Splits the B_local·S tokens
    across the ``axis`` ranks (padding up to a multiple; pad tokens take no
    capacity slots), routes through the two all_to_alls of
    :func:`_rank_dispatch`, and all_gathers back to the replicated layout.
    Returns ``(out [B_local, S, d], dropped)`` — the dropped fraction is
    psummed over ``reduce_axes`` (default: ``(axis,)``).

    This is the shared body of ``moe_ffn_dispatch_batched`` (which wraps it
    in its own shard_map) and the PP×EP dispatch path (models/vit.MoeMlp
    ``axes_bound`` — a nested shard_map would be illegal, but the
    collectives compose fine on the already-bound axes; VERDICT r3 #3).
    """
    n = jax.lax.axis_size(axis)
    E = params_local["gate"].shape[-1]
    B_l, S, d = xl.shape
    T = B_l * S
    ss = -(-T // n)  # per-axis-rank token shard (ceil)
    Tp = ss * n
    C = max(1, int(np.ceil(ss * top_k / E * capacity_factor)))
    if reduce_axes is None:
        reduce_axes = (axis,)

    flat = xl.reshape(T, d)
    r = jax.lax.axis_index(axis)
    flatp = jnp.pad(flat, ((0, Tp - T), (0, 0)))
    mine = jax.lax.dynamic_slice_in_dim(flatp, r * ss, ss, 0)
    valid = (r * ss + jnp.arange(ss)) < T
    out_l, kept, total = _rank_dispatch(
        params_local, mine, axis=axis, top_k=top_k, C=C, valid=valid
    )
    outp = jax.lax.all_gather(out_l, axis).reshape(Tp, d)
    out = outp[:T].reshape(xl.shape)
    kept = jax.lax.psum(kept, reduce_axes)
    total = jax.lax.psum(total, reduce_axes)
    dropped = 1.0 - kept / jnp.maximum(total, 1.0)
    return out, dropped


# ------------------------------------------------- dropless sorted experts


def expert_counts(indices, num_experts: int):
    """Rows each expert receives: [E] int32 from [..., k] expert ids."""
    hot = indices[..., None] == jnp.arange(num_experts, dtype=indices.dtype)
    return hot.sum(axis=tuple(range(indices.ndim)), dtype=jnp.int32)


def load_max_over_mean(counts):
    """The fullest expert's rows over the mean: 1.0 at a uniform routing,
    E when every assignment lands on one expert."""
    counts = counts.astype(jnp.float32)
    return counts.max() / jnp.maximum(counts.mean(), 1e-9)


def _pick(table, ids):
    """``table[ids]`` for a table of one entry an expert, as a compare and a
    sum: XLA:TPU unrolls a gather from a small table into one slice an
    index (576 of them for the tile table of ``olmoe_1b_7b``)."""
    hot = ids[..., None] == jnp.arange(table.shape[0], dtype=ids.dtype)
    return jnp.where(hot, table, 0).sum(axis=-1, dtype=table.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _take_sorted(x, src, dst, top_k: int, partial: bool = False):
    """Row ``i`` of the result is token ``src[i] // top_k``: the tokens
    repeated ``top_k`` times and permuted into expert order. A ``src`` of
    ``tokens * top_k`` is a pad row and reads zeros (one row of them behind
    ``x``: a select over the gathered rows would be a pass of its own). The
    backward is a gather through ``dst`` (where each (token, slot) went) and
    a sum over the slots, not the scatter-add a plain ``x[ids]`` transposes
    to. ``partial``: some (token, slot) went nowhere (its expert is not
    held here); its ``dst`` is past the buffer and its cotangent zeros."""
    pads = partial or src.shape[0] > x.shape[0] * top_k
    if pads:
        x = jnp.concatenate([x, jnp.zeros_like(x[:1])])
    return x[src // top_k]


def _take_sorted_fwd(x, src, dst, top_k, partial):
    return _take_sorted(x, src, dst, top_k, partial), (dst, x.shape[0])


def _take_sorted_bwd(top_k, partial, res, g):
    dst, tokens = res
    if partial:
        rows = jnp.where((dst < g.shape[0])[:, None],
                         g[jnp.minimum(dst, g.shape[0] - 1)], 0)
    else:
        rows = g[dst]
    return rows.reshape(tokens, top_k, -1).sum(axis=1), None, None


_take_sorted.defvjp(_take_sorted_fwd, _take_sorted_bwd)


@jax.custom_vjp
def _rows_back(y, dst, src):
    """``y[dst]``: every (token, slot)'s row out of the sorted buffer. The
    backward gathers by ``src``; a pad row gets some real row's cotangent,
    which nothing reads as the expert's zeros on that row multiply it."""
    return y[dst]


_rows_back.defvjp(
    lambda y, dst, src: (y[dst], (src, dst.shape[0])),
    lambda res, g: (g[jnp.minimum(res[0], res[1] - 1)], None, None),
)


def _gmm_row_tile(rows: int, E: int, d: int, f: int, interpret, total: int):
    """The row tile of the Pallas grouped matmul (``ops/pallas/moe_gmm.py``)
    for ``rows`` (token, slot) rows routed over ``total`` experts of which
    ``E`` are held here, or None where ``lax.ragged_dot`` runs: off the
    TPU, in a program that may span devices, where a width is no multiple
    of the 128 lanes, or where an expert averages under one row tile. The
    rows that land here depend on the data; the tile is decided on the
    EXPECTED ``rows * E / total`` (all of them where every expert is held),
    the buffer is sized for ``rows`` (``rows_bound``). ``interpret`` not
    None forces the kernel (the tests). Says which ran, and why, in a
    ``kernel.select``/``kernel.fallback`` record."""
    expected = rows * E // total
    tm = moe_gmm.row_tile(expected, E)
    reason = ""
    if d % 128 or f % 128:
        reason = f"widths {d} and {f}: not both multiples of the 128 lanes"
    elif tm is None:
        reason = (f"{expected // E} rows an expert on average: under one row "
                  f"tile of {moe_gmm.ROW_TILE}")
    impl = kernel_tier.select(
        "moe_gmm", supported=not reason, reason=reason,
        forced=interpret is not None, tm=tm, tk=d, tn=f,
        pad_row_share=round(E * (tm or 0) / max(expected, 1), 4),
        calls_a_step=moe_gmm.CALLS_A_STEP,
        experts_held=E, experts_total=total, rows_bound=rows,
    )
    return tm if impl == "pallas" else None


class _Layout(NamedTuple):
    """Where the (token, slot) rows sit in the sorted buffer
    (:func:`_sorted_layout`)."""

    src: jax.Array      # [rows of the buffer]: its (token, slot), T * k on a pad row
    dst: jax.Array      # [T * k]: its row of the buffer, past it if absent
    sizes: jax.Array    # [E]: rows an expert received
    expert: Any         # [tiles] and [1]: moe_gmm.tile_table's, None without
    n_live: Any         # a row tile (rows back to back)
    present: Any        # [T * k] bool where some expert is not held, else None
    flat: jax.Array     # [T * k]: its group, 0 .. E - 1 (E if absent)
    starts: Any         # [E]: the buffer row each group starts at, or None
    scale: Any          # [rows of the buffer]: its weight (a held share's movers)

    @property
    def tm(self) -> int:
        """The row tile (where there is a tile table)."""
        return self.src.shape[0] // self.expert.shape[0]


def _aligned(compact, sizes, offsets, table, tm: int, pad):
    """``compact`` [T * k], one entry a sorted (token, slot), the groups
    back to back, as the buffer's rows hold them: group ``e`` from row
    ``starts[e]``, ``pad`` on the pad rows. Each group is a SHIFTED COPY of
    its run (a roll by ``starts[e] - offsets[e]``), one an expert held: XLA:TPU
    runs the gather ``compact[pos]`` an element at a time, 0.48 ms for the
    67,584 rows of 16,384 tokens (PERF.md section 6, PR 42)."""
    expert, _, starts = table
    tiles = expert.shape[0]
    padded = jnp.concatenate([
        compact, jnp.full((tiles * tm - compact.shape[0],), pad, compact.dtype)])
    row = jnp.arange(tiles * tm, dtype=jnp.int32).reshape(tiles, tm)
    real = row - _pick(starts, expert)[:, None] < _pick(sizes, expert)[:, None]
    out = jnp.full((tiles, tm), pad, compact.dtype)
    for e in range(sizes.shape[0]):
        shifted = jnp.roll(padded, starts[e] - offsets[e]).reshape(tiles, tm)
        out = jnp.where(real & (expert == e)[:, None], shifted, out)
    return out.reshape(-1)


def _sorted_layout(indices, E: int, first: int, total: int, tm,
                   weights=None) -> _Layout:
    """Sort the (token, slot) choices ``indices`` [T, k] by expert: the held
    experts ``first .. first + E - 1`` of ``total`` in order, an absent
    choice behind them all. With a row tile ``tm`` every group starts on a
    tile boundary (``moe_gmm.tile_table``). ``weights`` [T, k] ride a held
    share's sort, so that ``scale`` is each buffer row's weight."""
    T, k = indices.shape
    partial = total != E
    flat, present = indices.reshape(T * k), None
    expert = n_live = starts = scale = None
    if partial:  # the absent sort behind every held group
        flat = flat - first
        present = (flat >= 0) & (flat < E)
        flat = jnp.where(present, flat, E)
    if partial and tm is not None and weights is not None:
        _, order, by_row = jax.lax.sort(
            (flat, jnp.arange(T * k, dtype=jnp.int32),
             jax.lax.stop_gradient(weights).reshape(T * k).astype(jnp.float32)),
            num_keys=1, is_stable=True)
    else:
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    sizes = expert_counts(flat, E)
    if tm is None:  # sorted rows, back to back
        src, dst = order, inverse
        if partial:  # behind the last group: zeros
            src = jnp.where(
                jnp.arange(T * k, dtype=jnp.int32) < sizes.sum(), order, T * k)
    else:  # every group from a row-tile boundary, zeros in between
        table = moe_gmm.tile_table(sizes, T * k, tm)
        expert, n_live, starts = table
        offsets = jnp.cumsum(sizes) - sizes  # the groups back to back
        if partial:  # a few groups: each a shifted copy of its run
            src = _aligned(order, sizes, offsets, table, tm, T * k)
            if weights is not None:
                scale = _aligned(by_row, sizes, offsets, table, tm, 0.0)
        else:
            # row r of the buffer, in a tile of group e, is row r - starts[e]
            # of the group if the group is that long, else a pad row
            row = jnp.arange(expert.shape[0] * tm, dtype=jnp.int32)
            within = row.reshape(-1, tm) - _pick(starts, expert)[:, None]
            pos = _pick(offsets, expert)[:, None] + within
            src = jnp.where(within < _pick(sizes, expert)[:, None],
                            order[jnp.minimum(pos, T * k - 1)], T * k)
            src = src.reshape(-1)
        dst = inverse + _pick(starts - offsets, flat)
    if partial:  # an absent (token, slot) lies past the buffer
        dst = jnp.where(present, dst, src.shape[0])
    return _Layout(src, dst, sizes, expert, n_live, present, flat, starts, scale)


def _mover_tables(lay: _Layout, top_k: int):
    """``(src, dst, bounds, scale, n_live)`` as ``ops/pallas/moe_rows``
    takes them."""
    bounds = moe_rows.block_bounds(lay.flat, lay.starts, lay.starts.shape[0], top_k)
    return (lay.src.reshape(-1, lay.tm), lay.dst.reshape(-1, top_k), bounds,
            lay.scale.reshape(-1, lay.tm), lay.n_live)


def _rows_in(x, lay: _Layout, top_k: int, tables, interpreted: bool):
    """The tokens ``x`` [T, d] into the sorted buffer: the row movers over
    the live tiles (``tables``: :func:`_mover_tables`'; a dead tile is not
    written), or, with none, XLA's gather of every row of the buffer."""
    if tables is not None:
        return moe_rows.take(x, *tables, interpreted)
    return _take_sorted(x, lay.src, lay.dst, top_k, lay.present is not None)


def _rows_out(rows, weights, lay: _Layout, tables, interpreted: bool):
    """The experts' rows back to their tokens, weighted by ``weights``
    [T, k] and summed: the row movers over the present slots, in float32,
    or, without ``tables``, XLA's gather of all T * k and its ``[T, k, d]``
    passes."""
    T, k = weights.shape
    if tables is not None:
        return moe_rows.combine(rows, weights, *tables, interpreted)
    dst = lay.dst
    if lay.present is not None:
        dst = jnp.minimum(dst, rows.shape[0] - 1)
    rows = _rows_back(rows, dst, lay.src).reshape(T, k, -1)
    rows = rows * weights[..., None].astype(rows.dtype)
    if lay.present is not None:  # whatever row an absent slot read, it adds nothing
        rows = jnp.where(lay.present.reshape(T, k, 1), rows, 0)
    return rows.sum(axis=1)


def _row_mover(T: int, k: int, d: int, dtype, tm, E: int, total: int,
               interpret) -> bool:
    """Whether ``ops/pallas/moe_rows`` moves the rows into and out of the
    sorted buffer, following the tile table as the grouped matmuls do: where
    the experts are a SHARE of the router's (most of the buffer's tiles are
    dead) and the grouped matmuls run (there is a tile table). With every
    expert held every row is live and XLA's gathers stay (PERF.md section
    6, PR 42 has both sides' ns a row). Decided on what is static in the
    call, no knob; says which ran, and why, as :func:`_gmm_row_tile`."""
    if total == E:
        reason = "every expert is held: every row of the buffer is live"
    elif tm is None:
        reason = "no tile table: the grouped matmuls run as lax.ragged_dot"
    else:
        reason = moe_rows.unsupported(T, d, dtype)
    impl = kernel_tier.select(
        "moe_rows", supported=not reason, reason=reason,
        forced=interpret is not None, tm=tm, rows_bound=T * k,
        experts_held=E, experts_total=total,
    )
    return impl == "pallas"


def sorted_experts(params, x, weights, indices, *, held=None, interpret=None):
    """The sorted path's body on ``x`` [T, d] with the router's verdict
    ``weights``/``indices`` [T, k] already taken: sort, the gated expert on
    each group, gather back, weight, sum. ``params`` holds ``w_gate``/
    ``w_up`` [E, d, f] and ``w_down`` [E, f, d]; the matmuls run in
    ``x.dtype``. On the TPU the groups are laid out on row-tile boundaries
    and the expert is ``ops/pallas/moe_gmm.expert_ffn``; elsewhere, and for
    shapes without a tile (:func:`_gmm_row_tile`), three ``lax.ragged_dot``.
    ``interpret`` True/False forces the kernel, interpreted or compiled.

    ``held = (first, total)``: the E experts of ``params`` are experts
    ``first .. first + E - 1`` of the ``total`` that ``indices`` range over
    (one chip's share of an expert-parallel layer). A (token, slot) whose
    expert is not held never enters the sorted buffer and adds nothing to
    the result: the sum is this chip's PART of the layer's. How many rows
    land here is the data's to say, so the buffer keeps the static height
    of all T * k (the kernels skip the tiles past the live ones) and nothing
    is ever dropped. ``None``: every expert is held, today's program."""
    T, k = indices.shape
    E, d, f = params["w_gate"].shape
    first, total = (0, E) if held is None else held
    tm = _gmm_row_tile(T * k, E, d, f, interpret, total)
    mover = _row_mover(T, k, d, x.dtype, tm, E, total, interpret)
    interpreted = kernel_tier.interpret_mode() if interpret is None else interpret
    with jax.named_scope("moe_route"):
        lay = _sorted_layout(indices, E, first, total, tm, weights)
        tables = _mover_tables(lay, k) if mover else None
        rows = _rows_in(x, lay, k, tables, interpreted)  # [T*k (+ pads), d]
    with jax.named_scope("moe_experts"):
        if tm is None:
            w_gate, w_up, w_down = (
                params[name].astype(x.dtype)
                for name in ("w_gate", "w_up", "w_down")
            )
            hidden = jax.nn.silu(jax.lax.ragged_dot(rows, w_gate, lay.sizes))
            hidden = hidden * jax.lax.ragged_dot(rows, w_up, lay.sizes)
            rows = jax.lax.ragged_dot(hidden, w_down, lay.sizes)
        else:
            rows = moe_gmm.expert_ffn(
                rows, params["w_gate"], params["w_up"], params["w_down"],
                lay.expert, lay.n_live, tm, interpreted,
            )
    with jax.named_scope("moe_route"):
        return _rows_out(rows, weights, lay, tables, interpreted)


def moe_ffn_sorted(params, x, *, top_k: int, mesh=None, data_axis: str = "data",
                   router_x=None, route=softmax_route, held=None, interpret=None):
    """Dropless top-k MoE with gated experts that no mesh axis shards
    (module docstring).

    ``x``: [B, S, d]. ``params``: ``router`` [d, E] and the three expert
    tensors. Returns ``(out [B, S, d], verdict)`` with ``verdict`` the
    router's: ``probs`` [B*S, E] f32 (for the balancing loss), ``indices``
    [B, S, k] (the experts chosen) and ``counts`` [E] (rows an expert
    received). ``route(tokens [T, d], router, top_k) -> (probs, weights,
    indices)`` is the router, in float32: :func:`softmax_route` (the
    probabilities as they are) unless the caller has another
    (:func:`sigmoid_route`); ``router_x`` is what it reads where that is
    not ``x`` (the same activations before their rounding to the compute
    dtype). ``held`` is :func:`sorted_experts`': the expert tensors are one
    chip's share of the router's E, and ``out`` that chip's part. On a mesh
    whose ``data_axis`` is populated every data rank sorts its own tokens
    (``shard_map`` over the batch dim); nothing crosses ranks. ``interpret``
    is :func:`sorted_experts`'s."""
    B, S, d = x.shape
    E = params["router"].shape[-1]
    with jax.named_scope("moe_route"):
        routed = x if router_x is None else router_x
        probs, weights, indices = route(
            routed.reshape(B * S, d), params["router"], top_k)
        counts = expert_counts(indices, E)
    experts = {name: params[name] for name in ("w_gate", "w_up", "w_down")}

    def body(experts, x, weights, indices):
        b = x.shape[0]
        out = sorted_experts(
            experts, x.reshape(b * S, d), weights.reshape(b * S, top_k),
            indices.reshape(b * S, top_k), held=held, interpret=interpret,
        )
        return out.reshape(b, S, d)

    weights = weights.reshape(B, S, top_k)
    indices = indices.reshape(B, S, top_k)
    shards = int(dict(mesh.shape).get(data_axis, 1)) if mesh is not None else 1
    if shards > 1 and B % shards == 0:
        def per_rank(*args, body=body):
            # one device's tokens: the kernel tier may engage
            with kernel_tier.single_device_program():
                return body(*args)

        body = jax.shard_map(
            per_rank, mesh=mesh,
            in_specs=(P(), P(data_axis), P(data_axis), P(data_axis)),
            out_specs=P(data_axis), check_vma=False,
        )
    verdict = {"probs": probs, "indices": indices, "counts": counts}
    return body(experts, x, weights, indices), verdict


def router_z_loss(x, router_w):
    """``mean(logsumexp(x @ router)^2)`` in float32 (ST-MoE, arXiv:2202.08906
    eq. 5): keeps the router's logits small."""
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    return jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
