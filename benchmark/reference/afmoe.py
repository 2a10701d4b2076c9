"""Trinity-Mini (``arcee-ai/Trinity-Mini``, HF ``model_type`` ``afmoe``),
plain: every formula as ``config.json`` and the published model class state
it, float32, matmul precision ``highest``, attention as a masked softmax on
whole rows of scores over REPEATED key/value heads with the window written
as a comparison of positions, the gate as a product, a loop over the held
experts, each on every token, with a mask, the loss from full rows of
logits. No kernel, no sort, no grouped head, no skipped tile. It is computed
in blocks so that 2 x 8192 tokens fit the chip beside the program's own
state: one sequence's ``QUERY_BLOCK`` queries against every key, and
``ROW_BLOCK`` tokens at a time through the dense MLP, the shared expert and
the head, each block computed again in the backward (``jax.checkpoint``),
which changes no value. Written from those formulas, not from the program's
modules; it reads the program's parameter tree, and the tree of its routers'
biases, by their names only.

    RMSNorm(x; w) = x * rsqrt(mean(x^2) + eps) * w
    x0 = E[token] * sqrt(hidden_size)                 (mup_enabled)
    h = x + N2(attn_i(N1(x)))      y = h + N4(F_i(N3(h)))
    attn_i(u): q = u W_q (H heads of D), k = u W_k, v = u W_v (G heads of D),
        g = u W_g (H heads of D)
        q, k <- RMSNorm over each head's D dims (one scale of D each)
        layer_types[i] == "sliding_attention": q, k <- R(q), R(k), R rotary,
            and query t keeps the keys s with t - window < s <= t
        layer_types[i] == "full_attention": NO rotary; every key s <= t
        score_h(t, s) = q_h(t) . k_{h // (H/G)}(s) / sqrt(D), softmax over
            the kept keys
        out = (concat_h(sum_s p v_{h // (H/G)}(s)) * sigmoid(g)) W_o
    F_i = W_2(silu(W_1 x) * W_3 x), width intermediate_size, in the first
        num_dense_layers layers; the mixture in every later one
    Mixture(u): s = sigmoid(u W_r);  I = the top-k of s + b;
        g_i = route_scale * s_i / (sum_{j in I} s_j + route_norm_eps)
        out = sum_{i in I and held} g_i E_i(u) + E_shared(u), E a gated-SiLU
        FFN of width moe_intermediate_size
    logits = RMSNorm(last y) W_head (untied)
    loss = CE(x_{t+1}) + alpha mean_mixtures(E sum_e f_e P_e), f_e the share
        of the (token, slot) choices expert e received, P_e the mean over
        tokens of s_e / sum_j s_j

``architecture["layer_types"]`` lists the layers that are RUN (a stage of
the published list) and ``architecture["num_dense_layers"]`` how many of
them, from the first, carry the dense FFN.

**The share.** As ``reference/glm_moe.py``: ``share_chips`` chips share
every layer and this is rank ``share_rank`` of them, holding ``experts_held``
consecutive experts of ``num_experts`` and ``vocab_held`` rows of embedding
and head. The router keeps all its outputs, its k a token and the
normalisation over all k chosen; the experts that are not held add nothing,
here as in the program, and that partial sum goes on to the next layer. The
shared expert is whole on every chip. The loss is over the held rows of the
vocabulary.

Departures from the published description, each on purpose:
* the output gate, the per-head q/k norms, the four norms a block and full
  layers without rotary are the published ``afmoe`` model class's, written
  from memory of it (``config.json`` names none of them; the configuration
  file lists each under ``assumed``).
* the bias ``b`` moves by DeepSeek-V3's rule ``b_e += gamma sign(mean(c) -
  c_e)`` (:func:`bias_after`) at gamma = ``bias_update_rate``;
  ``config.json`` gives ``load_balance_coeff`` 0.001 and no rule.
* the balancing term (``ops/moe.balance_stats``' form, over the batch, on
  the scores normalised over ALL experts) is this repo's.
* rotary is the rotate-half convention over the whole head; its angles are
  float32 for every precision.
* ``precision`` lets the benchmark show that its tolerances have teeth: with
  ``jnp.bfloat16`` every matmul input, the router, the norms, the gate, the
  softmaxes and the loss are rounded to bfloat16, the nearest precision
  below what the configuration states (float32 for those parts).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024
ROW_BLOCK = 2048
NORMS = ("input_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm")


def _by_rows(fn, *rows):
    """``fn`` on ``ROW_BLOCK`` rows at a time (a token's row depends on no
    other's), where the blocks divide them; whole otherwise."""
    count = rows[0].shape[0]
    if count % ROW_BLOCK or count == ROW_BLOCK:
        return fn(*rows)
    blocks = tuple(r.reshape(-1, ROW_BLOCK, *r.shape[1:]) for r in rows)
    out = jax.lax.map(jax.checkpoint(lambda b: fn(*b)), blocks)
    return out.reshape(count, *out.shape[2:])


def _rms_norm(x, weight, eps):
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + eps) * weight.astype(x.dtype)


def _rotary(x, theta):
    """x: [..., S, D], positions 0..S-1, rotate-half."""
    seq, dim = x.shape[-2:]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    freqs = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb).astype(x.dtype), jnp.sin(emb).astype(x.dtype)
    half = dim // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def _w(p, name, like):
    return p[name]["kernel"].astype(like.dtype)


def _attention(u, p, kind, a):
    batch, seq, _ = u.shape
    heads, kv_heads, dim = (
        a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"])
    eps = a["rms_norm_eps"]
    sliding = kind == "sliding_attention"

    def split(name, n):  # [B, n, S, D]
        return (u @ _w(p, f"{name}_proj", u)).reshape(
            batch, seq, n, dim).transpose(0, 2, 1, 3)

    q = _rms_norm(split("q", heads), p["q_norm"]["scale"], eps)
    k = _rms_norm(split("k", kv_heads), p["k_norm"]["scale"], eps)
    if sliding:  # a full layer carries no position signal
        q, k = _rotary(q, a["rope_theta"]), _rotary(k, a["rope_theta"])
    # query head h reads key/value head h // group: the heads repeated
    k, v = (jnp.repeat(t, heads // kv_heads, axis=1) for t in (k, split("v", kv_heads)))
    scale = 1.0 / jnp.sqrt(jnp.asarray(dim, u.dtype))
    block = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq
    key_pos = jnp.arange(seq)

    @jax.checkpoint
    def rows(at):
        """One sequence's block of queries against every key of it."""
        b, start = at
        qb = jax.lax.dynamic_slice_in_dim(q[b], start, block, axis=1)
        scores = jnp.einsum("hqd,hkd->hqk", qb, k[b]) * scale
        query_pos = (start + jnp.arange(block))[:, None]
        kept = key_pos[None, :] <= query_pos
        if sliding:
            kept = kept & (query_pos - key_pos[None, :] < a["sliding_window"])
        scores = jnp.where(kept, scores, jnp.finfo(scores.dtype).min)
        return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, axis=-1), v[b])

    starts = jnp.arange(0, seq, block)
    at = (jnp.repeat(jnp.arange(batch), starts.size), jnp.tile(starts, batch))
    out = jax.lax.map(rows, at)  # [B * n, H, block, D]
    out = out.reshape(batch, starts.size, heads, block, dim)
    out = out.transpose(0, 1, 3, 2, 4).reshape(batch, seq, heads * dim)
    gate = jax.nn.sigmoid(u @ _w(p, "gate_proj", u))
    return (out * gate) @ _w(p, "o_proj", u)


def _gated(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _mlp(u, p):
    """[..., d] -> [..., d], the tokens a block of rows at a time."""
    weights = tuple(_w(p, f"{n}_proj", u) for n in ("gate", "up", "down"))
    out = _by_rows(lambda rows: _gated(rows, *weights), u.reshape(-1, u.shape[-1]))
    return out.reshape(u.shape)


def _mixture(u, p, bias, a, held=None):
    """(out, the scores the experts were chosen by [T, E], the experts
    chosen [T, k], the unbiased scores [T, E]). ``held`` = (first, count)
    overrides the architecture's share (the tests' share test)."""
    batch, seq, width = u.shape
    tokens = u.reshape(batch * seq, width)
    scores = jax.nn.sigmoid(tokens @ p["router"].astype(u.dtype))
    chosen_by = scores + bias.astype(u.dtype)
    _, top_e = jax.lax.top_k(chosen_by, a["num_experts_per_tok"])
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    gates = a["route_scale"] * top_s / (
        top_s.sum(axis=-1, keepdims=True) + a["route_norm_eps"])
    first, count = held or (a["share_rank"] * a["experts_held"], a["experts_held"])

    @jax.checkpoint
    def weighted(e, w_gate, w_up, w_down):
        """One held expert on every token; its weight is 0 where not chosen."""
        out = _gated(tokens, *(w.astype(u.dtype) for w in (w_gate, w_up, w_down)))
        return out * jnp.where(top_e == e, gates, 0.0).sum(axis=-1)[:, None]

    def add_expert(out, expert):
        return out + weighted(*expert), None

    out, _ = jax.lax.scan(
        add_expert, _mlp(tokens, p["shared"]),  # the shared expert: every token
        (first + jnp.arange(count), p["w_gate"], p["w_up"], p["w_down"]),
    )
    return out.reshape(u.shape), chosen_by, top_e, scores


def _block(x, p, kind, bias, a):
    eps = a["rms_norm_eps"]

    def norm(i, t):
        return _rms_norm(t, p[NORMS[i]]["scale"], eps)

    x = x + norm(1, _attention(norm(0, x), p["attn"], kind, a))
    u = norm(2, x)
    if "mlp" in p:
        return x + norm(3, _mlp(u, p["mlp"])), None
    out, *routing = _mixture(u, p["moe"], bias, a)
    return x + norm(3, out), routing


def forward(params, biases, tokens, *, architecture: dict, precision=jnp.float32):
    """``(the final-normed state [B, S, d], the mixtures' routing: a
    (chosen_by, experts, scores) a mixture)``. ``biases`` is the program's
    ``batch_stats`` tree."""
    a = architecture
    embedding = params["tok_embed"]["embedding"].astype(precision)
    tokens = tokens - a["share_rank"] * a["vocab_held"]
    with jax.default_matmul_precision("highest"):
        x, routing = embedding[tokens], []
        if a["mup_enabled"]:
            x = x * jnp.sqrt(jnp.asarray(a["hidden_size"], x.dtype))
        for i, kind in enumerate(a["layer_types"]):
            name = f"Block_{i}"
            bias = None if i < a["num_dense_layers"] else (
                biases[name]["moe"]["router_bias"])
            x, routed = jax.checkpoint(
                lambda x, p, b, kind=kind: _block(x, p, kind, b, a)
            )(x, params[name], bias)
            if routed is not None:
                routing.append(routed)
        state = _rms_norm(x, params["final_norm"]["scale"], a["rms_norm_eps"])
    return state, routing


def logits(params, biases, tokens, *, architecture: dict, precision=jnp.float32):
    """The ``[B, S, vocab_held]`` logits (the CPU tests' size only)."""
    state, _ = forward(
        params, biases, tokens, architecture=architecture, precision=precision)
    with jax.default_matmul_precision("highest"):
        return state @ params["head"].astype(precision)


def bias_after(bias, counts, rate):
    """DeepSeek-V3's rule (arXiv:2412.19437 section 2.1.2): after a step, an
    expert that received fewer choices than the mean goes up by ``rate``,
    one that received more goes down."""
    counts = counts.astype(jnp.float32)
    return bias + rate * jnp.sign(counts.mean(axis=-1, keepdims=True) - counts)


def loss(params, biases, tokens, labels, *, architecture: dict,
         precision=jnp.float32):
    """``{"loss", "ce", "load_balance", "held_row_share", "counts" [mixtures,
    E], "experts" [mixtures, T, k], "chosen_by" [mixtures, T, E]}``: the loss
    and its parts on the whole batch, the share of the (token, slot) choices
    that fell on held experts (a mean over the mixtures), how many choices
    each expert of each mixture received, the experts chosen and the biased
    scores they were chosen by."""
    a = architecture
    state, routing = forward(
        params, biases, tokens, architecture=architecture, precision=precision)
    labels = labels - a["share_rank"] * a["vocab_held"]
    head = params["head"].astype(precision)

    def rows(h, y):
        """Per-token loss from full rows of logits."""
        with jax.default_matmul_precision("highest"):
            logp = jax.nn.log_softmax(h @ head, axis=-1)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]

    terms = {"ce": _by_rows(
        rows, state.reshape(-1, state.shape[-1]), labels.reshape(-1)).mean()}
    experts, k = a["num_experts"], a["num_experts_per_tok"]
    first = a["share_rank"] * a["experts_held"]
    balance, counts = [], []
    for _, top_e, scores in routing:
        counts.append(jax.nn.one_hot(top_e, experts, dtype=jnp.int32).sum(axis=(0, 1)))
        share = counts[-1].astype(scores.dtype) / (top_e.shape[0] * k)
        mean_score = (scores / (scores.sum(axis=-1, keepdims=True) + 1e-20)).mean(axis=0)
        balance.append(experts * jnp.sum(share * mean_score))
    terms["load_balance"] = jnp.mean(jnp.stack(balance))
    terms["loss"] = terms["ce"] + a["balance_loss_weight"] * terms["load_balance"]
    counts = jnp.stack(counts)
    out = {k: v.astype(jnp.float32) for k, v in terms.items()}
    out["held_row_share"] = (
        counts[:, first:first + a["experts_held"]].sum(-1) / counts.sum(-1)
    ).mean().astype(jnp.float32)
    out["counts"] = counts
    out["experts"] = jnp.stack([top_e for _, top_e, _ in routing])
    out["chosen_by"] = jnp.stack([c for c, _, _ in routing]).astype(jnp.float32)
    return out
