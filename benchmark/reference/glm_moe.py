"""GLM-4.7-Flash (``zai-org/GLM-4.7-Flash``, HF ``model_type``
``glm4_moe_lite``; the block is DeepSeek-V3's, arXiv:2412.19437), plain:
every formula as the paper and ``config.json`` state it, float32, matmul
precision ``highest``, masked attention on whole rows of scores, a loop over
the held experts, each on every token, with a mask, the loss from full rows
of logits. No kernel, no sort. It is computed in blocks so that 8192 tokens
fit the chip beside the program's own state: ``QUERY_BLOCK`` queries against
every key, and ``ROW_BLOCK`` tokens at a time through the dense MLP, the
shared expert and the head, each block computed again in the backward
(``jax.checkpoint``), which changes no value. Written from those formulas,
not from the program's module;
it reads the program's parameter tree, and the tree of its routers' biases,
by their names only.

    RMSNorm(x; w) = x * rsqrt(mean(x^2) + eps) * w
    h = x + MLA(RMSNorm(x))      y = h + F(RMSNorm(h))
    F = a dense gated-SiLU MLP in the first ``first_k_dense_replace``
        layers, the mixture in every later one
    MLA(u): c_q = RMSNorm(u W_qa);  [q_nope_h ; q_rope_h] = c_q W_qb
        [c_kv ; k_rope] = u W_kva;  c_kv <- RMSNorm(c_kv)
        [k_nope_h ; v_h] = c_kv W_kvb
        score_h(t, j) = (q_nope_h(t) . k_nope_h(j) + R(q_rope_h)(t) .
            R(k_rope)(j)) / sqrt(nope + rope), ONE k_rope for all heads, R
            rotary over all the rope dims; causal softmax; o_h = sum p v_h;
        out = concat_h(o_h) W_o
    Mixture(u): s = sigmoid(u W_r);  I = the top-k of s + b;
        g_i = scale * s_i / (sum_{j in I} s_j + 1e-20)
        out = sum_{i in I and held} g_i E_i(u) + E_shared(u)
        E(u) = (silu(u W_gate) * (u W_up)) W_down
    MTP: z_t = W_eh [RMSNorm(Emb(x_{t+1})) ; RMSNorm(h_t)], h the last
        layer's output before the final norm; one more mixture block on z;
        its own final norm; the same head, read against x_{t+2}
    loss = CE(x_{t+1}) + lambda CE_mtp(x_{t+2}) + alpha mean_layers(E sum_e
        f_e P_e), f_e the share of the (token, slot) choices expert e
        received, P_e the mean over tokens of s_e / sum_j s_j

**The share.** ``architecture["share_chips"]`` chips share every layer and
this is rank ``share_rank`` of them: it holds ``experts_held`` of the
``n_routed_experts`` (the rank's block of consecutive ones) and
``vocab_held`` rows of embedding and head. The router keeps all its outputs,
its k a token and the normalisation over all k chosen; the experts that are
not held add nothing, here as in the program, and that partial sum goes on
to the next layer. The loss is over the held rows of the vocabulary.

Departures from the published description, each on purpose:
* the bias ``b`` moves by DeepSeek-V3's rule ``b_e += gamma sign(mean(c) -
  c_e)`` (:func:`bias_after`) at gamma = ``bias_update_rate``;
  ``config.json`` names the method (``noaux_tc``) and no rate.
* the balancing term is ``ops/moe.balance_stats``' form, over the batch
  (one sequence a step makes batch and sequence the same), on the scores
  normalised over ALL experts; HF's modeling file has none, GLM-4.5's report
  has a sequence-level term of weight 1e-4.
* rotary is the rotate-half convention (the other, interleaved, convention
  is a fixed permutation of the rope columns of ``W_qb`` and ``W_kva``); its
  angles are float32 for every precision.
* ``x_{t+1}`` is the INPUT one to the left; at a sequence's last position,
  whose next token the input does not hold, it is that position's own token.
  The MTP loss gives that position weight 0 and no other position attends to
  it, but its router's choices are counted like any token's.
* ``precision`` lets the benchmark show that its tolerances have teeth: with
  ``jnp.bfloat16`` every matmul input, the router, the norms, the softmaxes
  and the loss are rounded to bfloat16, the nearest precision below what the
  configuration states (float32 for those parts).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024
ROW_BLOCK = 2048


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


def _mla(u, p, a):
    batch, seq, _ = u.shape
    heads, nope, rope, dv = (a["num_attention_heads"], a["qk_nope_head_dim"],
                             a["qk_rope_head_dim"], a["v_head_dim"])
    eps, rank = a["rms_norm_eps"], a["kv_lora_rank"]
    c_q = _rms_norm(u @ _w(p, "q_a_proj", u), p["q_a_norm"]["scale"], eps)
    q = (c_q @ _w(p, "q_b_proj", u)).reshape(batch, seq, heads, nope + rope)
    kv_a = u @ _w(p, "kv_a_proj", u)
    c_kv = _rms_norm(kv_a[..., :rank], p["kv_a_norm"]["scale"], eps)
    kv = (c_kv @ _w(p, "kv_b_proj", u)).reshape(batch, seq, heads, nope + dv)
    q_nope, k_nope, v = (t.transpose(0, 2, 1, 3) for t in (
        q[..., :nope], kv[..., :nope], kv[..., nope:]))  # [B, H, S, .]
    q_rope = _rotary(q[..., nope:].transpose(0, 2, 1, 3), a["rope_theta"])
    k_rope = _rotary(kv_a[..., rank:], a["rope_theta"])  # [B, S, rope]: ONE a token
    scale = 1.0 / jnp.sqrt(jnp.asarray(nope + rope, u.dtype))
    block = QUERY_BLOCK if seq % QUERY_BLOCK == 0 else seq
    key_pos = jnp.arange(seq)

    @jax.checkpoint
    def rows(start):
        """A block of queries against every key."""
        def cut(t):
            return jax.lax.dynamic_slice_in_dim(t, start, block, axis=2)

        scores = (jnp.einsum("bhqd,bhkd->bhqk", cut(q_nope), k_nope)
                  + jnp.einsum("bhqd,bkd->bhqk", cut(q_rope), k_rope)) * scale
        causal = key_pos[None, :] <= (start + jnp.arange(block))[:, None]
        scores = jnp.where(causal, scores, jnp.finfo(scores.dtype).min)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)

    # the query blocks one after the other: [n, B, H, block, dv]
    out = jax.lax.map(rows, jnp.arange(0, seq, block))
    out = jnp.moveaxis(out, 0, 2).reshape(batch, heads, seq, dv)
    out = out.transpose(0, 2, 1, 3).reshape(batch, seq, heads * dv)
    return out @ _w(p, "o_proj", u)


def _gated(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _mlp(u, p):
    """[..., d] -> [..., d], the tokens a block of rows at a time."""
    weights = tuple(_w(p, f"{n}_proj", u) for n in ("gate", "up", "down"))
    out = _by_rows(lambda rows: _gated(rows, *weights), u.reshape(-1, u.shape[-1]))
    return out.reshape(u.shape)


def _mixture(u, p, bias, a):
    """(out, the scores the experts were chosen by [T, E], the experts
    chosen [T, k], the unbiased scores [T, E])."""
    batch, seq, width = u.shape
    tokens = u.reshape(batch * seq, width)
    scores = jax.nn.sigmoid(tokens @ p["router"].astype(u.dtype))
    chosen_by = scores + bias.astype(u.dtype)
    _, top_e = jax.lax.top_k(chosen_by, a["num_experts_per_tok"])
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    gates = a["routed_scaling_factor"] * top_s / (
        top_s.sum(axis=-1, keepdims=True) + 1e-20)
    first = a["share_rank"] * a["experts_held"]

    @jax.checkpoint
    def weighted(e, w_gate, w_up, w_down):
        """One held expert on every token; its weight is 0 where not chosen."""
        out = _gated(tokens, *(w.astype(u.dtype) for w in (w_gate, w_up, w_down)))
        return out * jnp.where(top_e == e, gates, 0.0).sum(axis=-1)[:, None]

    def add_expert(out, expert):
        return out + weighted(*expert), None

    out, _ = jax.lax.scan(
        add_expert, _mlp(tokens, p["shared"]),  # the shared expert: every token
        (first + jnp.arange(a["experts_held"]), p["w_gate"], p["w_up"], p["w_down"]),
    )
    return out.reshape(u.shape), chosen_by, top_e, scores


def _block(x, p, bias, a):
    eps = a["rms_norm_eps"]
    x = x + _mla(_rms_norm(x, p["attn_norm"]["scale"], eps), p["attn"], a)
    if "mlp" in p:
        return x + _mlp(_rms_norm(x, p["mlp_norm"]["scale"], eps), p["mlp"]), None
    out, *routing = _mixture(
        _rms_norm(x, p["moe_norm"]["scale"], eps), p["moe"], bias, a)
    return x + out, routing


def _router_bias(biases, name):
    return biases[name]["moe"]["router_bias"]


def forward(params, biases, tokens, *, architecture: dict, precision=jnp.float32):
    """``(final-normed states of the trunk and of the MTP module [B, S, d]
    each, the mixtures' routing: a (chosen_by, experts, scores) a mixture)``.
    ``biases`` is the program's ``batch_stats`` tree."""
    a = architecture
    eps = a["rms_norm_eps"]
    embedding = params["tok_embed"]["embedding"].astype(precision)
    tokens = tokens - a["share_rank"] * a["vocab_held"]
    block = jax.checkpoint(lambda x, p, b: _block(x, p, b, a))
    with jax.default_matmul_precision("highest"):
        x, routing = embedding[tokens], []
        for i in range(a["layers"]):
            name = f"Block_{i}"
            dense = i < a["first_k_dense_replace"]
            x, routed = block(
                x, params[name], None if dense else _router_bias(biases, name))
            if routed is not None:
                routing.append(routed)
        states = [_rms_norm(x, params["final_norm"]["scale"], eps)]
        if a["num_nextn_predict_layers"]:
            ahead = jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)
            z = jnp.concatenate([
                _rms_norm(embedding[ahead], params["mtp_embed_norm"]["scale"], eps),
                _rms_norm(x, params["mtp_hidden_norm"]["scale"], eps),
            ], axis=-1) @ _w(params, "mtp_proj", x)
            z, routed = block(
                z, params["mtp_block"], _router_bias(biases, "mtp_block"))
            routing.append(routed)
            states.append(_rms_norm(z, params["mtp_final_norm"]["scale"], eps))
    return states, routing


def logits(params, biases, tokens, *, architecture: dict, precision=jnp.float32):
    """The trunk's ``[B, S, vocab_held]`` logits (the CPU tests' size only)."""
    states, _ = forward(
        params, biases, tokens, architecture=architecture, precision=precision)
    with jax.default_matmul_precision("highest"):
        return states[0] @ params["head"].astype(precision)


def bias_after(bias, counts, rate):
    """DeepSeek-V3's rule (section 2.1.2): after a step, an expert that
    received fewer choices than the mean goes up by ``rate``, one that
    received more goes down."""
    counts = counts.astype(jnp.float32)
    return bias + rate * jnp.sign(counts.mean(axis=-1, keepdims=True) - counts)


def loss(params, biases, tokens, labels, *, architecture: dict,
         precision=jnp.float32):
    """``{"loss", "ce", "ce_mtp", "load_balance", "held_row_share", "counts"
    [mixtures, E], "experts" [mixtures, T, k], "chosen_by" [mixtures, T,
    E]}``: the loss and its parts on the whole batch, the share of the
    (token, slot) choices that fell on held experts (a mean over the
    mixtures), how many choices each expert of each mixture received, the
    experts chosen and the biased scores they were chosen by. The mixtures
    are the trunk's in order, then the MTP module's."""
    a = architecture
    states, routing = forward(
        params, biases, tokens, architecture=architecture, precision=precision)
    labels = labels - a["share_rank"] * a["vocab_held"]

    def nll(state, targets):
        """Per-token loss ``[B, S']`` from full rows of logits."""
        def rows(h, y):
            with jax.default_matmul_precision("highest"):
                logp = jax.nn.log_softmax(h @ params["head"].astype(precision), axis=-1)
            return -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]

        return _by_rows(
            rows, state.reshape(-1, state.shape[-1]), targets.reshape(-1)
        ).reshape(targets.shape)

    terms = {"ce": nll(states[0], labels).mean()}
    terms["loss"] = terms["ce"]
    if len(states) > 1:
        # the MTP module at t reads x_{t+2} = label[t + 1]; the last position
        # of a sequence has none
        after = nll(states[1][:, :-1], labels[:, 1:])
        terms["ce_mtp"] = after.mean()
        terms["loss"] = terms["loss"] + a["mtp_loss_weight"] * terms["ce_mtp"]
    experts, k = a["n_routed_experts"], a["num_experts_per_tok"]
    first = a["share_rank"] * a["experts_held"]
    balance, counts = [], []
    for _, top_e, scores in routing:
        counts.append(jax.nn.one_hot(top_e, experts, dtype=jnp.int32).sum(axis=(0, 1)))
        share = counts[-1].astype(scores.dtype) / (top_e.shape[0] * k)
        mean_score = (scores / (scores.sum(axis=-1, keepdims=True) + 1e-20)).mean(axis=0)
        balance.append(experts * jnp.sum(share * mean_score))
    terms["load_balance"] = jnp.mean(jnp.stack(balance))
    terms["loss"] = terms["loss"] + a["balance_loss_weight"] * terms["load_balance"]
    counts = jnp.stack(counts)
    out = {k: v.astype(jnp.float32) for k, v in terms.items()}
    out["held_row_share"] = (
        counts[:, first:first + a["experts_held"]].sum(-1) / counts.sum(-1)
    ).mean().astype(jnp.float32)
    out["counts"] = counts
    out["experts"] = jnp.stack([top_e for _, top_e, _ in routing])
    out["chosen_by"] = jnp.stack([c for c, _, _ in routing]).astype(jnp.float32)
    return out
