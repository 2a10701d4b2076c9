"""SDAR-30B-A3B-Chat (``JetLM/SDAR-30B-A3B-Chat``, HF ``model_type``
``sdar_moe``) trained by block diffusion, plain: every formula as
``config.json``, the published Qwen3-MoE block and BD3-LM's training pass
(arXiv:2503.09573 section 3; SDAR, arXiv:2510.06303) state it, float32,
matmul precision ``highest``, attention as a masked softmax on whole rows of
scores over REPEATED key/value heads with the mask written as a dense
``[2S, 2S]`` boolean from the four rules below, a loop over the held
experts, each on every row, with a mask, the loss from full rows of logits.
No kernel, no sort, no grouped head, no skipped tile, no recomputation
policy. It is computed in blocks so that 2 x 8192 rows fit the chip beside
the program's own state: ``QUERY_BLOCK`` query rows against every key (the
mask's rows for them made in the block), and ``ROW_BLOCK`` rows at a time
through the head, each block computed again in the backward
(``jax.checkpoint``), which changes no value. Written from those formulas,
not from the program's modules; it reads the program's parameter tree by
its names only, and draws the noise ITSELF from the key it is given.

    RMSNorm(x; w) = x * rsqrt(mean(x^2) + eps) * w
    the noise, from ``noise_key`` (the step's key as the model's stream
    ``diffusion`` yields it at the root module; ``jax.random.key(0)`` in
    evaluation): k_level, k_mask = split(noise_key);
        t_b = 1 - (1 - eps_t) U_b,  U = uniform(k_level, [B, S / block])
        u_i = uniform(k_mask, [B, S]);  masked_i = u_i < t_b(i),  b(i) = i // block
    x_t,i = mask_id if masked_i else x_0,i
    rows = [x_t ; x_0] (2S of them), row i of either half at position i
    h = x + Attn(N1(x))      y = h + MoE(N2(h))
    Attn(u): q = u W_q (H heads of D), k = u W_k, v = u W_v (G heads of D)
        q, k <- RMSNorm over each head's D dims (one scale of D each), then
        rotary (rotate-half, the whole head) at the row's position
        score_h(i, j) = q_h(i) . k_{h // (H/G)}(j) / sqrt(D), softmax over
        the keys j that row i keeps, n a noised row, c a clean row:
            n_i -> n_j  iff b(i) == b(j)
            n_i -> c_j  iff b(j) <  b(i)
            c_i -> c_j  iff b(j) <= b(i)
            c_i -> n_j  never
        out = concat_h(sum_j p v_{h // (H/G)}(j)) W_o
    MoE(u): p = softmax(u W_r) over all E;  I = the top-k of p;
        w_i = p_i / sum_{j in I} p_j
        out = sum_{i in I and held} w_i E_i(u), E_i(u) = W_down,i
        (silu(W_gate,i u) * W_up,i u)
    logits(n_i) = RMSNorm(last y, noised half) W_head (untied)
    loss = 1 / (B S) sum_i masked_i / t_b(i) * CE(logits(n_i), x_0,i)
        + alpha mean_mixtures(E sum_e f_e P_e), f_e the share of the (row,
        slot) choices expert e received, P_e the mean over the 2S rows of p_e

**The share.** As ``reference/glm_moe.py``: ``share_chips`` chips share
every layer and this is rank ``share_rank`` of them, holding ``experts_held``
consecutive experts of ``num_experts`` and ``vocab_held`` rows of embedding
and head. The router keeps all its outputs, its k a row and the
renormalisation over all k chosen; the experts that are not held add
nothing, here as in the program, and that partial sum goes on to the next
layer. The loss is over the held rows of the vocabulary.

Departures from the published description, each on purpose:
* the per-head q/k norms before rotary and that labels are not shifted are
  the published ``sdar_moe`` / Qwen3-MoE model class's, written from memory
  of it; the block length, the schedule (linear in t, clipped at
  ``noise_eps``), one level a block a sequence, ``mask_id`` and the
  balancing weight are the configuration file's ``assumed``.
* the balancing term (``ops/moe.balance_stats``' form, over the batch's 2S
  rows) is this repo's.
* rotary's angles are float32 for every precision.
* ``precision`` lets the benchmark show that its tolerances have teeth: with
  ``jnp.bfloat16`` every matmul input, the router, the norms, the softmaxes
  and the loss are rounded to bfloat16, the nearest precision below what the
  configuration states (float32 for those parts). The draws stay float32: a
  position is masked or it is not.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
ROW_BLOCK = 2048


def _by_rows(fn, *rows):
    """``fn`` on ``ROW_BLOCK`` rows at a time (a row depends on no other),
    where the blocks divide them; whole otherwise."""
    count = rows[0].shape[0]
    if count % ROW_BLOCK or count == ROW_BLOCK:
        return fn(*rows)
    blocks = tuple(r.reshape(-1, ROW_BLOCK, *r.shape[1:]) for r in rows)
    out = jax.lax.map(jax.checkpoint(lambda b: fn(*b)), blocks)
    return out.reshape(count, *out.shape[2:])


def _rms_norm(x, weight, eps):
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + eps) * weight.astype(x.dtype)


def _rotary(x, positions, theta):
    """x: [..., R, D] at ``positions`` [R], rotate-half."""
    dim = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    freqs = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb).astype(x.dtype), jnp.sin(emb).astype(x.dtype)
    half = dim // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def noise(noise_key, batch: int, seq: int, a: dict):
    """``(masked [B, S] bool, level [B, S] float32)``: the module docstring's
    rule, drawn here."""
    block = a["block_length"]
    k_level, k_mask = jax.random.split(noise_key)
    level = 1.0 - (1.0 - a["noise_eps"]) * jax.random.uniform(
        k_level, (batch, seq // block), jnp.float32)
    level = jnp.repeat(level, block, axis=1)
    return jax.random.uniform(k_mask, (batch, seq), jnp.float32) < level, level


def kept(query_rows, seq: int, block: int):
    """The mask's rows for ``query_rows`` (indices into the 2S rows) against
    all 2S keys, ``[rows, 2S]`` bool, from the four rules."""
    keys = jnp.arange(2 * seq)
    q_clean, k_clean = (query_rows >= seq)[:, None], (keys >= seq)[None, :]
    qb, kb = (query_rows % seq // block)[:, None], (keys % seq // block)[None, :]
    return jnp.where(
        q_clean, k_clean & (kb <= qb), jnp.where(k_clean, kb < qb, kb == qb))


def _w(p, name, like):
    return p[name]["kernel"].astype(like.dtype)


def _attention(u, p, a):
    batch, rows, _ = u.shape
    seq = rows // 2
    heads, kv_heads, dim = (
        a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"])
    eps = a["rms_norm_eps"]
    positions = jnp.tile(jnp.arange(seq), 2)  # position i twice

    def split(name, n):  # [B, n, 2S, D]
        return (u @ _w(p, f"{name}_proj", u)).reshape(
            batch, rows, n, dim).transpose(0, 2, 1, 3)

    q = _rotary(_rms_norm(split("q", heads), p["q_norm"]["scale"], eps),
                positions, a["rope_theta"])
    k = _rotary(_rms_norm(split("k", kv_heads), p["k_norm"]["scale"], eps),
                positions, a["rope_theta"])
    # query head h reads key/value head h // group: the heads repeated
    k, v = (jnp.repeat(t, heads // kv_heads, axis=1) for t in (k, split("v", kv_heads)))
    scale = 1.0 / jnp.sqrt(jnp.asarray(dim, u.dtype))
    block = QUERY_BLOCK if rows % QUERY_BLOCK == 0 else rows

    @jax.checkpoint
    def some_rows(at):
        """One sequence's block of query rows against every key of it."""
        b, start = at
        qb = jax.lax.dynamic_slice_in_dim(q[b], start, block, axis=1)
        scores = jnp.einsum("hqd,hkd->hqk", qb, k[b]) * scale
        keep = kept(start + jnp.arange(block), seq, a["block_length"])
        scores = jnp.where(keep, scores, jnp.finfo(scores.dtype).min)
        return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, axis=-1), v[b])

    starts = jnp.arange(0, rows, block)
    at = (jnp.repeat(jnp.arange(batch), starts.size), jnp.tile(starts, batch))
    out = jax.lax.map(some_rows, at)  # [B * n, H, block, D]
    out = out.reshape(batch, starts.size, heads, block, dim)
    out = out.transpose(0, 1, 3, 2, 4).reshape(batch, rows, heads * dim)
    return out @ _w(p, "o_proj", u)


def _gated(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _mixture(u, p, _bias, a, held=None):
    """(out, the router's probabilities [T, E], the experts chosen [T, k]).
    ``held`` = (first, count) overrides the architecture's share (the tests'
    share test); ``_bias`` is not read: this router has none (the argument
    keeps the place it has in the other shares' references)."""
    batch, rows, width = u.shape
    tokens = u.reshape(batch * rows, width)
    probs = jax.nn.softmax(tokens @ p["router"].astype(u.dtype), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, a["num_experts_per_tok"])
    weights = top_p / top_p.sum(axis=-1, keepdims=True)
    first, count = held or (a["share_rank"] * a["experts_held"], a["experts_held"])

    @jax.checkpoint
    def weighted(e, w_gate, w_up, w_down):
        """One held expert on every row; its weight is 0 where not chosen."""
        out = _gated(tokens, *(w.astype(u.dtype) for w in (w_gate, w_up, w_down)))
        return out * jnp.where(top_e == e, weights, 0.0).sum(axis=-1)[:, None]

    def add_expert(out, expert):
        return out + weighted(*expert), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(tokens),
        (first + jnp.arange(count), p["w_gate"], p["w_up"], p["w_down"]),
    )
    return out.reshape(u.shape), probs, top_e


def _block(x, p, a):
    eps = a["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, p["input_norm"]["scale"], eps), p["attn"], a)
    out, *routing = _mixture(
        _rms_norm(x, p["post_attention_norm"]["scale"], eps), p["moe"], None, a)
    return x + out, routing


def forward(params, tokens, *, architecture: dict, noise_key,
            precision=jnp.float32):
    """``(the noised half's final-normed state [B, S, d], the mixtures'
    routing: a (probs, experts) a mixture, masked [B, S], level [B, S])``."""
    a = architecture
    batch, seq = tokens.shape
    masked, level = noise(noise_key, batch, seq, a)
    rows = jnp.concatenate([jnp.where(masked, a["mask_id"], tokens), tokens], axis=1)
    embedding = params["tok_embed"]["embedding"].astype(precision)
    with jax.default_matmul_precision("highest"):
        x, routing = embedding[rows - a["share_rank"] * a["vocab_held"]], []
        for i in range(a["layers"]):
            x, routed = jax.checkpoint(lambda x, p: _block(x, p, a))(
                x, params[f"Block_{i}"])
            routing.append(routed)
        state = _rms_norm(
            x[:, :seq], params["final_norm"]["scale"], a["rms_norm_eps"])
    return state, routing, masked, level


def logits(params, tokens, *, architecture: dict, noise_key,
           precision=jnp.float32):
    """The noised half's ``[B, S, vocab_held]`` logits (the CPU tests' size
    only)."""
    state, *_ = forward(
        params, tokens, architecture=architecture, noise_key=noise_key,
        precision=precision)
    with jax.default_matmul_precision("highest"):
        return state @ params["head"].astype(precision)


def loss(params, tokens, labels=None, *, architecture: dict, noise_key,
         precision=jnp.float32):
    """``{"loss", "ce", "load_balance", "held_row_share", "masked_share",
    "counts" [mixtures, E], "experts" [mixtures, T, k], "chosen_by"
    [mixtures, T, E], "masked" [B, S], "level" [B, S]}``: the loss and its
    parts on the whole batch, the share of the (row, slot) choices that fell
    on held experts (a mean over the mixtures), the share of the positions
    masked, how many choices each expert of each mixture received, the
    experts chosen and the probabilities they were chosen by, and the draws.
    ``labels`` is not read: a position's label is its own token."""
    del labels
    a = architecture
    state, routing, masked, level = forward(
        params, tokens, architecture=architecture, noise_key=noise_key,
        precision=precision)
    own = tokens - a["share_rank"] * a["vocab_held"]
    head = params["head"].astype(precision)

    def rows(h, y):
        """Per-row loss from full rows of logits."""
        with jax.default_matmul_precision("highest"):
            logp = jax.nn.log_softmax(h @ head, axis=-1)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]

    nll = _by_rows(rows, state.reshape(-1, state.shape[-1]), own.reshape(-1))
    weight = (masked / level).reshape(-1).astype(nll.dtype)
    terms = {"ce": (weight * nll).sum() / nll.size}
    experts, k = a["num_experts"], a["num_experts_per_tok"]
    first = a["share_rank"] * a["experts_held"]
    balance, counts = [], []
    for probs, top_e in routing:
        counts.append(jax.nn.one_hot(top_e, experts, dtype=jnp.int32).sum(axis=(0, 1)))
        share = counts[-1].astype(probs.dtype) / (top_e.shape[0] * k)
        balance.append(experts * jnp.sum(share * probs.mean(axis=0)))
    terms["load_balance"] = jnp.mean(jnp.stack(balance))
    terms["loss"] = terms["ce"] + a["balance_loss_weight"] * terms["load_balance"]
    counts = jnp.stack(counts)
    out = {k: v.astype(jnp.float32) for k, v in terms.items()}
    out["held_row_share"] = (
        counts[:, first:first + a["experts_held"]].sum(-1) / counts.sum(-1)
    ).mean().astype(jnp.float32)
    out["masked_share"] = masked.mean(dtype=jnp.float32)
    out["counts"] = counts
    out["experts"] = jnp.stack([top_e for _, top_e in routing])
    out["chosen_by"] = jnp.stack([p for p, _ in routing]).astype(jnp.float32)
    out["masked"], out["level"] = masked, level
    return out
