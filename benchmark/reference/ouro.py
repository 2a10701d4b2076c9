"""Ouro (arXiv:2510.25741, "Scaling Latent Reasoning via Looped Language
Models"; Hugging Face ``modeling_ouro.py``), plain: every formula as the paper
and the HF implementation state it, float32, matmul precision ``highest``,
dense ``[S, S]`` masked attention, full ``[B, S, V]`` logits a pass. No
kernel, no chunked head, no stacking of passes. Written from those formulas,
not from the program's module; it reads the program's parameter tree by its
names only (``attn_norm``, ``attn_post_norm``, ``mlp_norm``,
``mlp_post_norm`` are HF's ``input_layernorm``, ``input_layernorm_2``,
``post_attention_layernorm``, ``post_attention_layernorm_2``).

    RMSNorm(x; w) = x * rsqrt(mean(x^2) + eps) * w
    h(0) = Embed(tokens)
    for pass t = 1..R (R = total_ut_steps), the SAME layers every pass:
        u = h(t-1)
        for each layer:  u = u + RMSNorm(Attn(RMSNorm(u; g1)); g2)
                         u = u + RMSNorm(MLP(RMSNorm(u; g3)); g4)
        h(t) = RMSNorm(u; g_final);  logits(t) = h(t) Whead
        lam(t) = sigmoid(h(t) . w_exit + b_exit)
    Attn(n): q, k, v = n Wq, n Wk, n Wv, split into heads; rotary
        (rotate-half, base theta) on q and k;
        softmax(q k^T / sqrt(head_dim) + causal mask) v;  Wo
    MLP(n) = (silu(n Wgate) * (n Wup)) Wdown
    exit distribution: p_1 = lam_1;  p_t = lam_t prod_{j<t} (1 - lam_j);
        p_R = prod_{j<R} (1 - lam_j)
    loss = mean over tokens of [sum_t p_t nll_t - beta H(p)],
        nll_t = -log softmax(logits(t))[label],  H(p) = -sum_t p_t log p_t

Departures from ``modeling_ouro.py``, each on purpose:
* the loss. HF's ``OuroForCausalLM`` is the inference model: it turns the
  gates into this same exit distribution to stop at the pass where its
  cumulative mass passes ``early_exit_threshold``, and its ``loss`` is one
  cross-entropy on the logits it returns. The loss here is the paper's
  Stage I objective (expected task loss under the exit distribution, with an
  entropy bonus of weight ``beta`` = ``architecture["exit_entropy_weight"]``).
* no key/value cache, no early exit: every token takes all R passes.
* rotary angles are computed in float32 for every precision (HF computes
  them in float32 too and casts cos/sin to the activations' dtype).
* ``precision`` lets the benchmark show that its tolerance has teeth: with
  ``jnp.bfloat16`` every matmul input, the residual stream, the norms, the
  gate, the softmaxes and the loss are rounded to bfloat16, the nearest
  precision below what the configuration states for those parts (float32).
* memory, not mathematics: the passes are the steps of a ``lax.scan``, a
  pass (its head with it) and each of its blocks are under
  ``jax.checkpoint``, and attention walks the heads one at a time, so that
  the gradient at 4096 tokens keeps a pass's input, one block's activations
  and one head's dense ``[S, S]`` scores at a time and adds the passes'
  gradients of the shared weights up as it goes. The values are those of
  the formulas above.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import jax.scipy.special


def _rms_norm(x, weight, eps):
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + eps) * weight.astype(x.dtype)


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rotary(x, theta):
    """x: [H, B, S, D], positions 0..S-1."""
    seq, dim = x.shape[-2:]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    freqs = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb).astype(x.dtype), jnp.sin(emb).astype(x.dtype)
    return x * cos + _rotate_half(x) * sin


def _attention(n, p, a):
    batch, seq, width = n.shape
    heads = a["num_attention_heads"]

    def split(t):  # [B, S, width] -> [H, B, S, D]
        return t.reshape(batch, seq, heads, width // heads).transpose(2, 0, 1, 3)

    q = _rotary(split(n @ p["q_proj"]["kernel"].astype(n.dtype)), a["rope_theta"])
    k = _rotary(split(n @ p["k_proj"]["kernel"].astype(n.dtype)), a["rope_theta"])
    v = split(n @ p["v_proj"]["kernel"].astype(n.dtype))
    causal = jnp.tril(jnp.ones((seq, seq), bool))

    @jax.checkpoint
    def one_head(qkv):
        q, k, v = qkv
        scores = jnp.einsum("bqd,bkd->bqk", q, k) / jnp.sqrt(
            jnp.asarray(width // heads, n.dtype)
        )
        scores = jnp.where(causal, scores, jnp.finfo(scores.dtype).min)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(one_head, (q, k, v))  # the heads one after the other
    out = out.transpose(1, 2, 0, 3).reshape(batch, seq, width)
    return out @ p["o_proj"]["kernel"].astype(n.dtype)


def _mlp(n, p):
    gate = jax.nn.silu(n @ p["gate_proj"]["kernel"].astype(n.dtype))
    return (gate * (n @ p["up_proj"]["kernel"].astype(n.dtype))) @ p[
        "down_proj"]["kernel"].astype(n.dtype)


def _layer(u, p, a):
    eps = a["rms_norm_eps"]
    u = u + _rms_norm(
        _attention(_rms_norm(u, p["attn_norm"]["scale"], eps), p["attn"], a),
        p["attn_post_norm"]["scale"], eps)
    return u + _rms_norm(
        _mlp(_rms_norm(u, p["mlp_norm"]["scale"], eps), p["mlp"]),
        p["mlp_post_norm"]["scale"], eps)


def _walk(params, tokens, a, precision, of_state):
    """The R passes: ``(gates [R, B, S], of_state(h(t)) stacked over t)``.
    One pass is one step of a ``lax.scan`` under ``jax.checkpoint`` (and
    each layer inside it again), which changes no value: the backward then
    adds the passes' gradients of the shared weights up one pass at a time
    instead of holding all R of them."""
    gate = params["exit_gate"]
    layer = jax.checkpoint(lambda u, p: _layer(u, p, a))

    def one_pass(h, _):
        for i in range(a["layers"]):
            h = layer(h, params[f"Block_{i}"])
        h = _rms_norm(h, params["final_norm"]["scale"], a["rms_norm_eps"])
        lam = jax.nn.sigmoid(
            (h @ gate["kernel"].astype(precision))[..., 0]
            + gate["bias"].astype(precision)[0])
        return h, (lam, of_state(h))

    with jax.default_matmul_precision("highest"):
        h = params["tok_embed"]["embedding"][tokens].astype(precision)
        _, out = jax.lax.scan(
            jax.checkpoint(one_pass), h, None, length=a["total_ut_steps"])
    return out


def logits(params, tokens, *, architecture: dict, precision=jnp.float32):
    """``(gates [R, B, S], logits [R, B, S, V])`` of every pass (the CPU
    tests' size only)."""
    return _walk(params, tokens, architecture, precision,
                 lambda h: h @ params["head"].astype(precision))


def exit_distribution(gates):
    """``p [R, ...]`` from the R gates: a token exits after pass t with the
    gate's probability there, if it has not exited before; the last pass
    takes what is left."""
    remaining, p = jnp.ones_like(gates[0]), []
    for lam in gates[:-1]:
        p.append(lam * remaining)
        remaining = remaining * (1 - lam)
    return jnp.stack(p + [remaining])


def loss(params, tokens, labels, *, architecture: dict, precision=jnp.float32):
    """``{"loss", "ce", "ce_pass" [R], "exit_entropy", "exit_step_mean",
    "gates" [R, B, S]}``: the Stage I loss and its parts, each a mean over
    tokens."""

    def pass_nll(h):
        logp = jax.nn.log_softmax(h @ params["head"].astype(precision), axis=-1)
        return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]

    gates, nll = _walk(params, tokens, architecture, precision, pass_nll)
    p = exit_distribution(gates)
    ce = (p * nll).sum(0).mean()
    # -p log p with 0 log 0 = 0: a gate that saturated leaves p exactly 0
    entropy = jax.scipy.special.entr(p).sum(0).mean()
    steps = jnp.arange(1, len(gates) + 1, dtype=p.dtype)[:, None, None]
    out = {
        "loss": ce - architecture["exit_entropy_weight"] * entropy,
        "ce": ce,
        "ce_pass": nll.mean(axis=(1, 2)),
        "exit_entropy": entropy,
        "exit_step_mean": (p * steps).sum(0).mean(),
    }
    out = {k: v.astype(jnp.float32) for k, v in out.items()}
    out["gates"] = gates.astype(jnp.float32)
    return out
