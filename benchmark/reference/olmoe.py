"""OLMoE (arXiv:2409.02060; Hugging Face ``modeling_olmoe.py``), plain: every
formula as the paper and the HF implementation state it, float32, matmul
precision ``highest``, dense ``[S, S]`` masked attention, a loop over all
experts, each on every token, with a top-k mask, full ``[B, S, V]`` logits.
No kernel, no chunking, no sorting. Written from those formulas, not from the program's
module; it reads the program's parameter tree by its names only.

    RMSNorm(x; w) = x * rsqrt(mean(x^2) + eps) * w
    h = x + Attn(RMSNorm(x; attn_norm))     y = h + MoE(RMSNorm(h; moe_norm))
    Attn(u): q, k, v = u Wq, u Wk, u Wv;  q, k <- RMSNorm over the whole
        model width, then split into heads; rotary (rotate-half, base theta)
        on q and k;  softmax(q k^T / sqrt(head_dim) + causal mask) v;  Wo
    MoE(u): p = softmax(u Wr); the top_k largest p, used AS THEY ARE;
        out = sum_k p_k * (silu(u Wgate_k) * (u Wup_k)) Wdown_k
    logits = RMSNorm(y_last; final_norm) Whead

Loss terms (``loss``): mean next-token cross-entropy; the load-balancing
loss; the router z-loss ``mean(logsumexp(u Wr)^2)``, each averaged over the
layers.

Departures from HF, each on purpose:
* the load-balancing loss is ``E * sum_e f_e * P_e`` with ``f_e`` the share
  of (token, slot) assignments expert e received (sum f = 1) and ``P_e`` the
  mean router probability, per layer, averaged over layers. HF's
  ``load_balancing_loss_func`` concatenates the layers' tokens and does not
  divide ``f`` by ``top_k``, so its value is ``top_k`` times this one at one
  layer (minimum ``top_k``, here 1.0). The program uses this form
  (``ops/moe.balance_stats``) and the weight 0.01 is the paper's.
* HF has no z-loss in ``modeling_olmoe.py``; the paper trains with it
  (weight 0.001, section 3.2 / ST-MoE eq. 5).
* rotary angles are computed in float32 for every precision (HF computes
  them in float32 too and casts cos/sin to the activations' dtype).
* ``precision`` lets the benchmark show that its tolerance has teeth: with
  ``jnp.bfloat16`` every matmul input, the router, the norms, the softmaxes
  and the loss are rounded to bfloat16, the nearest precision below what
  the configuration states (float32 for those parts).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms_norm(x, weight, eps):
    variance = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(variance + eps) * weight.astype(x.dtype)


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rotary(x, theta):
    """x: [B, H, S, D], positions 0..S-1."""
    _, _, seq, dim = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    freqs = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    cos, sin = jnp.cos(emb).astype(x.dtype), jnp.sin(emb).astype(x.dtype)
    return x * cos + _rotate_half(x) * sin


def _attention(u, p, a):
    batch, seq, width = u.shape
    heads = a["num_attention_heads"]

    def split(t):
        return t.reshape(batch, seq, heads, width // heads).transpose(0, 2, 1, 3)

    q = _rms_norm(u @ p["q_proj"]["kernel"].astype(u.dtype), p["q_norm"]["scale"],
                  a["rms_norm_eps"])
    k = _rms_norm(u @ p["k_proj"]["kernel"].astype(u.dtype), p["k_norm"]["scale"],
                  a["rms_norm_eps"])
    v = split(u @ p["v_proj"]["kernel"].astype(u.dtype))
    q, k = _rotary(split(q), a["rope_theta"]), _rotary(split(k), a["rope_theta"])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(width // heads, u.dtype)
    )
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal, scores, jnp.finfo(scores.dtype).min)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
    out = out.transpose(0, 2, 1, 3).reshape(batch, seq, width)
    return out @ p["o_proj"]["kernel"].astype(u.dtype)


def _moe(u, p, a):
    """(out, router logits [T, E], chosen experts [T, k])."""
    batch, seq, width = u.shape
    tokens = u.reshape(batch * seq, width)
    router_logits = tokens @ p["router"].astype(u.dtype)
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, a["num_experts_per_tok"])

    @jax.checkpoint
    def weighted(e, w_gate, w_up, w_down):
        """One expert on every token; its weight is 0 where not chosen."""
        gate = jax.nn.silu(tokens @ w_gate.astype(u.dtype))
        expert_out = (gate * (tokens @ w_up.astype(u.dtype))) @ w_down.astype(u.dtype)
        weight = jnp.where(top_e == e, top_p, 0.0).sum(axis=-1)
        return expert_out * weight[:, None]

    def add_expert(out, expert):
        return out + weighted(*expert), None

    # a loop over all experts, one after the other (a scan, so that 64
    # copies of the body are not compiled; under a gradient each expert's
    # activations are computed again, not kept: all 64 on every token do
    # not fit the chip beside the gradient)
    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(tokens),
        (jnp.arange(a["num_experts"]), p["w_gate"], p["w_up"], p["w_down"]),
    )
    return out.reshape(u.shape), router_logits, top_e


def forward(params, tokens, *, architecture: dict, precision=jnp.float32):
    """(logits [B, S, V], per layer router logits, per layer chosen experts)."""
    a = architecture
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed"]["embedding"][tokens].astype(precision)
        routers, chosen = [], []
        for i in range(a["layers"]):
            p = params[f"Block_{i}"]
            x = x + _attention(
                _rms_norm(x, p["attn_norm"]["scale"], a["rms_norm_eps"]), p["attn"], a
            )
            out, router_logits, top_e = _moe(
                _rms_norm(x, p["moe_norm"]["scale"], a["rms_norm_eps"]), p["moe"], a
            )
            x = x + out
            routers.append(router_logits)
            chosen.append(top_e)
        x = _rms_norm(x, params["final_norm"]["scale"], a["rms_norm_eps"])
        return x @ params["head"].astype(precision), routers, chosen


def logits(params, tokens, *, architecture: dict, precision=jnp.float32):
    return forward(params, tokens, architecture=architecture, precision=precision)[0]


def loss(params, tokens, labels, *, architecture: dict, precision=jnp.float32,
         share=None):
    """``{"ce", "load_balance", "router_z", "experts", "router_logits",
    "share", "probs"}``: the three loss terms (unweighted scalars), the chosen
    experts ``[layers, T, k]``, the router's logits ``[layers, T, E]`` they
    were chosen by, and the two factors of the balancing term, ``[layers,
    E]`` each: the share ``f`` of the assignments an expert received and its
    mean router probability ``P``.

    ``share`` replaces this call's own ``f`` in the balancing term. A caller
    that walks a batch one sequence at a time needs that: ``f`` is taken
    over the whole batch and is a constant of the gradient, ``P`` is a mean
    over tokens, so the batch's term (and its gradient) is the mean of the
    sequences' terms computed with the batch's ``f``."""
    a = architecture
    out, routers, chosen = forward(
        params, tokens, architecture=architecture, precision=precision
    )
    logp = jax.nn.log_softmax(out, axis=-1)
    ce = -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()
    balance, z, shares, mean_probs = [], [], [], []
    for layer, (router_logits, top_e) in enumerate(zip(routers, chosen)):
        probs = jax.nn.softmax(router_logits, axis=-1)
        assigned = jax.nn.one_hot(top_e, a["num_experts"], dtype=probs.dtype).sum(axis=1)
        shares.append(assigned.mean(axis=0) / a["num_experts_per_tok"])
        mean_probs.append(probs.mean(axis=0))
        f = shares[-1] if share is None else share[layer].astype(probs.dtype)
        balance.append(a["num_experts"] * jnp.sum(f * mean_probs[-1]))
        z.append(jnp.mean(jnp.square(jax.nn.logsumexp(router_logits, axis=-1))))
    return {
        "ce": ce.astype(jnp.float32),
        "load_balance": jnp.mean(jnp.stack(balance)).astype(jnp.float32),
        "router_z": jnp.mean(jnp.stack(z)).astype(jnp.float32),
        "experts": jnp.stack(chosen),
        "router_logits": jnp.stack(routers).astype(jnp.float32),
        "share": jnp.stack(shares).astype(jnp.float32),
        "probs": jnp.stack(mean_probs).astype(jnp.float32),
    }
