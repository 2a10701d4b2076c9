"""GLM-4.7-Flash (``zai-org/GLM-4.7-Flash``; HF ``model_type``
``glm4_moe_lite``): DeepSeek-V3's block (arXiv:2412.19437), as ONE chip of
an expert-parallel group holds it.

    h = x + MLA(RMSNorm(x))          y = h + F(RMSNorm(h))

``F`` is a dense gated-SiLU MLP in the first ``dense_layers`` blocks and
the mixture in every later one.

* ``MLA`` (latent attention): queries through a ``q_lora_rank`` latent,
  keys and values through a ``kv_lora_rank`` latent, each latent RMS-normed;
  a ``qk_rope_head_dim``-wide rotary part of the key is ONE vector a token
  that every head shares, beside each head's ``qk_nope_head_dim`` unrotated
  dims. Training runs the expanded form: H heads of score dim ``nope +
  rope`` and value dim ``v_head_dim`` (equal here: q, k and v keep one shape
  through ``ops/flash_attention.py``), scaled by ``(nope + rope) ** -0.5``.
  ``RMSNorm``, ``rotary`` (rotate-half, over all the rope dims) and the
  attention entry are ``models/olmoe.py``'s.
* the mixture: ``s = sigmoid(u W_r)`` in float32; the ``top_k`` experts by
  ``s + b``; weights ``scale * s_i / sum_chosen s`` from the UNBIASED scores;
  ``out = sum_i g_i E_i(u) + E_shared(u)``, every expert ``W_down(silu(W_gate
  u) * W_up u)``. ``b`` is STATE (collection ``batch_stats``, where the step
  carries what no gradient moves): after a training step's routing ``b_e +=
  bias_rate * sign(mean(c) - c_e)`` with ``c`` that step's choices an
  expert. It takes no gradient and no optimizer ever sees it.
* the chip's share: ``share_chips`` chips share each layer and this is
  rank ``share_rank`` of them. It holds ``E / share_chips`` routed experts
  (``ops/moe.sorted_experts``' ``held``) and ``V / share_chips`` rows of the
  embedding and of the head; attention, the dense MLP and the shared expert
  are whole (they are replicated within the group). The router keeps all E
  outputs and its ``top_k`` a token; what the absent experts would add is
  left out, and that partial sum goes on to the next layer. Nothing stands
  in for the other chips or their traffic. Token ids must lie in the held
  rows ``[rank V/n, (rank + 1) V/n)``. ``share_chips = 1`` is the whole
  model.
* multi-token prediction (depth 1, the paper's section 2.2): ``z_t = W_eh
  [RMSNorm(Emb(x_{t+1})) ; RMSNorm(h_t)]`` with ``h`` the last block's
  output before the final norm, one more mixture block on ``z``, its own
  final norm, THE SAME embedding and head; ``loss = CE(x_{t+1}) + mtp_weight
  * CE_mtp(x_{t+2})``. The batch is ``image`` = tokens, ``label`` = next
  tokens: ``x_{t+1}`` is ``image`` one to the left (the last position, whose
  next token ``image`` does not hold, takes its own token: its target lies
  past the batch, its weight is 0 and no position attends to it), the MTP
  target at t is ``label[t + 1]``.
* parameters and the residual stream are float32, the matmuls read
  ``dtype``; norms, router, softmaxes and loss are float32.
* every block is recomputed in the backward from its float32 input, the
  attention branch's output and the flash kernel's output, log-sum-exp, q, k
  and v (``recompute``; ``models/ouro.recomputed``), as ``models/ouro.py``'s:
  six blocks' activations at 8192 tokens do not fit beside 11.3 GB of
  parameters and AdamW state.

As ``models/ouro.py`` the head is left to the step: ``hidden_only=True``
returns ``(states [B, 2, S, d], statistics)`` (the trunk's and the MTP
module's final-normed states; the mixtures' balancing term and load) and
the step's loss is :meth:`GLMMoE.head_loss`: ONE ``weighted_loss`` walk over
the 2 x B stacked rows. A plain call returns the trunk's ``[B, S, V/n]``
logits.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distribuuuu_tpu.models.layers import head_dtype
from distribuuuu_tpu.models.olmoe import RMSNorm, _attend, _normal, rotary
from distribuuuu_tpu.models.ouro import MLP, branch_out, kept_plan, recomputed
from distribuuuu_tpu.models.share import (
    ShareOfALayer,
    mixture_metrics,
    share_kwargs_from_cfg,
)
from distribuuuu_tpu.models.traits import ArchTraits
from distribuuuu_tpu.models.vit import Attention as VitAttention
from distribuuuu_tpu.ops import token_head


def _dense(width: int, dtype, name: str):
    return nn.Dense(
        width, use_bias=False, dtype=dtype, param_dtype=jnp.float32,
        kernel_init=_normal(), name=name,
    )


class MLA(nn.Module):
    """Multi-head latent attention, expanded (training) form."""

    dim: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    eps: float
    rope_theta: float
    dtype: Any
    attn_impl: str = "auto"
    mesh: Any = None

    @nn.compact
    def __call__(self, x, positions):
        B, S, _ = x.shape
        H, nope, rope, dv = (self.num_heads, self.qk_nope_head_dim,
                             self.qk_rope_head_dim, self.v_head_dim)
        if nope + rope != dv:
            raise ValueError(
                f"score dim {nope} + {rope} and value dim {dv} differ: the "
                "attention entry takes q, k and v of one shape"
            )

        def heads(t):  # [B, S, H * w] -> [B, H, S, w]
            return t.reshape(B, S, H, -1).transpose(0, 2, 1, 3)

        with jax.named_scope("mla_latent"):
            x = x.astype(self.dtype)
            c_q = RMSNorm(self.eps, name="q_a_norm")(
                _dense(self.q_lora_rank, self.dtype, "q_a_proj")(x))
            q = heads(_dense(H * (nope + rope), self.dtype, "q_b_proj")(
                c_q.astype(self.dtype)))
            kv = _dense(self.kv_lora_rank + rope, self.dtype, "kv_a_proj")(x)
            c_kv = RMSNorm(self.eps, name="kv_a_norm")(kv[..., :self.kv_lora_rank])
            k_rope = kv[..., None, self.kv_lora_rank:].transpose(0, 2, 1, 3)
            kv = heads(_dense(H * (nope + dv), self.dtype, "kv_b_proj")(
                c_kv.astype(self.dtype)))
            # ONE rotary key a token, the same for every head
            k_rope = rotary(k_rope, positions, self.rope_theta).astype(self.dtype)
            q = jnp.concatenate([
                q[..., :nope],
                rotary(q[..., nope:], positions, self.rope_theta).astype(self.dtype),
            ], axis=-1)
            k = jnp.concatenate([
                kv[..., :nope], jnp.broadcast_to(k_rope, (B, H, S, rope)),
            ], axis=-1)
            v = kv[..., nope:]
        impl = VitAttention.resolve_impl(self.attn_impl, S, 0.0)
        out = _attend(q, k, v, impl, self.dtype, self.mesh)
        out = out.astype(self.dtype).transpose(0, 2, 1, 3).reshape(B, S, H * dv)
        return _dense(self.dim, self.dtype, "o_proj")(out)


class Mixture(nn.Module):
    """A router, this chip's share of the routed experts, the shared expert
    (none where ``shared_experts`` is 0: ``models/lfm2_moe.py``). The router is
    ``route`` where the model hands one over (``(tokens [T, d], router [d, E],
    top_k) -> (probs, weights, indices)`` in float32, as
    ``ops/moe.moe_ffn_sorted`` takes it: ``models/sdar_moe.py``'s softmax
    renormalised over its choices), and carries no state; ``None`` is the
    sigmoid router with a balancing bias (``scale``, ``bias_rate``,
    ``norm_eps``), whose bias is the ``batch_stats`` variable ``router_bias``
    that a rule moves in training. Returns ``(out, statistics)``: ``aux`` (the
    balancing term, ``ops/moe.balance_stats``' form on the router's
    probabilities over all experts), ``load_max_over_mean`` (all E),
    ``held_row_share`` (the share of the (token, slot) choices that fell on
    held experts) and, where there is a bias, ``bias_abs_max``; sows
    ``moe_route/experts`` as ``models/olmoe.py``."""

    dim: int
    hidden: int
    num_experts: int
    top_k: int
    shared_experts: int
    scale: float
    bias_rate: float
    held: tuple  # (first, count) of the routed experts this chip holds
    dtype: Any
    train: bool = False
    mesh: Any = None
    # added to the chosen scores' sum where the weights are normalised
    # (ops/moe.top_k_biased): 1e-20 is GLM's, models/lfm2_moe.py gives 1e-6
    norm_eps: float = 1e-20
    route: Any = None  # the router as a function; None: sigmoid with a bias

    @nn.compact
    def __call__(self, x):
        from distribuuuu_tpu.ops import moe as moe_ops

        E, d, f = self.num_experts, self.dim, self.hidden
        first, count = self.held

        def expert(name, shape):
            return self.param(name, _normal(), shape, jnp.float32)

        params = {
            "router": self.param("router", _normal(), (d, E), jnp.float32),
            "w_gate": expert("w_gate", (count, d, f)),
            "w_up": expert("w_up", (count, d, f)),
            "w_down": expert("w_down", (count, f, d)),
        }
        route, bias = self.route, None
        if route is None:
            bias = self.variable(
                "batch_stats", "router_bias", lambda: jnp.zeros((E,), jnp.float32))
            route = functools.partial(
                moe_ops.sigmoid_route, bias=bias.value, scale=self.scale,
                eps=self.norm_eps)
        # the router reads the norm's float32 result, the experts its
        # rounding to the compute dtype
        out, verdict = moe_ops.moe_ffn_sorted(
            params, x.astype(self.dtype), top_k=self.top_k, mesh=self.mesh,
            router_x=x, held=(first, E), route=route,
        )
        if self.shared_experts:
            with jax.named_scope("moe_shared"):
                out = out + MLP(
                    d, f * self.shared_experts, self.dtype, name="shared")(x)
        with jax.named_scope("moe_route"):
            counts = verdict["counts"]
            # read only by a caller that makes the collection mutable (the
            # benchmark's comparison with its reference)
            self.sow("moe_route", "experts", verdict["indices"])
            if bias is not None and self.train and not self.is_initializing():
                bias.value = moe_ops.bias_after(bias.value, counts, self.bias_rate)
            share = counts.astype(jnp.float32) / counts.sum()
            stats = {
                "aux": moe_ops.aux_from_balance_stats(
                    share, verdict["probs"].mean(axis=0)),
                "load_max_over_mean": moe_ops.load_max_over_mean(counts),
                "held_row_share": share[first:first + count].sum(),
            }
            if bias is not None:
                stats["bias_abs_max"] = jnp.abs(bias.value).max()
        return out, stats


class Block(nn.Module):
    """One pre-norm block. ``mixture`` None: the dense MLP of width
    ``mlp_hidden``; else the :class:`Mixture` it builds. Each branch's output
    is named (``models/ouro.branch_out``): recomputed, the block keeps the
    attention's, which the sum the second norm reads is made of, and not the
    FFN's, which nothing in the backward reads."""

    attention: Any  # () -> MLA
    mixture: Any  # () -> Mixture, or None
    mlp_hidden: int
    dim: int
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x, positions):
        with jax.named_scope("attn"):
            x = x + branch_out(self.attention(name="attn")(
                RMSNorm(self.eps, name="attn_norm")(x), positions))
        if self.mixture is None:
            with jax.named_scope("mlp"):
                x = x + branch_out(
                    MLP(self.dim, self.mlp_hidden, self.dtype, name="mlp")(
                        RMSNorm(self.eps, name="mlp_norm")(x)))
            return x, {}
        with jax.named_scope("moe"):
            out, stats = self.mixture(name="moe")(RMSNorm(self.eps, name="moe_norm")(x))
        return x + branch_out(out), stats


_planned: set = set()


def _say_plan(model, batch: int, seq: int) -> None:
    """One ``share.plan`` record a shape, at trace time, beside
    ``kernel.select`` and ``models/ouro.py``'s ``loop.plan``: what of each
    layer this chip holds and what its step computes again."""
    key = (model.share_chips, model.share_rank, model.num_experts,
           model.vocab_size, model.depth, batch, seq, model.recompute)
    if key in _planned:
        return
    _planned.add(key)
    from distribuuuu_tpu.telemetry import spans

    spans.emit_event(
        "share.plan", share_chips=model.share_chips, share_rank=model.share_rank,
        experts_held=model.held[1], experts_total=model.num_experts,
        vocab_held=model.vocab_held, vocab_total=model.vocab_size,
        **kept_plan(
            model, model.depth + model.mtp_layers, batch, seq, model.v_head_dim,
            "every block, the MTP module's too",
            branches=model.depth + model.mtp_layers),  # the attention's
    )


class GLMMoE(ShareOfALayer):
    """Defaults are ``config.json``'s of zai-org/GLM-4.7-Flash."""

    vocab_size: int = 154880  # published; this chip holds vocab_size / share_chips rows
    seq_len: int = 8192  # the training context here; config.json allows 202,752 positions
    dim: int = 2048
    depth: int = 47  # num_hidden_layers, the first ``dense_layers`` of them dense
    dense_layers: int = 1  # first_k_dense_replace
    num_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    mlp_hidden: int = 10240  # intermediate_size, the dense layers'
    expert_hidden: int = 1536  # moe_intermediate_size
    num_experts: int = 64  # n_routed_experts
    top_k: int = 4  # num_experts_per_tok
    shared_experts: int = 1  # n_shared_experts
    routed_scale: float = 1.8  # routed_scaling_factor, with norm_topk_prob
    mtp_layers: int = 1  # num_nextn_predict_layers
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e6
    bias_rate: float = 0.001  # DeepSeek-V3's rule at GLM-4.5's stated rate
    mtp_weight: float = 0.3  # DeepSeek-V3's lambda for most of its training
    aux_weight: float = 1e-4  # MODEL.MOE.AUX_WEIGHT
    share_chips: int = 1  # LM.SHARE_CHIPS: chips that share each layer
    share_rank: int = 0  # LM.SHARE_RANK: which of them this is
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    mesh: Any = None
    # a block keeps its float32 input, its attention's output and what the
    # flash backward kernel reads (the forward's output, log-sum-exp, q, k and
    # v), nothing else
    recompute: bool = True
    # positions of every row the head takes at a time; its rows are the
    # batch's sequences twice over (the trunk's and the MTP module's)
    head_chunk: int = 512

    def _check_share(self) -> None:
        super()._check_share()
        if self.mtp_layers not in (0, 1):
            raise ValueError(f"mtp_layers={self.mtp_layers}: 0 or 1")

    @nn.compact
    def __call__(self, tokens, train: bool = False, hidden_only: bool = False):
        B, S = tokens.shape
        self._check_input(tokens)
        _say_plan(self, B, S)
        embed = self._embedding()
        tokens = tokens - self.share_rank * self.vocab_held
        positions = jnp.arange(S, dtype=jnp.int32)
        attention = functools.partial(
            MLA, self.dim, self.num_heads, self.q_lora_rank, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
            self.rms_norm_eps, self.rope_theta, self.dtype, self.attn_impl,
            self.mesh,
        )
        mixture = functools.partial(
            Mixture, self.dim, self.expert_hidden, self.num_experts, self.top_k,
            self.shared_experts, self.routed_scale, self.bias_rate, self.held,
            self.dtype, train, self.mesh,
        )
        block = recomputed(Block) if self.recompute else Block

        def make(name, dense):
            return block(
                attention, None if dense else mixture, self.mlp_hidden,
                self.dim, self.rms_norm_eps, self.dtype, name=name,
            )

        x, stats = embed(tokens), []
        for i in range(self.depth):
            x, s = make(f"Block_{i}", i < self.dense_layers)(x, positions)
            stats.append(s)
        states = [RMSNorm(self.rms_norm_eps, name="final_norm")(x)]
        if self.mtp_layers:
            with jax.named_scope("mtp"):
                def norm(name, t):
                    return RMSNorm(self.rms_norm_eps, name=name)(t).astype(self.dtype)

                # x_{t+1} is the input one to the left (module docstring)
                ahead = jnp.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)
                z = _dense(self.dim, self.dtype, "mtp_proj")(jnp.concatenate(
                    [norm("mtp_embed_norm", embed(ahead)),
                     norm("mtp_hidden_norm", x)], axis=-1))
                z, s = make("mtp_block", False)(z.astype(x.dtype), positions)
                stats.append(s)
                states.append(RMSNorm(self.rms_norm_eps, name="mtp_final_norm")(z))
        kernel = self.param(
            "head", _normal(), (self.dim, self.vocab_held), jnp.float32
        )
        if hidden_only:
            mixtures = [s for s in stats if s]
            return (jnp.stack([s.astype(self.dtype) for s in states], axis=1),
                    {k: jnp.stack([s[k] for s in mixtures]) for k in mixtures[0]})
        return jnp.einsum(
            "bsd,dv->bsv", states[0].astype(self.dtype), kernel.astype(self.dtype),
            preferred_element_type=head_dtype(self.dtype),
        )

    # ------------------------------------------------ partition-layer hooks
    @staticmethod
    def head_kernel(params):
        return params["head"]

    @staticmethod
    def eval_hidden(outputs):
        """What evaluation's head reads: the trunk's state."""
        return outputs[0][:, 0]

    def head_loss(self, outputs, kernel, labels, *, topk):
        """``(loss, hits, step metrics)``: next-token cross-entropy, the MTP
        module's on the token after it, the mixtures' balancing term.

        The trunk's and the MTP module's states go through the head as 2 x B
        rows of ONE ``weighted_loss`` walk (one ``[d, V]`` gradient buffer):
        weights ``1 / N`` a trunk row's token, ``mtp_weight / (N - B)`` an
        MTP row's, 0 at its last position, whose target lies past the
        batch."""
        states, stats = outputs
        B, R, S, d = states.shape
        n = labels.size
        labels = self.head_labels(labels)
        rows = [labels]
        weights = [jnp.full((B, S), 1.0 / n, jnp.float32)]
        if R > 1:
            rows.append(jnp.concatenate([labels[:, 1:], labels[:, -1:]], axis=1))
            counted = (jnp.arange(S) < S - 1).astype(jnp.float32)
            mtp_mean = jnp.broadcast_to(counted / (n - B), (B, S))
            weights.append(self.mtp_weight * mtp_mean)
        with jax.named_scope("lm_head"):
            heads, (nll, rank) = token_head.weighted_loss(
                states.reshape(B * R, S, d), kernel,
                jnp.stack(rows, axis=1).reshape(B * R, S),
                jnp.stack(weights, axis=1).reshape(B * R, S),
                chunk=self.head_chunk,
            )
            nll = nll.reshape(B, R, S)
            extra = {"ce": nll[:, 0].mean()}
            if R > 1:
                extra["ce_mtp"] = (nll[:, 1] * mtp_mean).sum()
        extra.update(mixture_metrics(stats))
        first = rank.reshape(B, R, S)[:, 0]
        hits = [(first < k).mean(dtype=jnp.float32) * 100.0 for k in topk]
        return heads + self.aux_weight * extra["moe_aux"], hits, extra


def glm_4_7_flash(num_classes=154880, **kw):
    """GLM-4.7-Flash at its published sizes (30B-A3B at depth 47 over 64
    experts; ``depth`` and the chips that share a layer are what one chip
    turns)."""
    return GLMMoE(vocab_size=num_classes, **kw)


def glm_moe_tiny(num_classes=512, **kw):
    """The same blocks at a size the CPU tests run: 64 wide, 4 heads of
    score dim 24 + 8 and value dim 32 through latents of 24 and 16, a dense
    MLP of 160, then 8 experts of 32 with 2 a token and a shared one, 1 + 2
    layers and the MTP module; two chips share a layer (4 experts and 256
    vocabulary rows held); the head in chunks of 48, which do not divide its
    128 positions."""
    for key, value in dict(
        seq_len=128, dim=64, depth=3, num_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=24, qk_rope_head_dim=8,
        v_head_dim=32, mlp_hidden=160, expert_hidden=32, num_experts=8,
        top_k=2, head_chunk=48, share_chips=2,
    ).items():
        kw.setdefault(key, value)
    return GLMMoE(vocab_size=num_classes, **kw)


glm_4_7_flash.traits = glm_moe_tiny.traits = ArchTraits(
    token_batch=True, batch_norm=False,
    # attention and the sorted experts per device, as models/olmoe.py; the
    # exchange of tokens across the chips that share a layer is ROADMAP R2
    mesh_axes=("data",),
    kwargs_from_cfg=share_kwargs_from_cfg,
    serve_refusal=(
        "trains only: serving latent attention takes a cache of the latents "
        "and the absorbed decode formulation (ROADMAP R3), which "
        "lm/generate.py lacks"
    ),
)
