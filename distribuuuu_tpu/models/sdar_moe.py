"""SDAR-30B-A3B-Chat (``JetLM/SDAR-30B-A3B-Chat``; HF ``model_type``
``sdar_moe``): Qwen3-MoE's block trained by BLOCK DIFFUSION (SDAR,
arXiv:2510.06303; the objective and its vectorised training pass are
BD3-LM's, arXiv:2503.09573 section 3), as ONE chip of an expert-parallel
group holds it.

    h = x + Attn(N1(x))          y = h + MoE(N2(h))

* ``Attn``: ``num_attention_heads`` query heads on ``num_key_value_heads``
  key/value heads of ``head_dim`` (query head ``h`` reading key/value head
  ``h // group``, a group of 8); RMSNorm over each head's dims on q and on k
  (one learned scale of ``head_dim`` each), then rotary (rotate-half, theta
  1e6, the whole head); ``softmax(q k^T / sqrt(head_dim))`` under the mask
  below; ``W_o``; no bias, no gate: ``models/lfm2_moe.Attention``, under the
  device scopes ``attn`` and ``attn_diffusion``.
* ``MoE``: ``p = softmax(x W_r)`` in float32 over ALL ``num_experts``; the
  ``top_k`` largest; weights ``p_i / sum of the chosen p`` (``norm_topk_prob``
  true: ``ops/moe.softmax_route(renormalise=True)``); ``sum_i w_i W_down,i
  (silu(x W_gate,i) * x W_up,i)`` over this chip's share of the experts; no
  shared expert, no bias, no state: ``models/glm_moe.Mixture`` with the
  router handed over as a function. Every layer is a mixture
  (``decoder_sparse_step`` 1, ``mlp_only_layers`` []). The balancing term is
  ``ops/moe.balance_stats``' form, ``E sum_e f_e P_e`` over all experts, a
  mean over the mixtures, weight ``aux_weight``.
* a final RMSNorm; an untied head.

**The objective.** A sequence ``x_0`` of S tokens is cut into blocks of
``block_length`` tokens, ``b(i) = i // block_length``. A step draws a level
``t_b`` in ``(noise_eps, 1]`` a block a sequence and masks each position on
its own: ``x_t,i = [MASK] if u_i < t_b(i) else x_0,i``. Every layer runs
ONCE over the 2S rows ``[x_t ; x_0]``, the noised copy followed by the clean
copy; row ``i`` of either half carries position ``i`` (rotary sees ``0..S-1``
twice). With ``n`` a noised row and ``c`` a clean row, query -> key:

    n_i -> n_j  iff b(i) = b(j)    a block denoises with two-sided attention
                                   inside itself
    n_i -> c_j  iff b(j) < b(i)    the clean past, never its own block's answer
    c_i -> c_j  iff b(j) <= b(i)   the context as inference will cache it
    c_i -> n_j  never

(``ops/flash_attention.py``'s ``diffusion_block``: neither kernel visits a
tile this mask empties, and no ``[2S, 2S]`` array exists.) The loss is over
the NOISED half alone, the label of a position its OWN clean token (no shift
by one; the batch's ``label``, the next tokens the loaders make, is not
read):

    L = 1 / (sequences S) sum_i [u_i < t_b(i)] / t_b(i) CE(logits(n_i), x_0,i)
        + aux_weight * balancing term

The clean half gives no logits: ``hidden_only=True`` returns ``(the noised
half's final-normed states [B, S, d], the mixtures' statistics, the noise)``
and the step's loss is :meth:`SDARMoE.head_loss`, a weighted cross-entropy
``head_chunk`` positions at a time (``ops/token_head.weighted_loss``). A
plain call returns the noised half's ``[B, S, V/n]`` logits.

**The noise** (under the device scope ``diffusion_noise``), from the key the
step hands the model as the stream ``diffusion``
(``parallel/partition/lowering.py``: the step's key; evaluation and any
call without the stream draw from ``jax.random.key(0)``, so a validation
loss is the same estimator on fixed draws): ``key = make_rng("diffusion")``
at the root module, ``k_level, k_mask = jax.random.split(key)``, ``t = 1 -
(1 - noise_eps) uniform(k_level, [B, S / block_length])`` (the clipped
uniform schedule, linear in t), ``u = uniform(k_mask, [B, S])``.
``benchmark/reference/sdar_moe.py`` draws the same by this rule, not by
this code.

``config.json`` names the widths, the heads, the experts, ``norm_topk_prob``
and the rotary base; the block length, the schedule and its clip, one level
a block, the mask's id, the balancing weight, that labels are not shifted
and the per-head q/k norms before rotary are the published ``sdar_moe`` /
Qwen3-MoE model class's or SDAR's recipe (``benchmark/configs/
sdar_30b_a3b.json``, ``assumed``). The stage, the chip's share, the block
and the recomputation policy are ``models/share.py``'s
(:class:`PatternStack`), as LFM2's and Trinity-Mini's; the float32 residual
stream and parameters ``models/glm_moe.py``'s.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distribuuuu_tpu.models.glm_moe import Mixture
from distribuuuu_tpu.models.layers import head_dtype
from distribuuuu_tpu.models.lfm2_moe import Attention
from distribuuuu_tpu.models.olmoe import RMSNorm, _normal
from distribuuuu_tpu.models.share import (
    PatternStack,
    mixture_metrics,
    pattern_kwargs_from_cfg,
    run_blocks,
    stacked,
)
from distribuuuu_tpu.models.traits import ArchTraits
from distribuuuu_tpu.ops import moe as moe_ops
from distribuuuu_tpu.ops import token_head

KIND = "block_diffusion_attention"  # every layer is this one kind
# the stream of the step's key the model draws its noise from: the step
# (parallel/partition/lowering.py) hands its key under every name here
NOISE_STREAM = "diffusion"


def draw_noise(key, batch: int, seq: int, block: int, eps: float):
    """``(masked [B, S] bool, level [B, S] float32)``: the module docstring's
    rule. One level a block a sequence, every position masked on its own."""
    k_level, k_mask = jax.random.split(key)
    level = 1.0 - (1.0 - eps) * jax.random.uniform(k_level, (batch, seq // block))
    level = jnp.repeat(level, block, axis=1)
    return jax.random.uniform(k_mask, (batch, seq)) < level, level


class SDARMoE(PatternStack):
    """Defaults are ``config.json``'s of JetLM/SDAR-30B-A3B-Chat."""

    KINDS = {KIND: "attn"}
    noise_streams = (NOISE_STREAM,)

    vocab_size: int = 151936  # published; this chip holds vocab_size / share_chips rows
    seq_len: int = 8192  # the training context here; config.json allows 32,768 positions
    dim: int = 2048
    layer_types: tuple = (KIND,) * 48  # num_hidden_layers, one kind
    first_layer: int = 0  # the published layer this chip's stage starts at
    depth: int = 0  # layers from first_layer on; 0: the rest of the list
    dense_layers: int = 0  # mlp_only_layers []: every layer a mixture
    num_heads: int = 32
    kv_heads: int = 4  # num_key_value_heads
    head_dim: int = 128
    mlp_hidden: int = 6144  # intermediate_size: no layer here is dense
    expert_hidden: int = 768  # moe_intermediate_size
    num_experts: int = 128
    top_k: int = 8  # num_experts_per_tok
    norm_eps: float = 1e-6  # rms_norm_eps
    rope_theta: float = 1e6
    block_length: int = 4  # the released checkpoint's; divides the input's length
    noise_eps: float = 1e-3  # the levels' lower clip
    mask_id: int = -1  # [MASK]'s token id; -1: the last row this chip holds
    aux_weight: float = 1e-3  # MODEL.MOE.AUX_WEIGHT
    share_chips: int = 1  # LM.SHARE_CHIPS: chips that share each layer
    share_rank: int = 0  # LM.SHARE_RANK: which of them this is
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    mesh: Any = None
    recompute: bool = True  # LM.RECOMPUTE
    head_chunk: int = 512

    @property
    def attn_head_dim(self) -> int:
        return self.head_dim

    @property
    def mask_token(self) -> int:
        """The id a masked position carries: the published ``<|MASK|>`` where
        this chip's rows of the vocabulary hold it, else their last row,
        which the data then never draws."""
        first = self.share_rank * self.vocab_held
        if first <= self.mask_id < first + self.vocab_held:
            return self.mask_id
        return first + self.vocab_held - 1

    def _check_input(self, tokens) -> None:
        super()._check_input(tokens)
        if tokens.shape[1] % self.block_length:
            raise ValueError(
                f"input length {tokens.shape[1]} is no whole number of "
                f"blocks of {self.block_length} tokens")

    def dummy_input(self):
        """Two whole blocks where the context has them (8 tokens at the
        published block length, as the other shares')."""
        length = max(self.block_length, min(8, self.seq_len) // self.block_length
                     * self.block_length)
        return jnp.full((2, length), self.share_rank * self.vocab_held, jnp.int32)

    @nn.compact
    def __call__(self, tokens, train: bool = False, hidden_only: bool = False):
        B, S = tokens.shape
        self._check_input(tokens)
        embed = self._embedding()
        with jax.named_scope("diffusion_noise"):
            key = (self.make_rng(NOISE_STREAM) if self.has_rng(NOISE_STREAM)
                   else jax.random.key(0))
            masked, level = draw_noise(key, B, S, self.block_length, self.noise_eps)
            noised = jnp.where(masked, self.mask_token, tokens)
            # read only by a caller that makes the collection mutable (the
            # benchmark's comparison with its reference's own draws)
            self.sow("diffusion_noise", "masked", masked)
            self.sow("diffusion_noise", "level", level)
            # the noised copy, then the clean copy; position i twice
            rows = jnp.concatenate([noised, tokens], axis=1)
            positions = jnp.tile(jnp.arange(S, dtype=jnp.int32), 2)
        mixers = {KIND: functools.partial(
            Attention, self.dim, self.num_heads, self.kv_heads, self.norm_eps,
            self.rope_theta, self.dtype, self.attn_impl, self.mesh,
            head_dim=self.head_dim, diffusion_block=self.block_length)}
        mixture = functools.partial(
            Mixture, self.dim, self.expert_hidden, self.num_experts, self.top_k,
            0, 1.0, 0.0, self.held, self.dtype, train, self.mesh,
            route=functools.partial(moe_ops.softmax_route, renormalise=True),
        )
        x, stats = run_blocks(
            self, embed(rows - self.share_rank * self.vocab_held), positions,
            mixers, mixture, norms=("input_norm", "post_attention_norm"))
        # the clean half has done its work as keys and values: no logits
        x = RMSNorm(self.norm_eps, name="final_norm")(x[:, :S]).astype(self.dtype)
        kernel = self.param(
            "head", _normal(), (self.dim, self.vocab_held), jnp.float32)
        if hidden_only:
            noise = {"masked": masked, "level": level, "labels": tokens}
            return x, stacked(stats), noise
        return jnp.einsum(
            "bsd,dv->bsv", x, kernel.astype(self.dtype),
            preferred_element_type=head_dtype(self.dtype),
        )

    @staticmethod
    def head_kernel(params):
        return params["head"]

    @staticmethod
    def loss_weights(noise):
        """``[u < t] / t`` a position: the objective's weight before the mean
        over the positions."""
        return noise["masked"] / noise["level"]

    def head_loss(self, outputs, kernel, labels, *, topk):
        """``(loss, hits over the masked positions, step metrics)``: the
        weighted cross-entropy of the noised half against each position's own
        clean token and the mixtures' balancing term. ``labels`` (the batch's
        next tokens) is not read."""
        del labels
        states, stats, noise = outputs
        masked = noise["masked"].astype(jnp.float32)
        with jax.named_scope("lm_head"):
            ce, (_, rank) = token_head.weighted_loss(
                states, kernel, self.head_labels(noise["labels"]),
                self.loss_weights(noise) / masked.size, chunk=self.head_chunk)
            hits = [((rank < k) * masked).sum() / jnp.maximum(masked.sum(), 1.0)
                    * 100.0 for k in topk]
        extra = {"ce": ce, "diffusion_masked_share": masked.mean(),
                 **mixture_metrics(stats)}
        return ce + self.aux_weight * extra["moe_aux"], hits, extra

    def eval_targets(self, outputs, labels):
        """``(the head's labels, a weight a position)`` of evaluation: the
        training objective's, on the fixed draws a call without the noise
        stream makes."""
        del labels
        noise = outputs[2]
        return self.head_labels(noise["labels"]), self.loss_weights(noise)


def sdar_30b_a3b(num_classes=151936, **kw):
    """SDAR-30B-A3B-Chat at its published sizes (48 layers over 128 experts;
    ``first_layer``, ``depth`` and the chips that share a layer are what one
    chip turns)."""
    return SDARMoE(vocab_size=num_classes, **kw)


def sdar_moe_tiny(num_classes=512, **kw):
    """The same blocks at a size the CPU tests run: 64 wide, 4 query heads on
    1 key/value head of 32 (so 4 x 32 = 128 is not the width, as published),
    8 experts of 32 with 2 a token, 4 layers, blocks of 4 tokens; two chips
    share a layer (4 experts and 256 vocabulary rows held, the mask's id the
    last of them); the head in chunks of 48, which do not divide its 128
    positions."""
    for key, value in dict(
        seq_len=128, dim=64, layer_types=(KIND,) * 4, depth=4, num_heads=4,
        kv_heads=1, head_dim=32, mlp_hidden=160, expert_hidden=32, num_experts=8,
        top_k=2, head_chunk=48, share_chips=2,
    ).items():
        kw.setdefault(key, value)
    return SDARMoE(vocab_size=num_classes, **kw)


sdar_30b_a3b.traits = sdar_moe_tiny.traits = ArchTraits(
    token_batch=True, batch_norm=False,
    # attention and the sorted experts per device, as models/glm_moe.py; the
    # exchange of tokens across the chips that share a layer is ROADMAP R2
    mesh_axes=("data",),
    kwargs_from_cfg=pattern_kwargs_from_cfg,
    serve_refusal=(
        "trains only: generation by diffusion over blocks commits a block of "
        "block_length tokens after several passes over that one block, and "
        "lm/generate.py's scheduler, cache and metrics assume one token a "
        "sequence a step"
    ),
)
