"""LFM2-24B-A2B (``LiquidAI/LFM2-24B-A2B``; HF ``model_type`` ``lfm2_moe``):
a decoder whose token mixer is chosen LAYER BY LAYER from the published
``layer_types``, as ONE chip of an expert-parallel group holds it.

    h = x + mixer_i(RMSNorm(x))          y = h + F_i(RMSNorm(h))

* ``mixer_i`` is a gated short convolution where ``layer_types[i]`` is
  ``conv`` (:class:`ShortConv`: ``[B, C, u] = split3(x W_in)``, ``g = B * u``,
  a depthwise causal filter of ``conv_L_cache`` taps over ``g``, ``out = (C *
  c) W_out``; no bias, no activation; ``ops/short_conv.py``: everything
  between the two matmuls is one Pallas call each way in a one-device TPU
  program, ``ops/pallas/short_conv.py``, and plain ``jax.numpy`` on the CPU
  and in a program that may span devices) and grouped-query
  attention where it is ``full_attention`` (:class:`Attention`:
  ``num_attention_heads`` query heads on ``num_key_value_heads`` key/value
  heads, query head ``h`` reading key/value head ``h // group``; RMSNorm over
  each head's dims on q and on k, one learned scale each; rotary over the
  whole head, rotate-half (:class:`HeadNorm`: at heads a multiple of 128
  wide in a one-device TPU program one Pallas call each way,
  ``ops/head_prologue.py``; at this model's 64 the ``jax.numpy`` lines);
  causal softmax through ``ops/flash_attention.py``,
  which takes k and v with their own fewer heads: nothing is repeated in
  HBM).
* ``F_i`` is a dense gated-SiLU MLP of ``intermediate_size`` in the first
  ``num_dense_layers`` blocks and the mixture in every later one:
  ``models/glm_moe.Mixture`` (sigmoid scores in float32, the ``top_k``
  experts by ``s + b``, weights ``scale * s_i / (sum of the chosen s +
  1e-6)``, ``b`` state a rule moves, this chip's share of the experts) with
  no shared expert.
* the layers are the published list from ``first_layer`` on: ``depth`` of
  them, so a chip's cut keeps the pattern's order and ratio. ``first_layer =
  1, depth = 5`` is layers 1..5 of LFM2-24B-A2B: ONE leading dense layer
  (the published two counted once) and a whole period of four mixture
  layers, ``conv, full_attention, conv, conv, conv``.
* final RMSNorm; the head is the embedding (tied): ``head_kernel`` hands the
  step ``E^T``, so the embedding's gradient has two sources, the lookup and
  the head, which autodiff adds.
* the chip's share (``share_chips``, ``share_rank``), the stage, the block
  around a mixer and the recomputation policy (every block of either kind
  keeps its float32 input, its mixer's output and what the flash backward
  kernel reads: ``models/ouro.recomputed``) are ``models/share.py``'s
  (:class:`PatternStack`, ``Block``, ``run_blocks``), the float32 residual
  stream and parameters ``models/glm_moe.py``'s; ``recompute = False`` keeps
  every activation instead, and the names lower to nothing.

``hidden_only=True`` returns ``(states [B, S, d], the mixtures'
statistics)`` and the step's loss is ``ShareOfALayer.head_loss``; a plain call
returns the ``[B, S, V/n]`` logits.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distribuuuu_tpu.models.glm_moe import Mixture, _dense
from distribuuuu_tpu.models.layers import head_dtype
from distribuuuu_tpu.models.olmoe import RMSNorm, _attend, _normal, rms_norm, rotary
from distribuuuu_tpu.models.share import (
    PatternStack,
    pattern_kwargs_from_cfg,
    run_blocks,
    stacked,
)
from distribuuuu_tpu.models.traits import ArchTraits
from distribuuuu_tpu.models.vit import Attention as VitAttention
from distribuuuu_tpu.ops import head_prologue
from distribuuuu_tpu.ops.short_conv import gated_short_conv

# config.json's layer_types: conv, conv, attention, conv, and so on to 40
LAYER_TYPES_24B = ("conv", "conv", "full_attention", "conv") * 10
class ShortConv(nn.Module):
    """The gated short-convolution mixer; ``filter`` is ``[H, L]``."""

    dim: int
    taps: int
    dtype: Any

    @nn.compact
    def __call__(self, x, positions):
        del positions  # the filter's taps are its only notion of position
        bcu = _dense(3 * self.dim, self.dtype, "in_proj")(x.astype(self.dtype))
        w = self.param("filter", _normal(), (self.dim, self.taps), jnp.float32)
        return _dense(self.dim, self.dtype, "out_proj")(gated_short_conv(bcu, w))


class HeadNorm(nn.Module):
    """A q or k projection's way to the attention kernels, under the device
    scope ``attn_prologue``: ``t [B, S, n D]`` (``x W`` as the projection wrote
    it) to heads-major ``[B, n, S, D]``, an RMSNorm over each head's dims with
    ONE learned ``scale [D]``, the rotary where ``theta`` is a number, float32
    throughout and rounded once to ``t``'s dtype.

    **Which path runs where** is ``ops/head_prologue.kernel_runs``'s to say,
    from what the call observes (no knob, no model's name): in a one-device
    TPU program with heads a multiple of 128 wide (Trinity-Mini, SDAR) ONE
    Pallas call each way, ``dtpu_head_prologue_fwd`` / ``_bwd``, which keeps
    ``t`` alone for the backward; on the CPU, in a program that may span
    devices and at LFM2's heads of 64 :meth:`xla`, the ``jax.numpy`` lines
    this class always ran, under plain autodiff (and the kernel's reference in
    ``tests/test_head_prologue.py``)."""

    heads: int
    eps: float
    theta: Any  # None: the layer carries no position signal

    @staticmethod
    def xla(t, scale, positions, heads: int, eps: float, theta):
        B, S, width = t.shape
        x = t.reshape(B, S, heads, width // heads).transpose(0, 2, 1, 3)
        x = rms_norm(x, scale, eps)
        if theta is not None:
            x = rotary(x, positions, theta)
        return x.astype(t.dtype)

    @nn.compact
    def __call__(self, t, positions):
        scale = self.param(
            "scale", nn.initializers.ones, (t.shape[-1] // self.heads,), jnp.float32)
        with jax.named_scope("attn_prologue"):
            if head_prologue.kernel_runs(t, self.heads, self.theta is not None):
                return head_prologue.head_prologue(
                    t, scale, positions, heads=self.heads, eps=self.eps,
                    theta=self.theta)
            return self.xla(t, scale, positions, self.heads, self.eps, self.theta)


class Attention(nn.Module):
    """Grouped-query attention with a per-head RMSNorm on q and on k
    (:class:`HeadNorm`, which says which path takes a projection's output to
    the kernels' layout: one Pallas call each way on the TPU at heads of 128,
    the ``jax.numpy`` lines elsewhere and at LFM2's 64). The
    defaults of the last five are LFM2's; ``models/afmoe.py`` gives four:
    heads of ``head_dim`` (0: ``dim / num_heads``), a sliding ``window``
    (under the scope ``attn_window``), no ``rotary`` where a layer carries no
    position signal, and ``gated``: ``out = (heads * sigmoid(x W_g)) W_o``
    (under ``attn_gate``); ``models/sdar_moe.py`` ``head_dim`` and
    ``diffusion_block``: the rows are a noised and a clean copy of a sequence
    under the block-diffusion mask (under ``attn_diffusion``)."""

    dim: int
    num_heads: int
    kv_heads: int
    eps: float
    rope_theta: float
    dtype: Any
    attn_impl: str = "auto"
    mesh: Any = None
    head_dim: int = 0
    window: Any = None  # int: query t reads keys t - window < s <= t
    rotary: bool = True
    gated: bool = False
    diffusion_block: Any = None  # int: ops/flash_attention's block-diffusion mask

    @nn.compact
    def __call__(self, x, positions):
        B, S, _ = x.shape
        H, G = self.num_heads, self.kv_heads
        D = self.head_dim or self.dim // H
        x = x.astype(self.dtype)

        def proj(name, n):
            return _dense(n * D, self.dtype, f"{name}_proj")(x)

        def heads(name, n):  # x W -> [B, n, S, D]
            return proj(name, n).reshape(B, S, n, D).transpose(0, 2, 1, 3)

        def normed(name, n):  # one scale of D for every head
            return HeadNorm(
                n, self.eps, self.rope_theta if self.rotary else None,
                name=f"{name}_norm")(proj(name, n), positions)

        masked = (jax.named_scope("attn_window") if self.window is not None
                  else jax.named_scope("attn_diffusion")
                  if self.diffusion_block is not None else contextlib.nullcontext())
        with masked:
            q, k, v = normed("q", H), normed("k", G), heads("v", G)
            impl = VitAttention.resolve_impl(self.attn_impl, S, 0.0)
            if impl != "flash":  # the dense path takes q, k and v of one shape
                k, v = (jnp.repeat(t, H // G, axis=1) for t in (k, v))
            # the block-diffusion mask by keyword, and only where a layer has
            # one: the entry's accepted callers hand it seven arguments
            out = _attend(q, k, v, impl, self.dtype, self.mesh, self.window, **(
                {} if self.diffusion_block is None
                else {"diffusion_block": self.diffusion_block}))
            out = out.astype(self.dtype).transpose(0, 2, 1, 3).reshape(B, S, H * D)
            if self.gated:
                with jax.named_scope("attn_gate"):
                    gate = _dense(H * D, self.dtype, "gate_proj")(x)
                    # float32 sigmoid and product, rounded once
                    out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                        gate.astype(jnp.float32))).astype(self.dtype)
            return _dense(self.dim, self.dtype, "o_proj")(out)


class LFM2MoE(PatternStack):
    """Defaults are ``config.json``'s of LiquidAI/LFM2-24B-A2B."""

    # layer_types' word for a mixer -> its params' name and device scope
    KINDS = {"conv": "short_conv", "full_attention": "attn"}

    vocab_size: int = 65536  # published; this chip holds vocab_size / share_chips rows
    seq_len: int = 8192  # the training context here; config.json allows 128,000 positions
    dim: int = 2048
    layer_types: tuple = LAYER_TYPES_24B  # the published list, whole
    first_layer: int = 0  # the published layer this chip's stage starts at
    depth: int = 0  # layers from first_layer on; 0: the rest of the list
    dense_layers: int = 2  # num_dense_layers, counted from published layer 0
    num_heads: int = 32
    kv_heads: int = 8  # num_key_value_heads
    conv_taps: int = 3  # conv_L_cache
    mlp_hidden: int = 11776  # intermediate_size, the dense layers'
    expert_hidden: int = 1536  # moe_intermediate_size
    num_experts: int = 64
    top_k: int = 4  # num_experts_per_tok
    routed_scale: float = 1.0  # routed_scaling_factor, with norm_topk_prob
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    route_norm_eps: float = 1e-6  # added to the chosen scores' sum
    bias_rate: float = 0.001  # DeepSeek-V3's rule and rate, as models/glm_moe.py
    aux_weight: float = 1e-4  # MODEL.MOE.AUX_WEIGHT
    share_chips: int = 1  # LM.SHARE_CHIPS: chips that share each layer
    share_rank: int = 0  # LM.SHARE_RANK: which of them this is
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    mesh: Any = None
    recompute: bool = True  # LM.RECOMPUTE
    head_chunk: int = 512

    @nn.compact
    def __call__(self, tokens, train: bool = False, hidden_only: bool = False):
        S = tokens.shape[1]
        self._check_input(tokens)
        embed = self._embedding()
        tokens = tokens - self.share_rank * self.vocab_held
        positions = jnp.arange(S, dtype=jnp.int32)
        mixers = {
            "conv": functools.partial(ShortConv, self.dim, self.conv_taps, self.dtype),
            "full_attention": functools.partial(
                Attention, self.dim, self.num_heads, self.kv_heads, self.norm_eps,
                self.rope_theta, self.dtype, self.attn_impl, self.mesh),
        }
        mixture = functools.partial(
            Mixture, self.dim, self.expert_hidden, self.num_experts, self.top_k,
            0, self.routed_scale, self.bias_rate, self.held, self.dtype, train,
            self.mesh, self.route_norm_eps,
        )
        x, stats = run_blocks(
            self, embed(tokens), positions, mixers, mixture,
            norms=("operator_norm", "ffn_norm"))
        x = RMSNorm(self.norm_eps, name="final_norm")(x).astype(self.dtype)
        if hidden_only:
            return x, stacked(stats)
        return jnp.einsum(
            "bsd,vd->bsv", x, embed.embedding.astype(self.dtype),
            preferred_element_type=head_dtype(self.dtype),
        )

    @staticmethod
    def head_kernel(params):
        """The head IS the embedding: ``[d, V/n]`` of it."""
        return params["tok_embed"]["embedding"].T


def lfm2_24b_a2b(num_classes=65536, **kw):
    """LFM2-24B-A2B at its published sizes (40 layers over 64 experts;
    ``first_layer``, ``depth`` and the chips that share a layer are what one
    chip turns)."""
    return LFM2MoE(vocab_size=num_classes, **kw)


def lfm2_moe_tiny(num_classes=512, **kw):
    """The same blocks at a size the CPU tests run: 64 wide, 4 query heads on
    2 key/value heads of 16, a dense MLP of 160 in the first 2 layers, then 8
    experts of 32 with 2 a token, 6 layers by the pattern conv, conv,
    attention, conv; two chips share a layer (4 experts and 256 vocabulary
    rows held); the head in chunks of 48, which do not divide its 128
    positions."""
    for key, value in dict(
        seq_len=128, dim=64, layer_types=("conv", "conv", "full_attention", "conv") * 2,
        depth=6, num_heads=4, kv_heads=2, mlp_hidden=160, expert_hidden=32,
        num_experts=8, top_k=2, head_chunk=48, share_chips=2,
    ).items():
        kw.setdefault(key, value)
    return LFM2MoE(vocab_size=num_classes, **kw)


lfm2_24b_a2b.traits = lfm2_moe_tiny.traits = ArchTraits(
    token_batch=True, batch_norm=False,
    # mixers and the sorted experts per device, as models/glm_moe.py; the
    # exchange of tokens across the chips that share a layer is ROADMAP R2
    mesh_axes=("data",),
    kwargs_from_cfg=pattern_kwargs_from_cfg,
    serve_refusal=(
        "trains only: serving a stack of two layer kinds takes a cache typed "
        "by layer (a convolution's last conv_L_cache - 1 gated inputs beside "
        "attention's keys and values), which lm/generate.py lacks"
    ),
)
