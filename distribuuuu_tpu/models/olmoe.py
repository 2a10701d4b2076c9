"""OLMoE (arXiv:2409.02060; HF ``model_type`` ``olmoe``): a decoder whose
every block is pre-norm attention with QK-norm and rotary positions, then a
dropless top-k mixture of gated-SiLU experts.

    h = x + Attn(RMSNorm(x))          y = h + MoE(RMSNorm(h))

* ``RMSNorm(x; w) = x * rsqrt(mean(x^2) + eps) * w``, in float32. The
  residual stream is float32 too; ``dtype`` is what the matmuls read.
* ``Attn``: bias-free q/k/v/o projections; RMSNorm on q and on k over the
  whole model width BEFORE the split into heads; rotary positions over the
  full head dim (rotate-half convention, base ``rope_theta``); causal
  softmax attention through the entry the ViT uses (``attn_impl`` ``auto``
  -> the flash kernel from 1024 tokens, dense XLA below).
* ``MoE``: float32 router, softmax over all experts, the ``top_k`` largest
  probabilities AS THEY ARE (no renormalization), three bias-free matrices
  an expert; ``ops/moe.moe_ffn_sorted`` does O(top_k) expert rows a token
  and drops nothing.
* final RMSNorm, untied bias-free head; no position table.

The block is written once, for training. Serving it (ROADMAP R1) adds a
key/value cache as an optional argument of :class:`Attention`: the cache
holds k AFTER its norm and rotary and v as projected, ``positions`` are
then the cache offsets, and ``_attend`` is the only line that changes.

The head is left to the step: ``hidden_only=True`` returns the final
hidden state and ``head_kernel`` the head's matrix, and
``lowering.loss_fn`` takes head, loss and hits ``head_chunk`` positions at a
time (``ops/token_head.py``); a plain call returns ``[B, S, V]`` logits.

Batch contract as ``models/gpt.py``: ``image`` = tokens, ``label`` = next
tokens, both ``[B, S]`` int32.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distribuuuu_tpu.models.layers import head_dtype
from distribuuuu_tpu.models.traits import ArchTraits
from distribuuuu_tpu.models.vit import Attention as VitAttention

INIT_STD = 0.02  # the paper's truncated normal; plain normal here


def _normal():
    return nn.initializers.normal(INIT_STD)


def rms_norm(x, scale, eps: float):
    """``x * rsqrt(mean(x^2) + eps) * scale`` over the last axis, statistics
    and result in float32."""
    x32 = x.astype(head_dtype(x.dtype))
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return x32 * inv * scale


class RMSNorm(nn.Module):
    """Statistics and result in float32; the caller rounds where it feeds a
    matmul."""

    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        return rms_norm(x, scale, self.eps)


def rotary(x, positions, theta: float):
    """Rotate-half rotary embedding of ``x [B, H, S, D]`` at ``positions
    [S]``: ``x*cos + rotate_half(x)*sin``, ``rotate_half([a, b]) = [-b, a]``
    on the two halves of the head dim; angles, and with a float32 ``x`` the
    result, in float32."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def _attend(q, k, v, impl: str, dtype, mesh=None, window: int | None = None,
            diffusion_block: int | None = None):
    """Causal softmax attention on ``[B, H, S, D]``; on a ``mesh`` whose data
    axis is populated the flash kernel runs per data rank. With a ``window``
    query t reads keys ``t - window < s <= t`` (``models/afmoe.py``); with a
    ``diffusion_block`` the S rows are a noised and a clean copy of a
    sequence under the block-diffusion mask (``models/sdar_moe.py``;
    ``ops/flash_attention.diffusion_mask``)."""
    from distribuuuu_tpu.ops import flash_attention as fa

    if impl == "flash":
        return fa.flash_attention(
            q, k, v, causal=True, mesh=mesh, window=window,
            diffusion_block=diffusion_block)
    S = q.shape[2]
    with jax.named_scope("attn_softmax_fp32"):
        scores = jnp.einsum(
            "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
        ) * q.shape[-1] ** -0.5
        if diffusion_block is not None:
            keep = fa.diffusion_mask(S, diffusion_block)
        else:
            keep = jnp.tril(jnp.ones((S, S), bool))
        if window is not None:  # and not the keys a window or more behind
            keep = keep & ~jnp.tril(jnp.ones((S, S), bool), -window)
        scores = jnp.where(keep[None, None], scores, jnp.float32(-1e30))
        out = jnp.einsum(
            "bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v.astype(jnp.float32)
        )
        return out.astype(dtype)


class Attention(nn.Module):
    dim: int
    num_heads: int
    eps: float
    rope_theta: float
    dtype: Any
    attn_impl: str = "auto"
    mesh: Any = None
    qk_norm: bool = True  # models/ouro.py's sandwich norms stand in its place

    @nn.compact
    def __call__(self, x, positions):
        B, S, _ = x.shape
        H, D = self.num_heads, self.dim // self.num_heads

        def proj(name):
            return nn.Dense(
                self.dim, use_bias=False, dtype=self.dtype,
                param_dtype=jnp.float32, kernel_init=_normal(), name=name,
            )

        def heads(t):
            return t.reshape(B, S, H, D).transpose(0, 2, 1, 3)

        x = x.astype(self.dtype)

        def normed(name):
            t = proj(f"{name}_proj")(x)
            return RMSNorm(self.eps, name=f"{name}_norm")(t) if self.qk_norm else t

        q, k = normed("q"), normed("k")
        v = heads(proj("v_proj")(x))
        q = rotary(heads(q), positions, self.rope_theta).astype(self.dtype)
        k = rotary(heads(k), positions, self.rope_theta).astype(self.dtype)
        impl = VitAttention.resolve_impl(self.attn_impl, S, 0.0)
        out = _attend(q, k, v, impl, self.dtype, self.mesh)
        out = out.astype(self.dtype).transpose(0, 2, 1, 3).reshape(B, S, self.dim)
        return proj("o_proj")(out)


class MoE(nn.Module):
    """Router and experts; sows what the step's loss and metrics read:
    ``intermediates/moe_aux`` (balancing loss, ``ops/moe.balance_stats``
    form), ``moe_z/z`` (router z-loss), ``moe_stats/dropped`` (0: the path
    has no capacity), ``moe_load/max_over_mean`` and
    ``moe_route/experts`` (the experts chosen, ``[B, S, k]``)."""

    dim: int
    hidden: int
    num_experts: int
    top_k: int
    dtype: Any
    mesh: Any = None
    moe_axis: str = "model"

    @nn.compact
    def __call__(self, x, train: bool = False):
        from distribuuuu_tpu.ops import moe as moe_ops

        E, d, f = self.num_experts, self.dim, self.hidden

        def expert(name, shape):
            init = nn.with_partitioning(_normal(), (self.moe_axis, None, None))
            return self.param(name, init, shape, jnp.float32)

        params = {
            "router": self.param("router", _normal(), (d, E), jnp.float32),
            "w_gate": expert("w_gate", (E, d, f)),
            "w_up": expert("w_up", (E, d, f)),
            "w_down": expert("w_down", (E, f, d)),
        }
        # the router reads the norm's float32 result, the experts its
        # rounding to the compute dtype
        out, route = moe_ops.moe_ffn_sorted(
            params, x.astype(self.dtype), top_k=self.top_k, mesh=self.mesh,
            router_x=x,
        )
        # read only by a caller that makes the collection mutable (the
        # benchmark's comparison with its reference)
        self.sow("moe_route", "experts", route["indices"])
        if train:
            self.sow("intermediates", "moe_aux", moe_ops.aux_from_balance_stats(
                *moe_ops.balance_stats(route["probs"], self.top_k)
            ))
            self.sow("moe_z", "z", moe_ops.router_z_loss(
                x.reshape(-1, d), params["router"]
            ))  # on the float32 input the router read
            self.sow("moe_stats", "dropped", jnp.float32(0.0),
                     reduce_fn=lambda a, b: a + b, init_fn=lambda: 0.0)
            self.sow("moe_load", "max_over_mean", moe_ops.load_max_over_mean(route["counts"]))
        return out


class Block(nn.Module):
    dim: int
    num_heads: int
    expert_hidden: int
    num_experts: int
    top_k: int
    eps: float
    rope_theta: float
    dtype: Any
    attn_impl: str
    mesh: Any
    moe_axis: str

    @nn.compact
    def __call__(self, x, positions, train: bool = False):
        """``x`` is the residual stream, float32 (what bf16 autocast over
        float32 parameters gives: the embedding's output is float32 and every
        addition promotes to it); the matmuls inside run in ``dtype``."""
        with jax.named_scope("attn"):
            x = x + Attention(
                self.dim, self.num_heads, self.eps, self.rope_theta, self.dtype,
                self.attn_impl, self.mesh, name="attn",
            )(RMSNorm(self.eps, name="attn_norm")(x), positions)
        with jax.named_scope("moe"):
            x = x + MoE(
                self.dim, self.expert_hidden, self.num_experts, self.top_k,
                self.dtype, self.mesh, self.moe_axis, name="moe",
            )(RMSNorm(self.eps, name="moe_norm")(x), train=train)
        return x


class OLMoE(nn.Module):
    """Defaults are ``config.json``'s of OLMoE-1B-7B-0125-Instruct."""

    vocab_size: int = 50304
    seq_len: int = 4096
    dim: int = 2048
    depth: int = 16
    num_heads: int = 16
    expert_hidden: int = 1024
    num_experts: int = 64
    top_k: int = 8
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    mesh: Any = None
    moe_axis: str = "model"
    # positions of every sequence whose head, loss, hits and (in training)
    # both of the head's gradients the step takes at a time
    # (ops/token_head.py): [B, head_chunk, V] float32 logits are the largest
    # block that ever exists (412 MB at 4 x 512 x 50,304), made once a chunk;
    # between forward and backward the head holds its two gradients only
    head_chunk: int = 512

    @nn.compact
    def __call__(self, tokens, train: bool = False, hidden_only: bool = False):
        B, S = tokens.shape
        if S > self.seq_len:
            raise ValueError(
                f"input length {S} exceeds the context LM.SEQ_LEN={self.seq_len}"
            )
        x = nn.Embed(
            self.vocab_size, self.dim, name="tok_embed",
            dtype=head_dtype(self.dtype), param_dtype=jnp.float32,
            embedding_init=_normal(),
        )(tokens)
        positions = jnp.arange(S, dtype=jnp.int32)
        for i in range(self.depth):
            x = Block(
                self.dim, self.num_heads, self.expert_hidden, self.num_experts,
                self.top_k, self.rms_norm_eps, self.rope_theta, self.dtype,
                self.attn_impl, self.mesh, self.moe_axis, name=f"Block_{i}",
            )(x, positions, train=train)
        x = RMSNorm(self.rms_norm_eps, name="final_norm")(x).astype(self.dtype)
        kernel = self.param(
            "head", _normal(), (self.dim, self.vocab_size), jnp.float32
        )
        if hidden_only:
            return x
        return jnp.einsum(
            "bsd,dv->bsv", x, kernel.astype(x.dtype),
            preferred_element_type=head_dtype(x.dtype),
        )

    # ------------------------------------------------ partition-layer hooks
    @staticmethod
    def head_kernel(params):
        """The head's ``[d, V]`` matrix in a parameter tree: its presence
        tells ``lowering.loss_fn`` to take head and loss in chunks."""
        return params["head"]

    def dummy_input(self):
        return jnp.zeros((2, min(8, self.seq_len)), jnp.int32)

    def param_spec_table(self):
        from distribuuuu_tpu.parallel.partition import specs

        return specs.lm_spec_table(moe_axis=self.moe_axis)

    def batch_spec_table(self):
        from distribuuuu_tpu.parallel.partition import specs

        return specs.TOKEN_BATCH_TABLE


def olmoe_1b_7b(num_classes=50304, **kw):
    """OLMoE-1B-7B at its published sizes (6.92 B parameters at depth 16;
    ``depth`` is the one knob a single chip has to turn down)."""
    return OLMoE(vocab_size=num_classes, **kw)


def olmoe_tiny(num_classes=512, **kw):
    """The same block at a size the CPU tests run: 64 wide, 4 heads of 16,
    8 experts of 32 with 2 a token, 2 layers; the head in chunks of 48, which
    do not divide its 128 positions."""
    for key, value in dict(
        seq_len=128, dim=64, depth=2, num_heads=4, expert_hidden=32,
        num_experts=8, top_k=2, head_chunk=48,
    ).items():
        kw.setdefault(key, value)
    return OLMoE(vocab_size=num_classes, **kw)


def decoder_kwargs_from_cfg(cfg, topology) -> dict:
    """The widths are the arch's own (``config.json``'s): the config sizes
    context, depth and the attention entry, nothing else. Shared with
    ``models/ouro.py``."""
    if cfg.DEVICE.ATTN_IMPL not in ("auto", "xla", "flash"):
        raise ValueError(
            f"DEVICE.ATTN_IMPL={cfg.DEVICE.ATTN_IMPL!r}: {cfg.MODEL.ARCH} "
            "accepts 'auto', 'xla' or 'flash'"
        )
    kwargs = {"seq_len": int(cfg.LM.SEQ_LEN), "attn_impl": cfg.DEVICE.ATTN_IMPL}
    if int(cfg.LM.LAYERS) > 0:
        kwargs["depth"] = int(cfg.LM.LAYERS)
    if topology.data > 1:
        from distribuuuu_tpu.parallel import mesh as mesh_lib

        kwargs["mesh"] = mesh_lib.mesh_from_cfg(cfg)
    return kwargs


def kwargs_from_cfg(cfg, topology) -> dict:
    return {**decoder_kwargs_from_cfg(cfg, topology), "moe_axis": topology.moe_axis()}


olmoe_1b_7b.traits = olmoe_tiny.traits = ArchTraits(
    token_batch=True, batch_norm=False,
    # experts unsharded and sorted (ops/moe.moe_ffn_sorted), attention per
    # device; expert and tensor parallelism for it are ROADMAP R2/D5
    mesh_axes=("data",),
    kwargs_from_cfg=kwargs_from_cfg,
    serve_refusal=(
        "trains only: the generation plane (lm/generate.py) mirrors the "
        "gpt_* modules by name, and serving this block through it is "
        "ROADMAP R1"
    ),
)
