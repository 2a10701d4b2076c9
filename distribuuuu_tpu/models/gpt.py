"""Decoder-only transformer LM — the second workload family (ISSUE 12).

The image zoo proves the partition layer on fixed-shape supervised
classification; this model proves it on the workload the pjit-consolidation
line of work was actually built for (arXiv:2204.06514 — LM training under
one lowering). It deliberately REUSES the ViT building blocks —
``models/vit.Attention`` (with ``causal=True``), ``Block``, ``MoeMlp`` —
so an LM stanza exercises the exact attention/FFN/expert code paths the
mesh axes were proven on, with only three LM-specific pieces added:

  * a token embedding table (``tok_embed``) + learned positions
    (``pos_embed`` — a max-context table, sliced to the input length, so
    prefill/decode can run shorter sequences against the same params);
  * causal masking threaded through the shared ``Attention``;
  * a vocab-sized head producing per-token logits ``[B, S, V]`` — the
    next-token cross-entropy task head (the trainer's existing CE loss
    handles the token dim by flattening, utils/metrics.py).

Placement is declared, not coded: the attention/MLP kernels carry the same
``nn.with_partitioning`` column annotations every ViT Dense does, and the
LM-specific leaves (embedding, positions, head) are covered by the
path-pattern rules in ``parallel/partition/specs.lm_spec_table`` — the
model trains on any dp×tp×ep mesh through the unchanged partition lowering
(the ISSUE 12 acceptance: zero new lowering code, new SpecTable rules
only). MoE FFNs ride ``MESH.EXPERT`` exactly as ``vit_tiny_moe`` does.

Batch contract (data/shards/tokens.py): ``image`` = input tokens
``[B, S] int32``, ``label`` = next tokens ``[B, S] int32`` — the loader's
existing keys, so the declared batch specs (specs.BATCH_TABLE) and every
sharding/prefetch path apply verbatim.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from distribuuuu_tpu.models.layers import Dense, head_dtype
from distribuuuu_tpu.models.traits import ArchTraits
from distribuuuu_tpu.models.vit import Block


class GPT(nn.Module):
    """Token embed + learned positions → causal pre-norm blocks → LN →
    per-token vocab head. ``vocab_size`` comes from ``MODEL.NUM_CLASSES``
    (the byte tokenizer's 320: 256 bytes + EOS, padded to a multiple of 64
    so the vocab dim shards EVENLY over any model-axis size — an uneven
    constraint silently degrades to replication on this jax line, which
    the stanza drift gate would flag), ``seq_len`` from ``LM.SEQ_LEN``."""

    vocab_size: int = 320
    seq_len: int = 256
    dim: int = 192
    depth: int = 12
    num_heads: int = 3
    mlp_ratio: float = 4.0
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    attn_impl: str = "xla"
    mesh: Any = None
    moe_experts: int = 0  # >0: MoE FFN in every ``moe_every``-th block
    moe_top_k: int = 2
    moe_every: int = 2
    moe_impl: str = "partial"
    moe_capacity_factor: float = 2.0
    moe_axis: str = "model"  # mesh axis EP rides (MoeMlp.moe_axis)

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        B, S = tokens.shape
        if S > self.seq_len:
            raise ValueError(
                f"input length {S} exceeds the trained context "
                f"LM.SEQ_LEN={self.seq_len} (the learned position table)"
            )
        x = nn.Embed(
            self.vocab_size, self.dim, name="tok_embed",
            dtype=self.dtype, param_dtype=jnp.float32,
            embedding_init=nn.initializers.normal(0.02),
        )(tokens)
        pos = self.param(
            "pos_embed",
            nn.initializers.normal(0.02),
            (1, self.seq_len, self.dim), jnp.float32,
        )
        x = x + pos[:, :S].astype(self.dtype)
        x = nn.Dropout(self.dropout, deterministic=not train)(x)
        for i in range(self.depth):
            # MoE in every moe_every-th block — the same GShard placement
            # vit_tiny_moe uses, so PP/EP conversion tooling stays shared
            moe = (
                self.moe_experts
                if self.moe_experts > 0
                and i % self.moe_every == self.moe_every - 1
                else 0
            )
            x = Block(
                self.dim, self.num_heads, self.mlp_ratio, self.dropout,
                self.dtype, self.attn_impl, self.mesh,
                moe_experts=moe, moe_top_k=self.moe_top_k,
                moe_impl=self.moe_impl,
                moe_capacity_factor=self.moe_capacity_factor,
                moe_axis=self.moe_axis,
                causal=True,
            )(x, train=train)
        x = nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32)(x)
        hd = head_dtype(x.dtype)
        return Dense(self.vocab_size, dtype=hd, name="head")(x.astype(hd))

    # ------------------------------------------------ partition-layer hooks
    def dummy_input(self):
        """Shape/annotation source for ``specs.abstract_state`` — token
        models can't eat the image dummy. Short (8 tokens): init slices
        the position table, so param SHAPES don't depend on the dummy.
        Under a populated seq axis the dummy's token dim is rounded to a
        multiple of the axis size — the ring shard_map splits it evenly
        at trace time, and an 8-token dummy on a seq=16 axis would refuse
        before the real refusal (LM.SEQ_LEN divisibility) could speak."""
        S = min(8, self.seq_len)
        if self.mesh is not None:
            n = int(dict(self.mesh.shape).get("seq", 1))
            if n > 1:
                S = max(S, n)
                S -= S % n
        return jnp.zeros((2, S), jnp.int32)

    def param_spec_table(self):
        """The LM leaf rules (parallel/partition/specs.lm_spec_table):
        path-pattern declarations for the LM-specific leaves plus the
        cross-checked attention/MLP kernel family."""
        from distribuuuu_tpu.parallel.partition import specs

        return specs.lm_spec_table(moe_axis=self.moe_axis)

    def batch_spec_table(self):
        """Token batch placement (parallel/partition/specs): ``[B, S]``
        input/target leaves shard the token dim over ``seq`` on top of the
        batch dim over ``data`` — the dp×sp layout ring attention consumes
        — while the per-sequence ``mask`` stays on ``data``. Collapses to
        the image-model layout on seq=1 meshes."""
        from distribuuuu_tpu.parallel.partition import specs

        return specs.TOKEN_BATCH_TABLE


def _gpt(num_classes, kw, **defaults):
    for k, v in defaults.items():
        kw.setdefault(k, v)
    return GPT(vocab_size=num_classes, **kw)


def gpt_nano(num_classes=320, **kw):
    """GPT-nano: 128 dim, 4 blocks, 4 heads (~1M params at vocab 320) —
    the CPU-testable LM the stanza gate and the generation plane drive."""
    return _gpt(num_classes, kw, dim=128, depth=4, num_heads=4)


def gpt_nano_moe(num_classes=320, **kw):
    """GPT-nano with MoE FFN in every 2nd block (8 experts, top-2 by
    default — MODEL.MOE.*): the dp×tp×ep LM citizen. Expert tensors ride
    ``MESH.EXPERT`` when populated, the ``model`` axis otherwise."""
    kw.setdefault("moe_experts", 8)
    return _gpt(num_classes, kw, dim=128, depth=4, num_heads=4)


gpt_nano.traits = gpt_nano_moe.traits = ArchTraits(token_batch=True, batch_norm=False)
