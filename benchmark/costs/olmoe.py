"""Multiply-accumulates of one OLMoE forward pass PER TOKEN, from the
configuration's sizes (``costs/common.py`` has the convention: 2 operations a
MAC, a training step is 3 forward passes, recomputation never counted).

A cell's item is a token. Per token and layer: the four projections
(4 d^2); attention's two matmuls, scores and values, under the causal mask,
where a token at position i reads i + 1 keys, S / 2 on average over a full
context of S (2 * S/2 * d); the router (d E); ``num_experts_per_tok`` gated
experts of three matrices (k * 3 * d * f). Once a token: the head (d V).
The embedding is a lookup. Norms, rotary, softmaxes, the sort and the loss
are not counted.

``attention_macs_per_token`` is the USEFUL work of the flash kernels:
forward two matmuls, backward four (dV, dP, dQ, dK), 3x the forward by the
convention. The two backward kernels each recompute the scores (one matmul
more each, 7 where 6 are counted), and every kernel computes whole blocks on
the diagonal where half is masked. So ``kernels.flash_attn_roofline`` is the
share of useful work and sits under what the MXU really does.
"""

from __future__ import annotations


def attention_macs_per_token(architecture: dict) -> int:
    a = architecture
    return a["layers"] * 2 * (a["max_position_embeddings"] // 2) * a["hidden_size"]


def expert_macs_per_token(architecture: dict) -> int:
    a = architecture
    return (a["layers"] * a["num_experts_per_tok"] * 3
            * a["hidden_size"] * a["intermediate_size"])


def forward_macs_per_item(architecture: dict) -> int:
    a = architecture
    d = a["hidden_size"]
    per_layer = 4 * d * d + d * a["num_experts"]
    return (a["layers"] * per_layer + attention_macs_per_token(a)
            + expert_macs_per_token(a) + d * a["vocab_size"])
