"""Multiply-accumulates of one LFM2-24B-A2B forward pass PER TOKEN as one
chip of its expert-parallel group runs it, and the bytes its short
convolution's gates must move, from the configuration's sizes
(``costs/common.py`` has the convention: 2 operations a MAC, a training step
is 3 forward passes, recomputation never counted).

A cell's item is a token. ``architecture["layer_types"]`` lists the layers
that are run and ``num_dense_layers`` how many of them, from the first,
carry the dense FFN. Per token: a ``conv`` layer's two projections (d 3d + d
d); a ``full_attention`` layer's four (d H D + 2 d G D + H D d, H query
heads on G key/value heads of D) and its two matmuls under the causal mask,
S / 2 keys a token on average over a full context of S = ``train_context``,
at the PUBLISHED head dim D = 64 (the kernels pad it to the 128 lanes: that
is their waste, not work); a dense layer's gated FFN (3 d f); every mixture's
router (d E) and the HELD experts' share of the ``num_experts_per_tok`` rows
a token: ``held_share`` of them, ``experts_held / num_experts`` unless the
caller measured it (the step's ``moe_held_row_share``), 3 d f_moe a row; the
head over the held rows of the vocabulary (d V/n; it is the embedding, whose
lookup is not counted). Norms, the gates and the filter, rotary, softmaxes,
the sort and the loss are not counted as operations.

``short_conv_gate_bytes_per_token`` is what a PERFECT fusion of gate ->
filter -> gate moves through HBM for one token, forward and backward, in
the compute dtype: forward it reads B, C and u (3 d) and writes the gated
result (d); backward it reads the result's cotangent (d) and B, C and u
again (3 d) and writes their three cotangents (3 d): 11 d elements a token a
``conv`` layer. The filter and its gradient (d L numbers a layer) are nothing
beside them, and a recomputed forward is time and not bytes, as everywhere.
"""

from __future__ import annotations


def _count(architecture: dict, kind: str) -> int:
    return list(architecture["layer_types"]).count(kind)


def mixtures(architecture: dict) -> int:
    return len(architecture["layer_types"]) - architecture["num_dense_layers"]


def head_dim(architecture: dict) -> int:
    return architecture["hidden_size"] // architecture["num_attention_heads"]


def projection_macs_per_token(architecture: dict) -> int:
    """The mixers' projections: a conv layer's in and out, an attention
    layer's q, k, v and o."""
    a = architecture
    d, dim = a["hidden_size"], head_dim(a)
    q, kv = a["num_attention_heads"] * dim, a["num_key_value_heads"] * dim
    return (_count(a, "conv") * (3 * d * d + d * d)
            + _count(a, "full_attention") * (d * q + 2 * d * kv + q * d))


def attention_macs_per_token(architecture: dict) -> int:
    """The USEFUL work of the flash kernels: scores and values under the
    mask at the published head dim."""
    a = architecture
    return (_count(a, "full_attention") * (a["train_context"] // 2)
            * a["num_attention_heads"] * 2 * head_dim(a))


def expert_macs_per_row(architecture: dict) -> int:
    """One routed expert on one row: its three matrices."""
    return 3 * architecture["hidden_size"] * architecture["moe_intermediate_size"]


def held_expert_macs_per_token(architecture: dict, held_share=None) -> float:
    """The held experts' rows a token: ``held_share`` of the
    ``num_experts_per_tok`` choices in every mixture."""
    a = architecture
    if held_share is None:
        held_share = a["experts_held"] / a["num_experts"]
    return (mixtures(a) * a["num_experts_per_tok"] * held_share
            * expert_macs_per_row(a))


def forward_macs_per_item(architecture: dict) -> float:
    a = architecture
    d = a["hidden_size"]
    return (
        projection_macs_per_token(a) + attention_macs_per_token(a)
        + a["num_dense_layers"] * 3 * d * a["intermediate_size"]
        + mixtures(a) * d * a["num_experts"]
        + held_expert_macs_per_token(a)
        + d * a["vocab_held"]
    )


def short_conv_gate_bytes_per_token(architecture: dict, itemsize: int = 2) -> int:
    """Bytes a perfect fusion of the gates and the filter moves a token,
    forward (3 d in, d out) and backward (4 d in, 3 d out), over the ``conv``
    layers, at ``itemsize`` bytes an element (2: bfloat16)."""
    a = architecture
    return _count(a, "conv") * 11 * a["hidden_size"] * itemsize
