"""Multiply-accumulates of one Trinity-Mini forward pass PER TOKEN as one
chip of its expert-parallel group runs it, from the configuration's sizes
(``costs/common.py`` has the convention: 2 operations a MAC, a training step
is 3 forward passes, recomputation never counted).

A cell's item is a token. ``architecture["layer_types"]`` lists the layers
that are run and ``num_dense_layers`` how many of them, from the first,
carry the dense FFN. Per token and layer, of either kind: the five
projections (q and the output's gate d H D each, k and v d G D each, o H D
d; H query heads on G key/value heads of D = ``head_dim``) and the two
matmuls under the mask at the published head dim, counted by the (query,
key) pairs the mask KEEPS over a full context of S = ``train_context``: a
``full_attention`` layer S / 2 keys a token, a ``sliding_attention`` layer
``(W (W + 1) / 2 + (S - W) W) / S`` of them (W = ``sliding_window``: the
first W rows see what a causal mask leaves them, every later row W keys;
1792.125 at S = 8192, W = 2048). Never the tiles a kernel visits: the part
of the two crossed tiles a row of blocks that the mask empties is the
kernels' waste, not work. A dense layer's gated FFN (3 d f); every
mixture's router (d E), its shared experts (3 d f_moe each) and the HELD
experts' share of the ``num_experts_per_tok`` rows a token: ``held_share``
of them, ``experts_held / num_experts`` unless the caller measured it (the
step's ``moe_held_row_share``), 3 d f_moe a row; the untied head over the
held rows of the vocabulary (d V/n; the embedding is a lookup). Norms, the
gate's sigmoid and product, rotary, softmaxes, the sort and the loss are not
counted.
"""

from __future__ import annotations


def _count(architecture: dict, kind: str) -> int:
    return list(architecture["layer_types"]).count(kind)


def mixtures(architecture: dict) -> int:
    return len(architecture["layer_types"]) - architecture["num_dense_layers"]


def projection_macs_per_token(architecture: dict) -> int:
    """q, k, v, o and the output's gate, every layer."""
    a = architecture
    d, dim = a["hidden_size"], a["head_dim"]
    q, kv = a["num_attention_heads"] * dim, a["num_key_value_heads"] * dim
    return len(a["layer_types"]) * (3 * d * q + 2 * d * kv)


def window_keys_per_token(architecture: dict) -> float:
    """Keys a token of a full context reads through the window, on average."""
    s, w = architecture["train_context"], architecture["sliding_window"]
    w = min(w, s)
    return (w * (w + 1) / 2 + (s - w) * w) / s


def _score_and_value(architecture: dict, keys: float) -> float:
    return keys * architecture["num_attention_heads"] * 2 * architecture["head_dim"]


def window_attention_macs_per_token(architecture: dict) -> float:
    """The USEFUL work of the flash kernels in the sliding layers."""
    a = architecture
    return _count(a, "sliding_attention") * _score_and_value(
        a, window_keys_per_token(a))


def attention_macs_per_token(architecture: dict) -> float:
    """The USEFUL work of the flash kernels, every layer: the pairs the
    window keeps and the causal half of the full layers'."""
    a = architecture
    return window_attention_macs_per_token(a) + _count(
        a, "full_attention") * _score_and_value(a, a["train_context"] // 2)


def expert_macs_per_row(architecture: dict) -> int:
    """One routed (or shared) expert on one row: its three matrices."""
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
        + mixtures(a) * (d * a["num_experts"]
                         + a["num_shared_experts"] * expert_macs_per_row(a))
        + held_expert_macs_per_token(a)
        + d * a["vocab_held"]
    )
