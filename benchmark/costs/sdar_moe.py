"""Multiply-accumulates of one SDAR-30B-A3B-Chat training forward pass PER
DATA TOKEN as one chip of its expert-parallel group runs it, from the
configuration's sizes (``costs/common.py`` has the convention: 2 operations
a MAC, a training step is 3 forward passes, recomputation never counted).

A cell's item is a DATA token: one position of a sequence of S =
``train_context`` tokens. The block-diffusion pass runs every layer over TWO
rows a data token, the noised copy's and the clean copy's, so per data token
and layer: the four projections on both rows (q and o d H D each, k and v d
G D each; H query heads on G key/value heads of D = ``head_dim``), the
router on both rows (d E) and the HELD experts' share of both rows'
``num_experts_per_tok`` choices: ``held_share`` of them,
``experts_held / num_experts`` unless the caller measured it (the step's
``moe_held_row_share``, which is over all 2S rows), 3 d f_moe a row. The two
matmuls under the mask at the published head dim are counted by the (query,
key) pairs the mask KEEPS, never by the tiles a kernel visits: with B =
``block_length`` and n = S / B blocks, a noised row of block b reads its own
block's B noised keys and the b B clean keys before it, a clean row of block
b the (b + 1) B clean keys up to its own block's end: B^2 (n + n (n - 1) / 2
+ n (n + 1) / 2) = S (S + B) pairs a sequence, S + B keys a data token over
its two rows (8196 at S = 8192, B = 4; a noised row (S - B) / 2 + B and a
clean row (S + B) / 2 on average). The untied head sees the NOISED rows
alone: d V/n a data token (the embedding is a lookup). Norms, rotary,
softmaxes, the draws, the sort and the loss are not counted.
"""

from __future__ import annotations


def mixtures(architecture: dict) -> int:
    return architecture["layers"]


def projection_macs_per_token(architecture: dict) -> int:
    """q, k, v and o on both rows, every layer."""
    a = architecture
    d, dim = a["hidden_size"], a["head_dim"]
    q, kv = a["num_attention_heads"] * dim, a["num_key_value_heads"] * dim
    return a["layers"] * 2 * (2 * d * q + 2 * d * kv)


def keys_per_token(architecture: dict) -> float:
    """Keys a data token's two rows read under the mask, together."""
    return architecture["train_context"] + architecture["block_length"]


def attention_macs_per_token(architecture: dict) -> float:
    """The USEFUL work of the flash kernels, every layer: scores and values
    over the pairs the block-diffusion mask keeps."""
    a = architecture
    return (a["layers"] * keys_per_token(a) * a["num_attention_heads"]
            * 2 * a["head_dim"])


def expert_macs_per_row(architecture: dict) -> int:
    """One routed expert on one row: its three matrices."""
    return 3 * architecture["hidden_size"] * architecture["moe_intermediate_size"]


def held_expert_macs_per_token(architecture: dict, held_share=None) -> float:
    """The held experts' rows a data token: ``held_share`` of the
    ``num_experts_per_tok`` choices of both its rows in every mixture."""
    a = architecture
    if held_share is None:
        held_share = a["experts_held"] / a["num_experts"]
    return (mixtures(a) * 2 * a["num_experts_per_tok"] * held_share
            * expert_macs_per_row(a))


def forward_macs_per_item(architecture: dict) -> float:
    a = architecture
    d = a["hidden_size"]
    return (
        projection_macs_per_token(a) + attention_macs_per_token(a)
        + mixtures(a) * 2 * d * a["num_experts"]
        + held_expert_macs_per_token(a)
        + d * a["vocab_held"]
    )
