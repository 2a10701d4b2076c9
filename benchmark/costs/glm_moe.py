"""Multiply-accumulates of one GLM-4.7-Flash forward pass PER TOKEN as one
chip of its expert-parallel group runs it, from the configuration's sizes
(``costs/common.py`` has the convention: 2 operations a MAC, a training step
is 3 forward passes, recomputation never counted).

A cell's item is a token. Per token and block (the ``layers`` of the trunk
and the MTP module's one): latent attention's five projections (d q_rank +
q_rank H (nope + rope) + d (kv_rank + rope) + kv_rank H (nope + v) + H v d)
and its two matmuls under the causal mask, S / 2 keys a token on average
over a full context of S = ``train_context``, H heads of score dim nope +
rope and value dim v. The leading dense layers: the gated MLP's three
matrices (3 d f). Every mixture: the router (d E), the shared experts (3 d
f_moe each) and the HELD experts' share of the ``num_experts_per_tok`` rows
a token: ``held_share`` of them, ``experts_held / n_routed_experts`` unless
the caller measured it (the step's ``moe_held_row_share``), 3 d f_moe a
row. The MTP module's projection (2 d d) and the two heads over the held
rows of the vocabulary (2 d V/n). The embedding is a lookup. Norms, rotary,
softmaxes, the sort and the loss are not counted.

The program recomputes every block in its backward (``models/glm_moe.py``),
a fourth forward of the blocks that this count leaves out by the
convention: ``models.mfu`` is the share of the peak that went into the
model's own arithmetic, and the MXU is busier than it says.
``attention_macs_per_token`` is the USEFUL work of the flash kernels, as
``costs/olmoe.py`` has it: the masked half of the diagonal tiles, the scores
the backward computes again and the forward kernel's second run are not in
it.
"""

from __future__ import annotations


def blocks(architecture: dict) -> int:
    return architecture["layers"] + architecture["num_nextn_predict_layers"]


def mixtures(architecture: dict) -> int:
    return blocks(architecture) - architecture["first_k_dense_replace"]


def projection_macs_per_token(architecture: dict) -> int:
    a = architecture
    d, h = a["hidden_size"], a["num_attention_heads"]
    nope, rope, v = a["qk_nope_head_dim"], a["qk_rope_head_dim"], a["v_head_dim"]
    return blocks(a) * (
        d * a["q_lora_rank"] + a["q_lora_rank"] * h * (nope + rope)
        + d * (a["kv_lora_rank"] + rope) + a["kv_lora_rank"] * h * (nope + v)
        + h * v * d)


def attention_macs_per_token(architecture: dict) -> int:
    a = architecture
    score_and_value = a["qk_nope_head_dim"] + a["qk_rope_head_dim"] + a["v_head_dim"]
    return (blocks(a) * (a["train_context"] // 2) * a["num_attention_heads"]
            * score_and_value)


def expert_macs_per_row(architecture: dict) -> int:
    """One routed (or shared) expert on one row: its three matrices."""
    return 3 * architecture["hidden_size"] * architecture["moe_intermediate_size"]


def held_expert_macs_per_token(architecture: dict, held_share=None) -> float:
    """The held experts' rows a token: ``held_share`` of the
    ``num_experts_per_tok`` choices in every mixture."""
    a = architecture
    if held_share is None:
        held_share = a["experts_held"] / a["n_routed_experts"]
    return (mixtures(a) * a["num_experts_per_tok"] * held_share
            * expert_macs_per_row(a))


def forward_macs_per_item(architecture: dict) -> float:
    a = architecture
    d = a["hidden_size"]
    heads = 1 + a["num_nextn_predict_layers"]
    return (
        projection_macs_per_token(a) + attention_macs_per_token(a)
        + a["first_k_dense_replace"] * 3 * d * a["intermediate_size"]
        + mixtures(a) * (d * a["n_routed_experts"]
                         + a["n_shared_experts"] * expert_macs_per_row(a))
        + held_expert_macs_per_token(a)
        + a["num_nextn_predict_layers"] * 2 * d * d
        + heads * d * a["vocab_held"]
    )
