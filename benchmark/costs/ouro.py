"""Multiply-accumulates of one Ouro forward pass PER TOKEN, from the
configuration's sizes (``costs/common.py`` has the convention: 2 operations a
MAC, a training step is 3 forward passes, recomputation never counted).

A cell's item is a token, and a token goes through the L layers R =
``total_ut_steps`` times. Per token and block application: the four
projections (4 d^2); attention's two matmuls under the causal mask, S / 2
keys a token on average over a full context of S = ``train_context``
(2 * S/2 * d); the gated MLP's three matrices (3 d f). Per pass: the head
(d V) and the exit gate (d). The embedding is a lookup. Norms, rotary,
softmaxes, the exit distribution and the loss are not counted.

The program recomputes every block application in its backward
(``models/ouro.py``), a fourth forward of the blocks that this count leaves
out by the convention: ``models.mfu`` is the share of the peak that went
into the model's own arithmetic, and the MXU is busier than it says.
``attention_macs_per_token`` is the USEFUL work of the flash kernels, as
``costs/olmoe.py`` has it (the forward kernel's second run in the
recomputation is not counted either).
"""

from __future__ import annotations


def block_applications(architecture: dict) -> int:
    return architecture["total_ut_steps"] * architecture["layers"]


def attention_macs_per_token(architecture: dict) -> int:
    a = architecture
    return block_applications(a) * 2 * (a["train_context"] // 2) * a["hidden_size"]


def mlp_macs_per_token(architecture: dict) -> int:
    a = architecture
    return block_applications(a) * 3 * a["hidden_size"] * a["intermediate_size"]


def forward_macs_per_item(architecture: dict) -> int:
    a = architecture
    d = a["hidden_size"]
    return (block_applications(a) * 4 * d * d + attention_macs_per_token(a)
            + mlp_macs_per_token(a)
            + a["total_ut_steps"] * (d * a["vocab_size"] + d))
