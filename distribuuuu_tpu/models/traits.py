"""What shared code asks of an arch before, or without, building it.

The topology table, the batch spec tables, ``trainer.build_model_from_cfg``
and ``serve_net`` all decide by ``MODEL.ARCH`` alone. An arch answers them
with an :class:`ArchTraits` on its constructor (``fn.traits = ...``) instead
of having its name tested there; ``models.traits(arch)`` reads it, and an
arch that declares nothing is a BN-normalized image model any engine serves.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable


@dataclass(frozen=True)
class ArchTraits:
    # batches are [B, S] token ids: TOKEN_BATCH_TABLE, no image transforms
    token_batch: bool = False
    # normalizes with BatchNorm: takes ``bn_group`` and the ghost-BN checks
    batch_norm: bool = True
    # the mesh axes the arch lowers on; None leaves it to the topology
    # table's family rules
    mesh_axes: tuple[str, ...] | None = None
    # (cfg, topology) -> the constructor's kwargs beyond num_classes/dtype
    kwargs_from_cfg: Callable | None = None
    # why no engine serves it, as the rest of "serve_net: <arch> ..."; ""
    # where one does
    serve_refusal: str = ""
