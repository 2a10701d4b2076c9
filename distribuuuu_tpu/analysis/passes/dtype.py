"""Dtype-promotion lint: f32 ops fed by bf16 values outside the
known-safe scopes.

With ``DEVICE.COMPUTE_DTYPE=bfloat16`` the model body is meant to run in
bf16 end to end; every bf16→f32 convert in the LOWERED program is a
place where compute silently leaves the fast path (f32 doubles both the
MXU cost and the bytes of everything downstream of it). Some promotions
are *correct by design* and stay: BN/LayerNorm statistics (variance in
bf16 underflows), the loss/log-softmax (accuracy of the reduction),
optimizer counters and LR schedules (integers/fp32 master params), and
the metrics. Those are the safe scopes; anything else is a finding with
the tensor shape (= the cost) and the resolved scope in the message.

The pass reads the lowered StableHLO with debug locations — trace-time
promotions the program author wrote — NOT the compiled HLO, where XLA
legitimately inserts f32 converts for collective numerics and fusion
internals that are nobody's bug.
"""

from __future__ import annotations

import re

from distribuuuu_tpu.analysis import hlo
from distribuuuu_tpu.analysis.findings import Finding, finding_key

PASS_ID = "dtype"

# scope/source patterns that are correct-by-design promotions
SAFE_SCOPES = (
    r"BatchNorm",          # BN batch statistics (variance underflows bf16)
    r"LayerNorm|RMSNorm",  # LN/RMS statistics, same argument
    r"GroupNorm",
    r"utils/metrics\.py",  # loss + accuracy (log-softmax reduction)
    r"cross_entropy|log_softmax|softmax|logsumexp|top_k",
    r"optimizer_update",   # fp32 master params / counters
    r"utils/optim\.py|utils/schedules\.py|optax",
    r"resilience/supervisor\.py",  # non-finite guard reads the f32 loss
    r"normalize_in_graph|transforms\.py",  # device-side normalization
    r"moe\.py|router",     # MoE router runs its softmax in f32 by design
    # the olmoe_*/ouro_* decoders keep a float32 residual stream by design
    # (models/olmoe.py, models/ouro.py docstrings): norms, rotary and the
    # stream's additions are float32, and every bf16 branch is upcast into
    # it, forward and transposed. A block under nn.remat resolves to the
    # line that applies it, so the file is the finest site there is
    r"models/(olmoe|ouro)\.py",
    # ... and so do the decoders that are one chip's share of a layer
    # (models/glm_moe.py's docstring; LFM2's, Trinity-Mini's and SDAR's
    # blocks are models/share.py's): a pre-norm block adds each bf16 branch
    # into the float32 stream on the block's own line, and recomputed, every
    # cast inside it resolves to the line of run_blocks that applies it
    r"models/share\.py",
    # the self-declaration convention: a DELIBERATE f32 region wraps
    # itself in jax.named_scope("<name>_fp32") at the promotion site
    # (attn_softmax_fp32, se_squeeze_fp32, …) — the code states the
    # numerical argument where it lives, and the lint reads it
    r"_fp32\b",
    # model head helpers (ViT._head): GAP-mean's internal f32
    # accumulation + the documented f32 head/loss boundary
    r"\._head\b",
)


# the fwd head/loss boundary: every zoo model upcasts its pooled
# features and runs the classifier head + loss in f32 by design
# (models/layers.head_dtype — "the loss boundary"); the cast sits at
# the model ROOT scope (no submodule between the model class and the
# convert), in the forward and in its autodiff transpose
_HEAD_BOUNDARY = re.compile(
    r"(?:jvp\(fwd\)|fwd|eval_fwd|transpose\(jvp\(fwd\)\))"
    r"/[A-Za-z_0-9]+/convert_element_type"
)


def _safe(scope: str) -> bool:
    return any(re.search(pat, scope) for pat in SAFE_SCOPES)


def run(bundle) -> list:
    import jax

    if bundle.geometry.get("compute_dtype") != "bfloat16":
        return []  # nothing to audit: the program computes in f32
    findings = []
    census = hlo.upcast_census(bundle.lowered_text)
    # fp32 master params: the transpose of each param's compute-dtype
    # downcast materializes that param's GRADIENT in f32 — mandatory for
    # the f32 optimizer state, recognized by shape (a transpose-scope
    # upcast at exactly a param shape is the grad cast, not a leak)
    param_shapes = {
        tuple(int(d) for d in leaf.shape)
        for leaf in jax.tree.leaves(bundle.state_in.params)
    }
    bundle.extras["upcasts"] = {
        "total": len(census),
        "unsafe": 0,
    }
    # aggregate per scope so one miswritten module line is one finding,
    # not one per block instance
    unsafe: dict = {}
    for up in census:
        if _safe(up["scope"]):
            continue
        dims = tuple(
            int(d) for d in up["shape"].split("x") if d.isdigit()
        )
        if "transpose(" in up["scope"] and dims in param_shapes:
            continue  # master-param grad cast (see above)
        if _HEAD_BOUNDARY.search(up["scope"]):
            continue  # the f32 head/loss boundary
        key = up["scope"] or f"<unattributed {up['shape']}>"
        slot = unsafe.setdefault(key, {"count": 0, "elements": 0,
                                       "shape": up["shape"]})
        slot["count"] += 1
        slot["elements"] += up["elements"]
    bundle.extras["upcasts"]["unsafe"] = sum(
        s["count"] for s in unsafe.values()
    )
    for scope, slot in sorted(unsafe.items()):
        skey = re.sub(r"[:/ ]+", ".", scope)[:120] or "unattributed"
        findings.append(Finding(
            pass_id=PASS_ID, severity="warning",
            location=f"{bundle.name}::{scope[:140]}",
            message=(
                f"{slot['count']} bf16→f32 upcast(s) "
                f"({slot['elements']} elements, e.g. shape "
                f"{slot['shape']}) outside the known-safe scopes at "
                f"{scope or '<unattributed>'} — compute leaves the bf16 "
                "path here; cast back or add the scope to SAFE_SCOPES "
                "with the numerical argument"
            ),
            waiver_key=finding_key(PASS_ID, bundle.name, skey),
        ))
    return findings
