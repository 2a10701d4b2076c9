"""Configuration system.

A from-scratch, yacs-compatible ``CfgNode`` built on pyyaml, providing the
same public surface the reference uses (ref: /root/reference/distribuuuu/
config.py:7-100): an attribute-access config tree with ``freeze``/``defrost``,
``merge_from_file`` (YAML), ``merge_from_list`` (dotted-key CLI overrides),
``dump``, and type-checked merges — so every shipped ``config/*.yaml`` parses
unchanged.

TPU-specific additions live under new top-level keys (``DEVICE``, ``MESH``,
``DATA``) which default sensibly and never collide with the reference schema.
"""

from __future__ import annotations

import argparse
import copy
import os
import sys

import yaml

__all__ = ["CfgNode", "cfg", "load_cfg_fom_args", "merge_from_file", "dump_cfg", "reset_cfg"]


_VALID_TYPES = (tuple, list, str, int, float, bool, type(None))


class CfgNode(dict):
    """A dict subclass with attribute access, freezing, and typed merges.

    API-compatible with ``yacs.config.CfgNode`` for the subset the reference
    framework exercises (ref: config.py usage + train_net.py:8 freeze).
    """

    _FROZEN = "__frozen__"

    def __init__(self, init_dict=None):
        init_dict = {} if init_dict is None else init_dict
        super().__init__()
        object.__setattr__(self, CfgNode._FROZEN, False)
        for k, v in init_dict.items():
            if isinstance(v, dict) and not isinstance(v, CfgNode):
                v = CfgNode(v)
            dict.__setitem__(self, k, v)

    # -- attribute access ---------------------------------------------------
    def __getattr__(self, name):
        if name in self:
            return self[name]
        raise AttributeError(f"Config key not found: {name}")

    def __setattr__(self, name, value):
        if self.is_frozen():
            raise AttributeError(
                f"Attempted to set {name} to {value}, but CfgNode is frozen"
            )
        dict.__setitem__(self, name, value)

    def __setitem__(self, name, value):
        if self.is_frozen():
            raise AttributeError(
                f"Attempted to set {name} to {value}, but CfgNode is frozen"
            )
        dict.__setitem__(self, name, value)

    # -- freezing -----------------------------------------------------------
    def is_frozen(self):
        return object.__getattribute__(self, CfgNode._FROZEN)

    def freeze(self):
        self._set_frozen(True)

    def defrost(self):
        self._set_frozen(False)

    def _set_frozen(self, frozen):
        object.__setattr__(self, CfgNode._FROZEN, frozen)
        for v in self.values():
            if isinstance(v, CfgNode):
                v._set_frozen(frozen)

    # -- merging ------------------------------------------------------------
    def clone(self):
        return copy.deepcopy(self)

    def merge_from_file(self, cfg_filename):
        with open(cfg_filename, "r") as f:
            loaded = yaml.safe_load(f)
        if loaded is None:
            return
        self._merge_dict(CfgNode(loaded), [])

    def merge_from_other_cfg(self, other):
        self._merge_dict(other, [])

    def merge_from_list(self, cfg_list):
        if len(cfg_list) % 2 != 0:
            raise ValueError(
                f"Override list has odd length: {cfg_list}; it must be (key, value) pairs"
            )
        for full_key, v in zip(cfg_list[0::2], cfg_list[1::2]):
            d = self
            key_parts = full_key.split(".")
            for sub in key_parts[:-1]:
                if sub not in d:
                    raise KeyError(f"Non-existent key: {full_key}")
                d = d[sub]
            sub = key_parts[-1]
            if sub not in d:
                raise KeyError(f"Non-existent key: {full_key}")
            value = _decode_value(v)
            value = _check_and_coerce(value, d[sub], full_key)
            dict.__setitem__(d, sub, value)

    def _merge_dict(self, other, key_path):
        for k, v in other.items():
            full_key = ".".join(key_path + [str(k)])
            if k not in self:
                raise KeyError(f"Non-existent config key: {full_key}")
            old = self[k]
            if isinstance(old, CfgNode):
                if not isinstance(v, (dict, CfgNode)):
                    raise ValueError(
                        f"Cannot merge non-dict value into config section {full_key}"
                    )
                old._merge_dict(CfgNode(v) if not isinstance(v, CfgNode) else v, key_path + [str(k)])
            else:
                value = _check_and_coerce(copy.deepcopy(v), old, full_key)
                dict.__setitem__(self, k, value)

    # -- serialization ------------------------------------------------------
    def to_dict(self):
        out = {}
        for k, v in self.items():
            out[k] = v.to_dict() if isinstance(v, CfgNode) else v
        return out

    def dump(self, **kwargs):
        kwargs.setdefault("default_flow_style", None)
        return yaml.safe_dump(self.to_dict(), **kwargs)

    def __repr__(self):
        return f"CfgNode({dict.__repr__(self)})"

    def __str__(self):
        return self.dump()


def _decode_value(v):
    """Parse a CLI string into a Python literal (yaml rules, like yacs)."""
    if not isinstance(v, str):
        return v
    try:
        return yaml.safe_load(v)
    except yaml.YAMLError:
        return v


def _check_and_coerce(new, old, full_key):
    """Type-check a replacement value, with yacs-style coercions."""
    old_type, new_type = type(old), type(new)
    if old_type is new_type or old is None or new is None:
        return new
    # yacs-sanctioned casts
    if isinstance(old, (tuple, list)) and isinstance(new, (tuple, list)):
        return old_type(new)
    if isinstance(old, float) and isinstance(new, int) and not isinstance(new, bool):
        return float(new)
    if isinstance(old, int) and isinstance(new, float):
        # allow e.g. WEIGHT_DECAY-style float into int slot only if integral
        if float(new).is_integer():
            return int(new)
    if isinstance(old, float) and isinstance(new, str):
        # yaml 1.1 wants a dot in a float: "3e-05", python's own repr of one,
        # comes back from it as a string
        try:
            return float(new)
        except ValueError:
            pass
    raise ValueError(
        f"Type mismatch ({old_type} vs {new_type}) for config key {full_key}: "
        f"cannot replace {old!r} with {new!r}"
    )


# ---------------------------------------------------------------------------
# Default config tree. Mirrors the reference defaults (ref: config.py:10-63)
# with TPU-native additions under DEVICE / MESH / DATA.
# ---------------------------------------------------------------------------

_C = CfgNode()
cfg = _C

# ------------------------------- model -------------------------------------
_C.MODEL = CfgNode()
_C.MODEL.ARCH = "resnet18"
_C.MODEL.NUM_CLASSES = 1000
_C.MODEL.PRETRAINED = False
# BatchNorm statistic regime. SYNCBN True ⇒ stats over the GLOBAL batch
# (cross-replica, ≙ torch SyncBatchNorm, ref: trainer.py:131). False (the
# reference default — every published baseline) ⇒ "ghost" BN: stats over
# independent BN_GROUP-sample groups, reproducing the reference's per-GPU
# statistics on any chip count.
_C.MODEL.SYNCBN = False
# Ghost-BN group size when SYNCBN is False. 0 ⇒ TRAIN.BATCH_SIZE (the
# per-chip batch — exactly the reference's per-GPU BN batch). Must divide
# the (micro-)batch each training forward sees.
# (Running-stats decay is per-module — torch-parity 0.9; the trace-time
# env knob DISTRIBUUUU_BN_MOMENTUM overrides it globally for eval-
# stability experiments, PERF.md r5 "stabilizing the convergence
# artifact".)
_C.MODEL.BN_GROUP = 0
_C.MODEL.WEIGHTS = None
# Use randomly generated fake data (no dataset on disk needed).
_C.MODEL.DUMMY_INPUT = False
# Mixture-of-experts knobs for the *_moe archs (ops/moe.py expert
# parallelism over the ``model`` mesh axis).
_C.MODEL.MOE = CfgNode()
_C.MODEL.MOE.NUM_EXPERTS = 8
_C.MODEL.MOE.TOP_K = 2
# Every Nth block gets the MoE FFN (2 = the GShard/ViT-MoE placement).
_C.MODEL.MOE.EVERY = 2
# λ for the switch-transformer load-balancing aux loss added to the task
# loss (0 disables; without it top-k routing collapses onto few experts).
_C.MODEL.MOE.AUX_WEIGHT = 0.01
# Execution strategy: "partial" = local experts on all tokens + one psum
# (exact, O(E/n) compute/token — right for small E); "dispatch" =
# switch-style all_to_all routing at fixed capacity (O(top_k)
# compute/token — the scalable path for large E; over-capacity
# assignments drop, logged as the ``moe_dropped`` train metric).
_C.MODEL.MOE.IMPL = "partial"
# Dispatch capacity: each expert takes ceil(T_shard·top_k/E × this) slots
# per source rank. Raise toward E/top_k for exactness, lower for speed.
_C.MODEL.MOE.CAPACITY_FACTOR = 2.0
# Weight of the router z-loss mean(logsumexp(router logits)^2) that the
# olmoe_* archs sow (ops/moe.router_z_loss); 0 disables.
_C.MODEL.MOE.Z_WEIGHT = 0.0
# beta of the ouro_* archs' expected-exit loss (models/ouro.py):
# mean[sum_t p_t nll_t - beta H(p)] over the exit distribution p the model's
# gate emits; the entropy term keeps the gate from collapsing onto one pass.
_C.MODEL.EXIT_ENTROPY_WEIGHT = 0.05

# ------------------------------- training ----------------------------------
_C.TRAIN = CfgNode()
_C.TRAIN.DATASET = "./data/ILSVRC/"
_C.TRAIN.SPLIT = "train"
_C.TRAIN.IM_SIZE = 224
# Per-process (per-host) batch size, matching the reference's per-GPU meaning.
_C.TRAIN.BATCH_SIZE = 32
_C.TRAIN.AUTO_RESUME = True
_C.TRAIN.LOAD_OPT = True
# Preemption-safe training (utils/preempt.py): on SIGTERM the epoch loop
# stops at the next dispatch boundary and writes a mid-epoch checkpoint
# that AUTO_RESUME prefers — the interrupted epoch re-runs from the
# preserved params/optimizer state instead of the last epoch boundary.
_C.TRAIN.PREEMPT_SAVE = True
_C.TRAIN.WORKERS = 4
_C.TRAIN.PIN_MEMORY = True
_C.TRAIN.PRINT_FREQ = 30
_C.TRAIN.TOPK = 5
# Device-side prefetch ring depth (data/loader.device_prefetch): the H2D
# transfer of batches k+1..k+PREFETCH_DEVICE is dispatched while the
# compiled step still works on batch k, so transfers never serialize
# behind steps. Applies to train_epoch AND validate. 0 = the unoverlapped
# put-then-step order. Results are bit-identical at every depth (same
# device_put order, same step order — tests/test_overlap.py); only dispatch
# timing moves. HBM cost: depth extra device batches resident.
_C.TRAIN.PREFETCH_DEVICE = 2
# Per-batch stage-boundary timeline records (kind="timeline" in
# {OUT_DIR}/metrics.jsonl — utils/jsonlog.timeline_log): decode/augment,
# host assembly, H2D dispatch, and step dispatch monotonic timestamps for
# every batch (train + eval). Feed them to tools/overlap_report.py for
# exact wall-time attribution. Primary process only; one small JSON line
# per batch.
_C.TRAIN.TIMELINE = True
# Rematerialize (jax.checkpoint via nn.remat) ResNet stages 1-2 — the
# largest-activation stages: their block activations are not stored for
# the backward but recomputed, trading cheap MXU flops for HBM traffic on
# a 93%-bus-bound step (PERF.md "Where the time goes"; the one untried
# roofline lever, VERDICT r5 #3). Exact same math (step-equivalence:
# tests/test_remat.py). resnet/resnext/wide_resnet family only (densenet
# always remats its dense layers; other archs refuse the knob loudly).
# A/B on hardware: benchmark/run.py --workload … --set (PERF.md §2).
_C.TRAIN.REMAT = False
# Split each optimizer step's batch into this many sequential micro-batches,
# summing gradients in-graph before the (single) update. Runs the
# reference's large-global-batch recipes (README.md:210-211 — 8192/16384
# over 64 GPUs) on far fewer chips: BATCH_SIZE stays the *optimizer* batch
# per chip; HBM holds only BATCH_SIZE/GRAD_ACCUM_STEPS activations at once.
# Gradient math is exact (mean-CE grads average over equal micro-batches);
# BN batch stats are per-micro-batch — the same semantics torch DDP +
# gradient accumulation has (stats over what the device sees per forward).
_C.TRAIN.GRAD_ACCUM_STEPS = 1
# Non-finite loss policy (resilience/supervisor.py). "raise" fails fast at
# the next metric flush (honest failure beats silently training garbage);
# "skip" discards the poisoned update IN-GRAPH (pre-step state selected,
# step cursor still advances) and logs the skipped step — for rare bad
# batches; "rollback" reloads the last intact checkpoint and re-runs
# (TRAIN.MAX_ROLLBACKS attempts) — for transient corruption.
_C.TRAIN.NONFINITE = "raise"
_C.TRAIN.MAX_ROLLBACKS = 2
# Heartbeat watchdog (resilience/supervisor.Heartbeat): warn + emit a
# kind="stall" metrics record when no train-loop progress lands within
# this many seconds — a wedged collective, dead peer host, or hung
# storage would otherwise hang silently forever. 0 disables (default:
# first-step compiles legitimately take minutes on some backends; set
# ~2-5× your steady-state fold wall in production).
_C.TRAIN.STALL_TIMEOUT = 0.0

# ------------------------------- testing -----------------------------------
_C.TEST = CfgNode()
_C.TEST.DATASET = "./data/ILSVRC/"
_C.TEST.SPLIT = "val"
_C.TEST.IM_SIZE = 256
_C.TEST.BATCH_SIZE = 200
_C.TEST.PRINT_FREQ = 10

# ------------------------------- cudnn (compat) -----------------------------
# Accepted for YAML compatibility (ref: config.py:38-40); on TPU these map to
# XLA autotune/determinism behavior (see runtime.apply_backend_flags).
_C.CUDNN = CfgNode()
_C.CUDNN.BENCHMARK = True
_C.CUDNN.DETERMINISTIC = False

# ------------------------------- optimizer ----------------------------------
_C.OPTIM = CfgNode()
# "sgd" (the reference's recipe) or "adamw" (typical for the ViT archs).
_C.OPTIM.OPTIMIZER = "sgd"
_C.OPTIM.BETA1 = 0.9
_C.OPTIM.BETA2 = 0.999
_C.OPTIM.BASE_LR = 0.1
_C.OPTIM.LR_POLICY = "cos"
_C.OPTIM.LR_MULT = 0.1
_C.OPTIM.MAX_EPOCH = 100
_C.OPTIM.MOMENTUM = 0.9
_C.OPTIM.DAMPENING = 0.0
_C.OPTIM.NESTEROV = True
_C.OPTIM.WEIGHT_DECAY = 5e-5
_C.OPTIM.WARMUP_FACTOR = 0.1
_C.OPTIM.WARMUP_EPOCHS = 0
_C.OPTIM.STEPS = []
_C.OPTIM.MIN_LR = 0.0

# SGD momentum-buffer dtype: "float32" (torch-exact) or "bfloat16"
# (fp32 master params + half-traffic momentum; utils/optim.py)
_C.OPTIM.MOMENTUM_DTYPE = "float32"

# ------------------------------- language model -----------------------------
# Decoder-only LM workload plane (distribuuuu_tpu/lm/, models/gpt.py —
# ISSUE 12). The gpt_* archs train through the SAME trainer/partition
# lowering the image zoo uses: batches are {"image": tokens [B, S] int32,
# "label": next-tokens [B, S] int32, "mask": [B]} from token shards
# (DATA.FORMAT=tokens), the loss is the same cross-entropy — computed per
# token — and placement comes from the LM SpecTable rules
# (parallel/partition/specs.LM_TABLE).
_C.LM = CfgNode()
# Trained context length. Token shards must be packed with
# ``--pack-len SEQ_LEN`` (each record holds SEQ_LEN+1 tokens: input =
# [:-1], next-token targets = [1:]); a mismatch is refused at loader
# construction with the repack command. Also the learned-position table
# size, so generation prompts + new tokens must fit under it.
_C.LM.SEQ_LEN = 256
# Depth override for archs whose depth is a knob (olmoe_*, ouro_*, glm_*, lfm2_*,
# trinity_mini/afmoe_tiny, sdar_*): 0
# keeps the arch's own. One chip holds 1 of OLMoE-1B-7B's 16 layers, 8 of
# Ouro-2.6B's 48, or 1 + 4 of GLM-4.7-Flash's 47 as an eighth of each, with
# its optimizer state (PERF.md section 4).
_C.LM.LAYERS = 0
# One chip's share of an expert-parallel group (the glm_* archs,
# models/glm_moe.py): SHARE_CHIPS chips share every layer and this program
# is rank SHARE_RANK of them. It holds 1/SHARE_CHIPS of each layer's routed
# experts and of the embedding's and head's MODEL.NUM_CLASSES rows (token
# ids must lie in its rows); attention and the shared expert are whole. The
# exchange of tokens across the group's chips is not run. 0 keeps the arch's
# own (1: the whole model).
_C.LM.SHARE_CHIPS = 0
# Which of the LM.SHARE_CHIPS chips that share a layer this program is: it
# holds that rank's block of the experts and of the vocabulary's rows.
_C.LM.SHARE_RANK = 0
# The published layer a chip's stage starts at (the lfm2_*, afmoe and sdar_* archs,
# models/lfm2_moe.py, models/afmoe.py, models/sdar_moe.py): its LM.LAYERS layers are the published ``layer_types``
# from here on, so a cut keeps the pattern's order and the leading dense
# layers that fall into it. 1 with LM.LAYERS 5 is layers 1..5 of
# LFM2-24B-A2B: one dense layer, then conv, attention, conv, conv mixtures.
_C.LM.FIRST_LAYER = 0
# Whether a block of the lfm2_*, afmoe and sdar_* archs is recomputed in the backward from its
# float32 input and what the flash backward kernel reads (as the ouro_* and
# glm_* archs always do), or keeps every activation: the first decoder here
# whose state (7.5 GB) may leave a step room for them (PERF.md section 4).
_C.LM.RECOMPUTE = True
# -------------------------------- generation --------------------------------
# Autoregressive serving (lm/generate.py): paged per-request KV cache,
# prefill/decode split, continuous batching. The serve engine's AOT-bucket
# idea generalizes to (batch, cache-len) TILES: decode is compiled once
# per (batch_tile, cache_tile) pair and a step runs the smallest tile
# covering the live slots / longest sequence, so steady-state decoding
# never recompiles.
_C.GENERATE = CfgNode()
# Hard cap on generated tokens per request (requests may ask for fewer).
_C.GENERATE.MAX_NEW_TOKENS = 64
# Batch tiles: concurrent-sequence capacities decode is compiled for.
# The largest is the continuous-batching slot count. [] ⇒ powers of two
# up to 4.
_C.GENERATE.BATCH_TILES = []
# KV-cache length tiles. The largest must cover PROMPT_LEN + MAX_NEW_TOKENS
# (validated with the exact arithmetic at engine build) and every tile
# must be ≤ LM.SEQ_LEN (positions beyond the learned table don't exist).
# [] ⇒ [LM.SEQ_LEN].
_C.GENERATE.CACHE_TILES = []
# Longest admissible prompt (tokens). Prefill pads to this length.
_C.GENERATE.PROMPT_LEN = 64
# Chunked paged prefill (lm/generate.py, ISSUE 19): > 0 streams each
# prompt into its KV-cache page in fixed CHUNK_PREFILL-token
# prefill-shaped calls — a long prompt needs no wide prefill bucket, and
# the admissible prompt length grows from PROMPT_LEN to whatever the
# largest cache tile can hold next to the request's max_new (+ SPECULATE.K).
# Every cache tile >= the chunk must be a chunk multiple (the final padded
# chunk writes ceil(plen/chunk)*chunk page positions — validated with the
# arithmetic at engine build). 0 = classic whole-prompt prefill.
_C.GENERATE.CHUNK_PREFILL = 0
# Token id that terminates a sequence early (the byte tokenizer's EOS
# document-boundary token). -1 = generate exactly max_new_tokens.
_C.GENERATE.EOS_ID = 256
# Scheduler admission poll (seconds) while decode slots are free.
_C.GENERATE.POLL_S = 0.002

# ------------------------------- sampling -----------------------------------
# Decode-time token selection (lm/generate.sample_token). The default is
# greedy (TEMPERATURE=0.0 ⇒ argmax, the pre-ISSUE-17 behaviour, and what
# the speculative greedy-identity pin runs against). Any sampled stream
# is REPLAYABLE: selection uses counter-based uniforms keyed on
# (SEED, stream, decision-index), never a stateful RNG, so the same seed
# in the ctrl frame reproduces the same token stream bit-for-bit on any
# replica regardless of batching — the serving-side twin of the
# (seed, epoch, idx) augmentation invariant.
_C.GENERATE.SAMPLE = CfgNode()
# 0.0 = greedy argmax (deterministic, ignores TOP_K/TOP_P/SEED).
# > 0 scales logits by 1/T before the softmax.
_C.GENERATE.SAMPLE.TEMPERATURE = 0.0
# Keep only the k highest-probability tokens (0 = off).
_C.GENERATE.SAMPLE.TOP_K = 0
# Nucleus sampling: keep the minimal prefix of the probability-sorted
# vocab with cumulative mass >= TOP_P (1.0 = off).
_C.GENERATE.SAMPLE.TOP_P = 1.0
# Default replay seed when a request carries none.
_C.GENERATE.SAMPLE.SEED = 0

# ----------------------------- speculative decode ---------------------------
# Draft-model speculation (lm/generate.py, ISSUE 17): a small draft
# model proposes SPECULATE.K tokens per round; the target verifies all K
# in ONE prefill-shaped call through the existing cache tiles (the
# roofline-native fix — decode is memory-bound, so K verify positions
# cost barely more than 1). Standard accept/reject + bonus-token rule:
# the emitted distribution is IDENTICAL to target-only decoding (greedy:
# exact token match for any draft; sampled: same seed ⇒ same stream).
_C.GENERATE.SPECULATE = CfgNode()
_C.GENERATE.SPECULATE.ENABLED = False
# Draft arch (a gpt_* zoo name, e.g. gpt_nano drafting for gpt_nano_moe).
# Must share the target's tokenizer identity + vocab (validated with the
# exact values in-message at engine build).
_C.GENERATE.SPECULATE.DRAFT_ARCH = ""
# Optional draft checkpoint (same restore path as MODEL.WEIGHTS).
_C.GENERATE.SPECULATE.DRAFT_WEIGHTS = ""
# Tokens proposed per round. Each round may append up to K+1 tokens, so
# the largest cache tile must hold PROMPT_LEN + MAX_NEW_TOKENS + K
# (validated with the sum named in-message).
_C.GENERATE.SPECULATE.K = 4

# ------------------------------- kernel tier ---------------------------------
# The Pallas kernel tier (ops/pallas/, ISSUE 13): hand-fused kernels for
# the memory-bound regions the cost ledger pinned, each behind its own
# impl knob. Values: "auto" (pallas on the TPU backend for supported
# shapes, XLA elsewhere — interpret mode is the CPU *test* path, never
# the auto choice), "pallas" (force; interpret mode off-TPU, falls back
# loudly on unsupported shapes), "xla" (the always-available escape
# hatch). Every resolution emits a kernel.select record; every
# forced-but-unsupported site a kernel.fallback record + one warning
# (run_report's `kernels` section shows what actually ran).
_C.KERNELS = CfgNode()
# Fused optimizer update (ops/pallas/opt_update.py): ONE HBM pass over
# params+grads+moments for SGD-momentum and AdamW, replacing the optax
# chain's re-read-per-transform traffic in the trainer's
# optimizer_update scope. Bit-exact vs the optax reference (pinned).
_C.KERNELS.OPT_UPDATE = "auto"
# Fused pointwise conv + BN-affine + activation for the eval/inference
# path (ops/pallas/conv_epilogue.py): 1x1/s1 ungrouped convs with a
# known activation (ResNet/RegNet bottleneck 1x1s, EfficientNet
# expand/project/head). Other shapes fall back per call site.
_C.KERNELS.CONV_EPILOGUE = "auto"
# Fused decode attention over the paged KV cache
# (ops/pallas/decode_attn.py): the T=1 decode step of lm/generate's
# CachedAttention — online softmax per (row, head), ragged block-skip,
# no fp32 cache copy, no [B,H,1,C] logits round-trip.
_C.KERNELS.DECODE_ATTN = "auto"
# Key-block height of the decode kernel (sublane dim; multiple of 8).
# Each GENERATE.CACHE_TILES entry must be a multiple of it (or fit in
# one block) — validated with the arithmetic at engine build.
_C.KERNELS.DECODE_BLOCK = 128

# ------------------------------- device / mesh (TPU-native additions) -------
_C.DEVICE = CfgNode()
# "tpu" | "cpu" | "auto" — jax platform selection.
_C.DEVICE.PLATFORM = "auto"
# Compute dtype for the model ("bfloat16" keeps the MXU fed; params stay fp32).
_C.DEVICE.COMPUTE_DTYPE = "bfloat16"
# Deterministic XLA ops (maps CUDNN.DETERMINISTIC intent onto TPU).
_C.DEVICE.DETERMINISTIC = False
# Attention implementation for attention archs. BoTNet: "auto" | "xla"
# (the fused Pallas path for the 196-token grid was retired r5 at 0.854×
# XLA e2e — PERF.md "BoTNet attention").
# ViT: "auto" picks the Pallas flash kernel (ops/flash_attention.py) for
# sequences ≥1024 tokens WHEN dropout is 0 (the kernel has no
# probability-dropout; with dropout>0 auto stays on dense XLA — at long
# sequences that materializes O(L²) logits, so prefer dropout 0 there),
# and dense XLA below; "flash" forces the kernel (blockwise-scan fallback
# off-TPU); "blockwise" is the lax.scan O(L·chunk) exact path; MESH.SEQ>1
# overrides with ring attention.
_C.DEVICE.ATTN_IMPL = "auto"
# Space-to-depth stem for the 7x7/s2-stem archs (resnet/resnext/wide_resnet/
# botnet): compute the stem as a 4x4/s1 conv over 2x2-block-folded input
# (models/layers.StemConv7x7). Exact same math and the SAME params/
# checkpoints either way. Measured NEUTRAL on v5e (XLA already lays the stem
# out well there — PERF.md); kept as a knob for TPU generations where the
# classic MLPerf gain applies.
_C.DEVICE.S2D_STEM = False

_C.MESH = CfgNode()
# Logical mesh axis sizes; -1 means "all remaining devices" on that axis.
# Axes: data (DP), model (TP), seq (SP/CP), pipe (PP — parallel/pp.py),
# expert (EP — a dedicated MoE dispatch axis, so expert parallelism can
# compose with tensor parallelism on a 3-axis dp×tp×ep mesh instead of
# riding the model axis). Any stanza is validated/classified up front by
# the partition-layer topology registry (parallel/partition/topology.py).
_C.MESH.DATA = -1
_C.MESH.MODEL = 1
_C.MESH.SEQ = 1
_C.MESH.PIPE = 1
# Expert-parallel axis for the *_moe archs. 1 (default) keeps the legacy
# behavior where expert tensors ride the ``model`` axis; >1 dedicates
# this axis to MoE dispatch (must divide MODEL.MOE.NUM_EXPERTS).
_C.MESH.EXPERT = 1
# GPipe microbatches per step when PIPE > 1 (parallel/pp.py schedule);
# 0 → 2 × PIPE. The per-data-shard batch must divide by it.
_C.MESH.MICROBATCH = 0
# ZeRO / FSDP redundancy elimination over the data axis (parallel/zero.py).
# 0 = off (DDP layout: params + optimizer state replicated per data rank,
# the reference's topology). 1 = optimizer state sharded over data, grads
# reduce-scattered into the sharded update (ZeRO-1). 3 = params also
# sharded at rest (FSDP; weights all-gathered at use). Same math in every
# stage — only per-rank memory and the compiled collective schedule change.
# Stage 2 is subsumed: in-graph gradients are transient, the stage-1
# constraint already materializes them sharded.
_C.MESH.ZERO = 0

# ------------------------------- ZeRO collective scheduling -----------------
# Latency-hiding controls for the ZeRO/FSDP collective schedule the
# partition layer derives (parallel/partition/specs.gather_schedule +
# lowering.train_step_body). The MESH.ZERO stage declares WHERE state
# rests; this node declares WHEN the spec-induced collectives run.
_C.ZERO = CfgNode()
# Collective/compute overlap. True (default): the step's ZeRO collectives
# (gather-once entry all-gathers, backward reduce-scatters, rest-layout
# re-gathers) are emitted as independent per-leaf ops with no serializing
# joins, so XLA's latency-hiding scheduler can run them concurrently with
# compute (proof artifact: trace_report's overlap-fraction rollup over
# the zero_*@data named scopes). False: an optimization_barrier joins
# each collective class before the consuming compute — the synchronous
# control arm of the A/B (tools/collective_bench.py --zero-ab); values
# are bit-identical either way (pinned: tests/test_zero_overlap.py).
_C.ZERO.OVERLAP = True
# ZeRO-3 gather-once prefetch depth, in parameter block-groups (the
# path-pattern groups specs.gather_groups derives — one group per
# numbered model block). -1 (default): the WHOLE FSDP param tree is
# all-gathered once at step entry (~1 gather/leaf instead of the per-use
# gather storm the PR 14 census priced at ~9.3/leaf; full-model gathered
# footprint lives through the step). N >= 1: only the first N groups are
# hoisted to step entry, later groups keep per-use gathering (bounds the
# gathered-live footprint on memory-tight configs at the cost of extra
# collectives). 0: no hoisting at all — the legacy per-use schedule, the
# escape hatch the census A/B compares against.
_C.ZERO.GATHER_AHEAD = -1

# ------------------------------- data pipeline -------------------------------
_C.DATA = CfgNode()
# Dataset storage format. "imagefolder" reads root/split/class/*.jpg one
# file per sample (the reference layout). "shards" streams indexed record
# shards packed by tools/make_shards.py (data/shards/): sequential IO from
# a few large files, a (seed, epoch)-only topology-independent sample
# order, and exact mid-epoch resume — the preemption checkpoint embeds the
# loader's global cursor, so a restart continues at the exact next batch
# instead of re-running the epoch. TRAIN/TEST.DATASET point at the shards
# root (the directory holding <split>/MANIFEST.json). "tokens" streams
# packed-sequence TOKEN shards (data/shards/tokens.py, packed by
# tools/make_token_shards.py) for the gpt_* LM archs: same record
# container, same window-shuffled order, same exact mid-epoch resume —
# batches become {"image": tokens [B,S] int32, "label": next-tokens}
# (LM.SEQ_LEN must match the pack length; refused with the repack
# command otherwise).
_C.DATA.FORMAT = "imagefolder"
# Shard-streaming order knobs (data/shards/order.py): storage order is cut
# into SHARDS_BLOCK-record sequential runs, the runs are permuted, and a
# SHARDS_WINDOW-sample shuffle buffer decorrelates neighbors. Bigger block
# = more sequential IO, less mixing; bigger window = better mixing, more
# read scatter. block=1 + window≥dataset restores the exact uniform
# shuffle of the imagefolder sampler.
_C.DATA.SHARDS_BLOCK = 64
_C.DATA.SHARDS_WINDOW = 1024
# Decode backend: "auto" uses the C++ kernel (native/decode.cc) when it
# builds, else PIL; "native" requires it; "pil" forces pure Python.
_C.DATA.BACKEND = "auto"
# Ship uint8 pixels and run (x/255 - mean)/std in-graph on device instead
# of on the host: 4× fewer host→device bytes per batch (PCIe)
# and less host CPU, numerically equivalent (pixels are uint8 after
# resampling either way — transforms.normalize_in_graph). Default ON
# since r4 (VERDICT r3 #6): measured strictly better (2.7× faster fenced
# H2D), eval metrics bit-identical on both decode backends
# (tests/test_device_normalize.py); False restores the reference's
# host-normalized float pipeline byte-for-byte.
_C.DATA.DEVICE_NORMALIZE = True
# Loader-level resilience (data/loader.py): a failed sample/batch decode
# is retried RETRIES times with exponential backoff starting at
# RETRY_BACKOFF_S (transient filesystem/network hiccups), then — with
# SKIP_CORRUPT — the corrupt sample is replaced by a good sample from the
# same batch and logged (logger warning + kind="data_error" metrics
# record) instead of aborting the whole epoch. False restores fail-stop.
_C.DATA.RETRIES = 2
_C.DATA.RETRY_BACKOFF_S = 0.05
_C.DATA.SKIP_CORRUPT = True

# ------------------------------- fault injection -----------------------------
# Deterministic failure injection (utils/faults.py) — every resilience
# recovery path is exercised by tests and tools/resilience_drill.py
# through these knobs. All hooks are no-ops unless ENABLED.
_C.FAULTS = CfgNode()
_C.FAULTS.ENABLED = False
# Compile `loss × where(step==NAN_STEP, NaN, 1)` into the train step:
# loss AND grads go non-finite at exactly that global step. -1 = off.
_C.FAULTS.NAN_STEP = -1
# Decode of this dataset sample index raises. "once": the first retry
# succeeds (transient I/O); "always": the loader's skip-and-log path
# engages (corrupt file). -1 = off.
_C.FAULTS.DECODE_ERROR_IDX = -1
_C.FAULTS.DECODE_ERROR_MODE = "once"
# SIGKILL process KILL_RANK at (KILL_EPOCH, KILL_AT_BATCH) — the
# uncatchable hard crash. -1 = off.
_C.FAULTS.KILL_RANK = -1
_C.FAULTS.KILL_EPOCH = 0
_C.FAULTS.KILL_AT_BATCH = -1
# Sleep STALL_S seconds at (STALL_EPOCH, STALL_AT_BATCH) so the heartbeat
# watchdog must flag. -1 = off.
_C.FAULTS.STALL_EPOCH = 0
_C.FAULTS.STALL_AT_BATCH = -1
_C.FAULTS.STALL_S = 0.0
# Deliver SIGTERM to this process at (PREEMPT_EPOCH, PREEMPT_AT_BATCH) —
# a deterministic scheduler preemption through the REAL signal handler
# (utils/preempt.py): the epoch loop exits at the next boundary and the
# mid-epoch checkpoint (with the shards data cursor) is written. -1 = off.
_C.FAULTS.PREEMPT_EPOCH = 0
_C.FAULTS.PREEMPT_AT_BATCH = -1
# Trigger RECOMPILE_N real backend compiles (trivial jits at distinct
# shapes — genuine kind="compile" events, nothing feeds the train step)
# at (RECOMPILE_EPOCH, RECOMPILE_AT_BATCH): the mid-run recompile storm
# a shape leak or bad bucket config causes, injectable so the monitor's
# recompile-storm alert is provable (tools/soak.py). -1 = off.
_C.FAULTS.RECOMPILE_EPOCH = 0
_C.FAULTS.RECOMPILE_AT_BATCH = -1
_C.FAULTS.RECOMPILE_N = 8
# Sleep SLOWDOWN_MS at EVERY batch boundary of SLOWDOWN_EPOCH — a
# sustained host-side throughput regression (thermal throttle, noisy
# neighbor, degraded storage) that must trip the monitor's
# throughput-regression rule without tripping the stall watchdog
# (keep SLOWDOWN_MS well under TRAIN.STALL_TIMEOUT). 0 = off.
_C.FAULTS.SLOWDOWN_EPOCH = 0
_C.FAULTS.SLOWDOWN_MS = 0.0
# SIGKILL the process from the async checkpoint committer thread AFTER
# ckpt_ep_{KILL_MID_ASYNC_SAVE}'s orbax payload is fully written but
# BEFORE its MANIFEST.json commits (CHECKPOINT.ASYNC) — the async-save
# crash window. The restart must quarantine the manifest-less directory
# and walk back to the previous intact checkpoint
# (tools/resilience_drill.py killed_mid_async_save). -1 = off.
_C.FAULTS.KILL_MID_ASYNC_SAVE = -1
# Truncate shard file #TRUNCATE_SHARD of the dataset split to 60% of its
# manifest size before the reader opens it (DATA.FORMAT=shards): kills the
# index footer and the tail records — the reader must recover the index by
# forward scan and the lost records must flow through DATA.SKIP_CORRUPT.
# -1 = off.
_C.FAULTS.TRUNCATE_SHARD = -1
# After ckpt_ep_{CORRUPT_EPOCH} commits: "truncate" halves its largest
# payload file (digest-mismatch path); "partial" deletes its manifest
# (crash-before-commit path). -1 = off.
_C.FAULTS.CORRUPT_EPOCH = -1
_C.FAULTS.CORRUPT_MODE = "truncate"
# Hold dispatch token #WEDGE_DISPATCH (the sequencer's global grant
# counter — asyncplane/sequencer.py) for WEDGE_S seconds before the
# dispatch proceeds: a wedged dispatcher thread. The sequencer's wedge
# watchdog (wired through supervisor.watch_blocking) must flag it as a
# kind="dispatch.wedge" record instead of the run hanging silently
# (tools/resilience_drill.py dispatch_wedge_recovery). -1 = off.
_C.FAULTS.WEDGE_DISPATCH = -1
_C.FAULTS.WEDGE_S = 0.0
# SIGKILL the PRIMARY host from its committer thread inside the
# multi-host async-commit crash window: AFTER every host arrived at the
# cross-host commit barrier (payload durable everywhere) but BEFORE
# MANIFEST.json commits (asyncplane/committer.py). The restart must
# quarantine the manifest-less dir and walk back
# (tools/resilience_drill.py multihost_async_save_kill). -1 = off.
_C.FAULTS.KILL_AT_COMMIT_BARRIER = -1
# Hold the LEADER's cross-host ring slot #WEDGE_RING for WEDGE_RING_S
# seconds BEFORE its order publishes (asyncplane/ring.py): followers
# starve at that slot past ASYNC.RING_DEADLINE_S, must flag
# kind="dispatch.wedge", and the trainer must run that epoch's eval
# synchronously — degraded, never hung (tools/resilience_drill.py
# ring_wedge_degrade). WEDGE_RING_S must exceed ASYNC.RING_DEADLINE_S or
# the wedge is unobservable (validated, utils/faults.validate_cfg).
# -1 = off.
_C.FAULTS.WEDGE_RING = -1
_C.FAULTS.WEDGE_RING_S = 0.0
# SIGKILL the PRIMARY inside the SHARDED async-commit crash window:
# every host's shard file durable + all barrier arrivals in, but
# MANIFEST.json not committed (the sharded protocol's analogue of
# KILL_AT_COMMIT_BARRIER). The restart must quarantine the manifest-less
# dir — shard files and all — and walk back
# (tools/resilience_drill.py sharded_save_kill_at_barrier). -1 = off.
_C.FAULTS.KILL_AT_SHARD_BARRIER = -1
# After ckpt_ep_{DROP_SHARD_FILE} fully commits: delete host
# DROP_SHARD_HOST's shards_host<r>.npz from it (primary's post-commit
# hook). The next restart's manifest verification must fail the digest
# walk, quarantine, and walk back; a DIRECT load must refuse with the
# recorded sharding named (tools/resilience_drill.py
# sharded_restore_fewer_shards). DROP_SHARD_HOST must be a valid host
# rank — validated against the live world at the hook site. -1 = off.
_C.FAULTS.DROP_SHARD_FILE = -1
_C.FAULTS.DROP_SHARD_HOST = 1

# ------------------------------- async dispatch plane ------------------------
# The dispatch sequencer (asyncplane/sequencer.py): the primitive that
# makes overlapped execution safe on multi-DEVICE processes. Two host
# threads dispatching SPMD programs concurrently can enqueue in
# different per-device orders; their collectives then cross-wait at the
# XLA rendezvous and the backend deadlocks (pinned: PR 10, reproduced
# deterministically on the 8-virtual-device CPU mesh). With SEQUENCER on
# (the default), every step dispatch from the trainer / concurrent-eval
# / snapshot threads first acquires a dispatch token — tokens are
# granted in ONE global order, and switching dispatch streams fences on
# the previous stream's completion — so every device observes one
# program sequence and the deadlock precondition is structurally
# removed. SEQUENCER False is the explicit escape hatch: it restores the
# PR 10 degrade-to-sync gates (concurrent eval single-device only, async
# commit single-host only) with a logged warning.
_C.ASYNC = CfgNode()
_C.ASYNC.SEQUENCER = True
# Cross-host commit barrier (multi-host CHECKPOINT.ASYNC): how long a
# host waits for its peers' barrier arrivals / the manifest commit
# before the background commit fails (surfaced as AsyncCommitError at
# the next join barrier — never silent, never a hang).
_C.ASYNC.BARRIER_TIMEOUT_S = 600.0
# Cross-host dispatch ring (multi-host concurrent eval, ISSUE 18): how
# long a FOLLOWER waits for the leader's published dispatch order before
# flagging kind="dispatch.wedge" and degrading that epoch's eval to
# synchronous (asyncplane/ring.py). The run keeps going either way; past
# BARRIER_TIMEOUT_S of zero leader progress the follower detaches to
# host-local order with an error log (a leader silent that long is a
# dead host — the group scheduler's restart to make). Seconds, > 0.
_C.ASYNC.RING_DEADLINE_S = 30.0

# ------------------------------- checkpointing ------------------------------
# Async execution plane (distribuuuu_tpu/asyncplane/): checkpoint commit off
# the trainer's critical path. With ASYNC on, a save blocks the epoch loop
# only for the device→host snapshot of the state tree (donation-safe copy);
# the orbax payload write, file digests, and the atomic MANIFEST.json commit
# run on a background committer thread. The PR 3 crash-consistency protocol
# is preserved exactly — the manifest is still written strictly LAST, so a
# process killed mid-async-save leaves a manifest-less directory that
# find_last_valid_checkpoint quarantines and walks back over. A join
# barrier runs before the next save (at most one commit in flight), at
# preemption (the committer drains inside the SIGTERM grace window before
# the preempt save), and at exit. Telemetry splits the cost:
# "ckpt_snapshot" spans are the on-path time, "ckpt_commit" spans the
# off-path time (tools/run_report.py reports both). Multi-host runs
# commit async too (ASYNC.SEQUENCER on, the default): hosts rendezvous
# on a cross-host commit barrier — per-host background threads, payload
# durable on every host, MANIFEST.json strictly last behind the
# all-hosts-durable barrier (asyncplane/committer.py; a host killed
# between barrier and manifest is recovered by the walk-back). A state
# tree sharded ACROSS hosts (e.g. ZeRO over a cross-host axis) commits
# through the SHARDED variant of the same protocol: each host writes its
# own shards_host<r>.npz + layout under the barrier, the manifest
# records the sharding, restore reassembles elastically (ISSUE 18).
# Only trees a host snapshot cannot represent at all (non-dict
# containers, object-dtype leaves) still degrade to the synchronous
# collective save, with a warning.
_C.CHECKPOINT = CfgNode()
_C.CHECKPOINT.ASYNC = False

# Run validate() concurrently with the NEXT train epoch (asyncplane/
# evalloop.py): at each epoch boundary the trainer takes an on-device copy
# of params/batch_stats and hands it to an eval worker thread; the result
# joins — with best-acc/is_best bookkeeping and the "eval"/"epoch" log
# records — at the following boundary. Trajectory-neutral by contract
# (eval reads a snapshot; training math never sees it —
# tests/test_asyncplane.py pins async-everything ≡ sync bit-identically).
# Epoch checkpoints record best_acc1 as of one eval earlier (the in-flight
# eval hasn't joined when the boundary save happens); the weights-only
# "best" checkpoint itself is always written when a new best joins.
# Multi-device processes run it under the dispatch sequencer
# (ASYNC.SEQUENCER, asyncplane/sequencer.py): train/eval/snapshot
# dispatches are token-ordered into one global program sequence, which
# removes the cross-thread collective deadlock PR 10 pinned on the
# 8-virtual-device mesh. Multi-host processes attach the cross-host
# dispatch ring (asyncplane/ring.py, ISSUE 18): the leader publishes
# its grant order through the run directory and followers grant only
# in that order, so eval overlaps train ACROSS hosts too; a host
# starving past ASYNC.RING_DEADLINE_S flags dispatch.wedge and that
# epoch's eval collectively degrades to sync (never a hang).
# ASYNC.SEQUENCER=False on multi-device remains the explicit escape
# hatch, degrading to synchronous eval with a logged warning.
_C.TRAIN.CONCURRENT_EVAL = False

# ------------------------------- compilation cache ---------------------------
# JAX persistent compilation cache (asyncplane/compile_cache.py): compiled
# step programs are serialized to DIR, so a restart — crash recovery,
# preemption resume, elastic resume at the same topology — skips the
# compile storm PR 5's jit.compiles counter measures. Cache hits/misses
# are counted (jit.cache_hits / jit.cache_misses registry counters +
# kind="compile.cache" telemetry records); a compile served from the
# cache is NOT counted as a jit.compile (it is a deserialization, not a
# compilation), so a warm restart shows jit.compiles at/near zero for
# previously-compiled programs (tools/asyncplane_bench.py proves it into
# BENCH_r06.json). While the cache is active on the CPU backend the
# cost-model HBM ledger (TELEMETRY.COSTMODEL_MEMORY) runs its extra AOT
# compile in an ISOLATED child process (telemetry/costmodel.py subprocess
# probe) — the in-process compile corrupted the CPU backend heap when
# combined with the cache's executable (de)serialization and a checkpoint
# restore; the probe keeps cache and ledger coexisting.
# ENABLED is the CPU's opt-in. On the TPU backend the cache is on without
# it, and a JAX_COMPILATION_CACHE_DIR in the environment is never cleared
# or replaced, whatever this node says.
_C.COMPILE_CACHE = CfgNode()
_C.COMPILE_CACHE.ENABLED = False
# Cache directory; "" = the fixed `.compile_cache/` at the root of the
# checkout (git-ignored) — never under OUT_DIR: the directory is part of
# the cache key, so a cache that moves with the run never hits.
_C.COMPILE_CACHE.DIR = ""
# Only compiles at least this long are persisted (0 caches everything —
# jax's own default of 1s would skip most CPU-test-sized programs).
_C.COMPILE_CACHE.MIN_COMPILE_TIME_S = 0.0
# Evict least-recently-used entries past this size. 0 = unbounded.
_C.COMPILE_CACHE.MAX_SIZE_MB = 0

# ------------------------------- serving ------------------------------------
# Online inference (serve/, serve_net.py) — the request-level engine that
# turns the eval step into a service. No reference analogue (the reference
# stops at offline test_net.py).
_C.SERVE = CfgNode()
# Dynamic micro-batch assembly: flush when MAX_BATCH requests are waiting
# or MAX_WAIT_MS after the oldest request arrived, whichever comes first.
_C.SERVE.MAX_BATCH = 8
_C.SERVE.MAX_WAIT_MS = 5.0
# Batch-shape buckets compiled ONCE at startup (jax.jit AOT lowering);
# a batch of n pads to the smallest bucket ≥ n, so steady-state serving
# never recompiles. [] ⇒ powers of two up to MAX_BATCH.
_C.SERVE.BUCKET_SIZES = []
# Bounded-queue backpressure: submissions beyond this depth are rejected
# with a retry-after hint instead of growing latency without bound.
_C.SERVE.MAX_QUEUE = 64
# Length-aware serving (the long-context plane): prompts of at least
# LONG_PROMPT_THRESHOLD tokens form the "long" admission/routing class;
# 0 disables classification (every request is "short").
_C.SERVE.LONG_PROMPT_THRESHOLD = 0
# At most this many of the MAX_QUEUE slots may hold long-class requests
# at once, so a burst of long prompts backpressures while short decode
# traffic keeps admitting — one chunked 4k prefill cannot starve the
# decode batch. Must stay below MAX_QUEUE (the short-class headroom IS
# the reservation); 0 = no reservation.
_C.SERVE.LONG_MAX_QUEUE = 0
# Optional per-length-class windowed p99 SLO targets (ms; 0 = no
# target). The fleet router surfaces `length:short` / `length:long`
# rows next to its per-model SLO rows, so the slo-breach alert rule
# referees them unchanged (telemetry/live.py).
_C.SERVE.SHORT_P99_SLO_MS = 0.0
_C.SERVE.LONG_P99_SLO_MS = 0.0
# Local device index the serving replica pins to (latency-optimal
# small-batch serving is one single-chip replica per chip; run one
# serve_net process per chip for throughput).
_C.SERVE.DEVICE = 0
# Socket frontend (length-prefixed frames; serve_net.py). PORT 0 picks an
# ephemeral port (logged at startup).
_C.SERVE.HOST = "127.0.0.1"
_C.SERVE.PORT = 8765

# Weight-only serving quantization (serve/quantize.py): "" (full
# precision), "bf16", or "int8". Repacks the weights before the AOT
# bucket compiles — buckets, protocol, and batching are unchanged; int8
# weights dequantize in-graph. Accuracy deltas are pinned by
# `zoo_check.py --quantize` against per-mode tolerances.
_C.SERVE.QUANTIZE = ""

# Request-scoped distributed tracing (telemetry/tracectx.py): the
# fraction of requests the client/bench edge opens a trace context for
# (head-based deterministic sampling — the decision is a pure function
# of the minted trace id, made once at the edge; downstream hops only
# honor presence). Traced requests carry the context in every protocol
# frame and accumulate a `trace.span` tree across router and replica
# sinks (queue wait, prefill chunks, decode steps, speculation rounds);
# the router's latency ring keeps trace ids so p99-breach alerts name
# their worst exemplars. 0.0 (default) keeps every frame byte-identical
# to the untraced wire format — server math is bit-identical either way
# (the trajectory-neutrality pin, tests/test_trace.py).
_C.SERVE.TRACE_SAMPLE = 0.0

# Serving fleet (serve/fleet/, `serve_net.py --fleet N`): a shared-nothing
# replica pool behind a router process. The router owns SERVE.HOST:PORT;
# each replica is a full serve_net engine in its own process on an
# ephemeral port, dispatched to by least-loaded policy (router in-flight
# depth + replica queue depth + occupancy + EWMA latency), with idempotent
# retry on replica failure and verbatim backpressure passthrough when the
# whole fleet is saturated.
_C.SERVE.FLEET = CfgNode()
# Initial replica count (`--fleet N` overrides). The autoscaler moves the
# target inside [MIN_REPLICAS, MAX_REPLICAS]; the pool keeps the target
# met (dead replicas are replaced automatically).
_C.SERVE.FLEET.REPLICAS = 2
_C.SERVE.FLEET.MIN_REPLICAS = 1
_C.SERVE.FLEET.MAX_REPLICAS = 4
# Autoscale-from-telemetry policy loop (fleet/autoscale.py): add a replica
# after BREACH_N consecutive windows with fleet p99 over P99_TARGET_MS or
# total queued work over QUEUE_HIGH; remove one after BREACH_N consecutive
# calm windows (p99 under SCALE_DOWN_FRAC x target AND queue under
# QUEUE_LOW); COOLDOWN_S of hysteresis after every action. False pins the
# fleet at its launch size (the pool still replaces dead replicas).
_C.SERVE.FLEET.AUTOSCALE = True
_C.SERVE.FLEET.P99_TARGET_MS = 250.0
_C.SERVE.FLEET.QUEUE_HIGH = 32
_C.SERVE.FLEET.QUEUE_LOW = 2
_C.SERVE.FLEET.SCALE_DOWN_FRAC = 0.5
_C.SERVE.FLEET.BREACH_N = 3
_C.SERVE.FLEET.EVAL_PERIOD_S = 2.0
_C.SERVE.FLEET.COOLDOWN_S = 10.0
# Replica health-checking (fleet/pool.py): a stats probe every
# HEALTH_PERIOD_S; HEALTH_FAILS consecutive failures (or process exit)
# marks the replica dead, removes it from routing, and spawns its
# replacement. WARMUP_TIMEOUT_S bounds how long a fresh replica may take
# to AOT-compile its bucket shapes before it is abandoned — a replica is
# never routable before its warm-up probe reports every bucket compiled.
_C.SERVE.FLEET.HEALTH_PERIOD_S = 1.0
_C.SERVE.FLEET.HEALTH_FAILS = 3
_C.SERVE.FLEET.WARMUP_TIMEOUT_S = 180.0
# Per-request router->replica socket timeout; a replica that sits on one
# request longer than this is treated as failed (the request reroutes).
_C.SERVE.FLEET.REQUEST_TIMEOUT_S = 60.0
# Fleet telemetry cadence: kind="fleet.stats"/"fleet.replica" records
# into the router's per-rank telemetry sink every EMIT_INTERVAL_S.
_C.SERVE.FLEET.EMIT_INTERVAL_S = 10.0

# ------------------------------- telemetry -----------------------------------
# Unified telemetry layer (distribuuuu_tpu/telemetry/): per-rank JSONL
# event files ({OUT_DIR}/telemetry/rank*.jsonl — spans, compile events,
# registry snapshots, mirrored resilience events), merged by
# tools/run_report.py into a run health report and a Perfetto trace.
# Trajectory-neutral by contract: ENABLED True vs False produces
# bit-identical training states (tests/test_telemetry.py); overhead is a
# few JSON lines per batch per rank, off the measured intervals.
_C.TELEMETRY = CfgNode()
_C.TELEMETRY.ENABLED = True
# Per-rank sink directory; "" = {OUT_DIR}/telemetry.
_C.TELEMETRY.DIR = ""
# Per-batch wait/h2d/step spans on EVERY rank (the per-rank half of the
# TRAIN.TIMELINE records, which stay primary-only): cross-rank step-time
# percentiles and straggler skew come from these. False keeps only
# epoch-level records (registry snapshots, memstats) and event mirrors.
_C.TELEMETRY.STEP_SPANS = True
# Count jit compiles + wall time via the jax.monitoring bus (kind=
# "compile" records + jit.compiles/jit.compile_s registry counters).
_C.TELEMETRY.COMPILE_EVENTS = True
# Sample device.memory_stats() per epoch (kind="memstats"; TPU/GPU
# backends — the CPU backend reports none and is skipped).
_C.TELEMETRY.MEMSTATS = True
# XLA cost-model ledger (telemetry/costmodel.py): once per step program,
# lower the jitted step and emit kind="cost.step"/"cost.roofline"
# records (flops, bytes accessed, roofline position) from XLA's own
# cost_analysis — the source run_report's MFU section and the monitor's
# mfu-regression rule read. Lowering only re-traces; no extra compile.
_C.TELEMETRY.COSTMODEL = True
# Additionally AOT-compile the lowered step for memory_analysis()
# (kind="cost.memory": executable HBM footprint vs capacity → headroom %
# and the hbm-headroom-low rule). Costs ONE extra backend compile per
# distinct step program at startup — disable for compile-latency-
# sensitive runs; the serving engine's bucket ledger is unaffected (it
# reads executables it already built).
_C.TELEMETRY.COSTMODEL_MEMORY = True

# ------------------------------- profiler ------------------------------------
# jax.profiler trace capture (TensorBoard/XProf format). When enabled, the
# primary process traces NUM_STEPS train steps starting at START_STEP of
# epoch 0 into {OUT_DIR}/profile (or DIR when set). The reference offers
# wall-clock meters only (SURVEY.md §5.1); this is the TPU-idiomatic upgrade.
_C.PROF = CfgNode()
_C.PROF.ENABLED = False
_C.PROF.DIR = ""
_C.PROF.START_STEP = 10
_C.PROF.NUM_STEPS = 5

# ------------------------------- misc ---------------------------------------
_C.OUT_DIR = "./output"
_C.CFG_DEST = "config.yaml"
_C.RNG_SEED = None
_C.LOG_DEST = "stdout"

# Snapshot of defaults for reset_cfg (ref: config.py:65-66).
_CFG_DEFAULT = _C.clone()
_CFG_DEFAULT.freeze()


def merge_from_file(cfg_file):
    """Merge a YAML file into the global cfg (ref: config.py:69-72)."""
    _C.merge_from_file(cfg_file)


def dump_cfg(out_dir=None):
    """Dump the merged config to OUT_DIR/CFG_DEST (ref: config.py:75-79)."""
    out_dir = _C.OUT_DIR if out_dir is None else out_dir
    cfg_file = os.path.join(out_dir, _C.CFG_DEST)
    os.makedirs(out_dir, exist_ok=True)
    with open(cfg_file, "w") as f:
        f.write(_C.dump())
    return cfg_file


def reset_cfg():
    """Reset the global cfg back to defaults (ref: config.py:82-84)."""
    _C.defrost()
    _C.merge_from_other_cfg(_CFG_DEFAULT)


def load_cfg_fom_args(description="Config file options.", argv=None):
    """Load config from command line args and a --cfg file (ref: config.py:87-100).

    Supports ``--cfg path.yaml`` plus a remainder of dotted ``KEY VALUE``
    overrides; absorbs ``--local_rank`` for launcher compatibility.
    """
    parser = argparse.ArgumentParser(description=description)
    help_s = "Config file location"
    parser.add_argument("--cfg", dest="cfg_file", help=help_s, required=True, type=str)
    # Accepted and ignored: process placement comes from the TPU runtime env.
    parser.add_argument("--local_rank", default=0, type=int)
    help_s = "See distribuuuu_tpu/config.py for all options"
    parser.add_argument("opts", help=help_s, default=None, nargs=argparse.REMAINDER)
    args_list = sys.argv[1:] if argv is None else argv
    if not args_list:
        parser.print_help()
        sys.exit(1)
    args = parser.parse_args(args_list)
    merge_from_file(args.cfg_file)
    _C.merge_from_list(args.opts)
    return _C
