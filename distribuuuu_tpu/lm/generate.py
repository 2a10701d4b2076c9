"""KV-cache autoregressive generation with continuous batching (ISSUE 12).

The serving half of the LM workload plane, reproducing the production TPU
LM-serving pattern (arXiv:2605.25645) at miniature scale:

**Prefill/decode split.** A request's prompt runs ONCE through a
teacher-forced forward (``GPTDecoder`` against an empty cache) — compute-
bound, one pass, produces the prompt's K/V and the first generated token.
Every subsequent token is a ``decode`` step: one token per sequence
against the cached K/V — tiny flops over the whole cache + params, i.e.
memory-bound by construction (the cost-model ledger attributes exactly
that; ROADMAP #3's future kernels get their canonical target here).

**Paged per-request KV cache.** The cache is ``[L, B, H, C, Dh]`` with
one PAGE (row) per request slot: admitting a request claims a free slot
and overwrites its page via the prefill insert; retiring frees the slot
with no data movement — other requests' pages are never touched, which is
what makes admit/retire contamination-free (pinned by tests).

**(batch, cache-len) tiles — the serve engine's AOT buckets generalized.**
``serve/engine.py`` compiles one executable per batch bucket; generation
needs TWO dynamic dims, so the engine AOT-compiles a decode executable
per ``(batch_tile, cache_tile)`` pair (``GENERATE.BATCH_TILES`` ×
``CACHE_TILES``), prefill per prompt tile, and the insert/grow glue per
shape pair — all at startup, so steady-state generation NEVER recompiles
(the fleet pool's warm-up gate reads the same ``n_compiles``/``buckets``
stats contract the image engine exposes). A step runs the smallest tile
covering the live slots and the longest sequence; crossing a tile
boundary pays one precompiled cache grow.

**Continuous batching.** The scheduler admits and retires per DECODE STEP
— a finishing request frees its slot for a waiting one while its former
batch-mates keep decoding (ragged completions, zero idle slots, zero
drops). Tokens stream to each requester the step they're produced
(``GenStream``), and through the fleet router as streaming ctrl frames
(serve/protocol.py + fleet/router.py).

**Exactness.** ``GPTDecoder`` reuses the training modules (vit.Mlp,
MoeMlp's reference path, the same Dense/LayerNorm layers under the same
param names), so it applies the TRAINING param tree directly, and
prefill+decode logits are pinned logit-identical (within float tolerance)
to the full teacher-forced ``GPT.__call__`` forward — the test
``tests/test_lm.py`` asserts it position by position.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
import time
from collections import deque
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from distribuuuu_tpu.config import cfg
from distribuuuu_tpu.models.layers import Dense, head_dtype
from distribuuuu_tpu.models.vit import Mlp, MoeMlp
from distribuuuu_tpu.serve.admission import (
    AdmissionController,
    QueueFullError,
)
from distribuuuu_tpu.telemetry import registry as telemetry_registry
from distribuuuu_tpu.telemetry import tracectx


# --------------------------------------------------------- decode modules
#
# Structural mirrors of models/gpt.GPT: same submodule NAMES, same layer
# types, same dtypes — so ``GPTDecoder.apply({"params": gpt_params}, ...)``
# consumes the training checkpoint unchanged. The only new math is the
# cache write (per-row dynamic_update_slice at each row's length) and the
# per-row causal mask over cached positions.


class CachedAttention(nn.Module):
    """vit.Attention's math against a KV cache: the qkv/out projections
    are the same ``Dense_0``/``Dense_1`` params; K/V of the T new tokens
    are written into the cache at each row's current length; queries
    attend every cached position ≤ their own."""

    dim: int
    num_heads: int
    dtype: Any

    @nn.compact
    def __call__(self, x, cache_k, cache_v, lengths):
        B, T, _ = x.shape
        H = self.num_heads
        D = self.dim // H
        C = cache_k.shape[2]
        qkv = Dense(3 * self.dim, dtype=self.dtype, name="Dense_0")(x)
        qkv = qkv.reshape(B, T, 3, H, D).transpose(2, 0, 3, 1, 4)
        q, k, v = qkv[0], qkv[1], qkv[2]  # [B, H, T, D]

        def write(c, new, start):  # [H, C, D], [H, T, D], scalar
            return jax.lax.dynamic_update_slice(c, new, (0, start, 0))

        cache_k = jax.vmap(write)(cache_k, k, lengths)
        cache_v = jax.vmap(write)(cache_v, v, lengths)
        scale = D ** -0.5
        # Kernel tier (KERNELS.DECODE_ATTN, ops/pallas/decode_attn.py):
        # the T=1 decode step fuses q·K, mask, online softmax and ·V into
        # one kernel over the cache pages — no fp32 cache copy, no
        # [B,H,1,C] logits round-trip, masked-out blocks never read.
        # Prefill (T>1) and unsupported tiles stay on the dense
        # reference below; selection is trace-time, per (batch, cache)
        # tile executable.
        if T == 1:
            from distribuuuu_tpu.ops import pallas as kernel_tier
            from distribuuuu_tpu.ops.pallas import decode_attn as decode_kernel

            blk = int(cfg.KERNELS.DECODE_BLOCK)
            ok, reason = decode_kernel.supported(T, C, D, blk)
            if kernel_tier.select(
                "decode_attn", supported=ok, reason=reason
            ) == "pallas":
                out = decode_kernel.decode_attention(
                    q[:, :, 0, :], cache_k, cache_v, lengths,
                    scale=scale, blk_k=blk,
                    interpret=kernel_tier.interpret_mode(),
                )[:, :, None, :]  # [B, H, 1, D] fp32
                out = out.astype(self.dtype).transpose(
                    0, 2, 1, 3
                ).reshape(B, T, self.dim)
                return Dense(self.dim, dtype=self.dtype, name="Dense_1")(
                    out
                ), cache_k, cache_v
        s = jnp.einsum(
            "bhtd,bhcd->bhtc",
            q.astype(jnp.float32), cache_k.astype(jnp.float32),
        ) * scale
        # key j is visible to new-token t iff j ≤ lengths[b] + t (the new
        # token itself sits at absolute position lengths[b] + t)
        j = jnp.arange(C)[None, None, None, :]
        t = jnp.arange(T)[None, None, :, None]
        visible = j <= (lengths[:, None, None, None] + t)
        s = jnp.where(visible, s, jnp.float32(-1e30))
        w = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhtc,bhcd->bhtd", w, cache_v.astype(jnp.float32))
        out = out.astype(self.dtype).transpose(0, 2, 1, 3).reshape(B, T, self.dim)
        return Dense(self.dim, dtype=self.dtype, name="Dense_1")(out), \
            cache_k, cache_v


class DecodeBlock(nn.Module):
    """vit.Block with the attention swapped for :class:`CachedAttention`;
    the FFN is the SAME module (vit.Mlp, or MoeMlp's exact single-device
    reference path for the *_moe archs) under the same name."""

    dim: int
    num_heads: int
    mlp_ratio: float
    dtype: Any
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0

    @nn.compact
    def __call__(self, x, cache_k, cache_v, lengths):
        y = nn.LayerNorm(
            dtype=self.dtype, param_dtype=jnp.float32, name="LayerNorm_0"
        )(x)
        a, cache_k, cache_v = CachedAttention(
            self.dim, self.num_heads, self.dtype, name="Attention_0"
        )(y, cache_k, cache_v, lengths)
        x = x + a
        y = nn.LayerNorm(
            dtype=self.dtype, param_dtype=jnp.float32, name="LayerNorm_1"
        )(x)
        if self.moe_experts > 0:
            # mesh=None selects MoeMlp's exact dense reference formulation
            # (replicated experts — the single-device serving layout)
            ffn = MoeMlp(
                self.dim, int(self.dim * self.mlp_ratio), self.moe_experts,
                self.moe_top_k, self.dtype, None,
                capacity_factor=self.moe_capacity_factor, name="MoeMlp_0",
            )
        else:
            ffn = Mlp(
                int(self.dim * self.mlp_ratio), self.dim, 0.0, self.dtype,
                name="Mlp_0",
            )
        return x + ffn(y, train=False), cache_k, cache_v


class GPTDecoder(nn.Module):
    """Applies the GPT param tree to T new tokens per row against a KV
    cache. ``lengths[b]`` tokens are already cached for row b; positions
    and causal visibility follow from it. Returns per-new-token logits
    and the updated cache."""

    vocab_size: int
    seq_len: int
    dim: int
    depth: int
    num_heads: int
    mlp_ratio: float = 4.0
    dtype: Any = jnp.bfloat16
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_every: int = 2
    moe_capacity_factor: float = 2.0

    @nn.compact
    def __call__(self, tokens, lengths, cache):
        B, T = tokens.shape
        x = nn.Embed(
            self.vocab_size, self.dim, name="tok_embed",
            dtype=self.dtype, param_dtype=jnp.float32,
            embedding_init=nn.initializers.normal(0.02),
        )(tokens)
        pos_table = self.param(
            "pos_embed",
            nn.initializers.normal(0.02),
            (1, self.seq_len, self.dim), jnp.float32,
        )
        pos_idx = jnp.clip(
            lengths[:, None] + jnp.arange(T)[None, :], 0, self.seq_len - 1
        )
        x = x + jnp.take(pos_table[0], pos_idx, axis=0).astype(self.dtype)
        ks, vs = [], []
        for i in range(self.depth):
            moe = (
                self.moe_experts
                if self.moe_experts > 0
                and i % self.moe_every == self.moe_every - 1
                else 0
            )
            x, ck, cv = DecodeBlock(
                self.dim, self.num_heads, self.mlp_ratio, self.dtype,
                moe_experts=moe, moe_top_k=self.moe_top_k,
                moe_capacity_factor=self.moe_capacity_factor,
                name=f"Block_{i}",
            )(x, cache["k"][i], cache["v"][i], lengths)
            ks.append(ck)
            vs.append(cv)
        x = nn.LayerNorm(
            dtype=self.dtype, param_dtype=jnp.float32, name="LayerNorm_0"
        )(x)
        hd = head_dtype(x.dtype)
        logits = Dense(self.vocab_size, dtype=hd, name="head")(x.astype(hd))
        return logits, {"k": jnp.stack(ks), "v": jnp.stack(vs)}


def decoder_for(model) -> GPTDecoder:
    """The decode mirror of a ``models/gpt.GPT`` instance (same hyper
    fields, so the param trees coincide)."""
    return GPTDecoder(
        vocab_size=model.vocab_size, seq_len=model.seq_len, dim=model.dim,
        depth=model.depth, num_heads=model.num_heads,
        mlp_ratio=model.mlp_ratio, dtype=model.dtype,
        moe_experts=model.moe_experts, moe_top_k=model.moe_top_k,
        moe_every=model.moe_every,
        moe_capacity_factor=model.moe_capacity_factor,
    )


# ----------------------------------------------------------- tile algebra


def default_tiles(cap: int) -> list[int]:
    """Powers of two up to ``cap`` plus ``cap`` itself (the serve-bucket
    rule, serve/engine.default_buckets)."""
    out, b = [], 1
    while b < cap:
        out.append(b)
        b *= 2
    out.append(int(cap))
    return sorted(set(out))


def tile_for(tiles: list[int], n: int) -> int:
    """Smallest tile ≥ n (tiles sorted ascending)."""
    for t in tiles:
        if t >= n:
            return t
    raise ValueError(f"no tile covers {n} (tiles: {tiles})")


def validate_generate_cfg(seq_len: int, prompt_len: int, max_new: int,
                          batch_tiles: list[int], cache_tiles: list[int]):
    """The GENERATE config refusals, with the exact arithmetic in each
    message (ISSUE 12 satellite). Returns (batch_tiles, cache_tiles)."""
    if prompt_len < 1 or max_new < 1:
        raise ValueError(
            f"GENERATE.PROMPT_LEN={prompt_len} and MAX_NEW_TOKENS={max_new} "
            "must be >= 1"
        )
    batch_tiles = sorted(set(int(b) for b in batch_tiles)) or default_tiles(4)
    cache_tiles = sorted(set(int(c) for c in cache_tiles)) or [int(seq_len)]
    if batch_tiles[0] < 1:
        raise ValueError(f"GENERATE.BATCH_TILES {batch_tiles} must be >= 1")
    for c in cache_tiles:
        if c > seq_len:
            raise ValueError(
                f"GENERATE.CACHE_TILES contains {c} > LM.SEQ_LEN={seq_len}: "
                "the learned position table has no entry past the trained "
                "context — lower the tile or retrain with a longer LM.SEQ_LEN"
            )
    need = prompt_len + max_new
    if cache_tiles[-1] < need:
        raise ValueError(
            f"largest GENERATE.CACHE_TILES entry {cache_tiles[-1]} cannot "
            f"hold a full request: GENERATE.PROMPT_LEN={prompt_len} + "
            f"MAX_NEW_TOKENS={max_new} = {need} cached positions — raise "
            f"CACHE_TILES to >= {need} (and <= LM.SEQ_LEN={seq_len}) or "
            "lower MAX_NEW_TOKENS/PROMPT_LEN"
        )
    return batch_tiles, cache_tiles


def validate_chunk_prefill_cfg(chunk: int, cache_tiles: list[int]):
    """The GENERATE.CHUNK_PREFILL refusals, exact arithmetic in-message
    (ISSUE 19): chunked prefill streams a prompt into its KV page in
    fixed ``chunk``-token appends, and the final chunk is PADDED — it
    writes ``ceil(plen/chunk)*chunk`` page positions — so every cache
    tile wide enough to be a page must be a chunk multiple, or a ragged
    prompt near the tile edge would write past it (dynamic_update_slice
    clamps the start: silent page corruption, not an error)."""
    if chunk < 1:
        raise ValueError(
            f"GENERATE.CHUNK_PREFILL={chunk} must be >= 1 (0 disables "
            "chunked prefill)"
        )
    if chunk > cache_tiles[-1]:
        raise ValueError(
            f"GENERATE.CHUNK_PREFILL={chunk} exceeds the largest "
            f"GENERATE.CACHE_TILES entry {cache_tiles[-1]} — no page "
            f"could hold even one chunk; lower CHUNK_PREFILL to "
            f"<= {cache_tiles[-1]} or raise CACHE_TILES"
        )
    for c in cache_tiles:
        if c >= chunk and c % chunk:
            raise ValueError(
                f"GENERATE.CHUNK_PREFILL={chunk} does not divide "
                f"GENERATE.CACHE_TILES entry {c} ({c} % {chunk} = "
                f"{c % chunk}) — the final padded chunk writes "
                f"ceil(plen/{chunk})*{chunk} positions into its page, "
                f"which can spill past a {c}-wide tile; use cache tiles "
                f"that are multiples of {chunk} (e.g. {c - c % chunk} or "
                f"{c + chunk - c % chunk}) or a CHUNK_PREFILL that "
                f"divides every tile"
            )


# --------------------------------------------------------------- sampling
#
# Decode-time token selection (ISSUE 17b). Greedy (temperature <= 0) is
# argmax and draws NO randomness — the pre-17 behaviour, bit-for-bit.
# Sampled selection is REPLAYABLE by construction: every random decision
# consumes exactly one counter-based uniform ``_uniform(seed, stream, n)``
# where ``n`` is a per-request per-stream draw counter — never a stateful
# RNG — so the same ctrl-frame seed reproduces the same token stream on
# any replica regardless of how requests were batched (the serving-side
# twin of the (seed, epoch, idx) augmentation invariant).

# uniform streams: one lane per decision kind, so the plain-decode,
# acceptance, draft-proposal and residual-resample draws of one request
# never collide
_U_PLAIN, _U_ACCEPT, _U_DRAFT, _U_RESID = 0, 1, 2, 3


@dataclasses.dataclass(frozen=True)
class SampleParams:
    """Per-request selection knobs (``GENERATE.SAMPLE`` defaults; the
    ``op="generate"`` ctrl frame may override all four per request)."""

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


def validate_sample_cfg(temperature: float, top_k: int, top_p: float):
    """The GENERATE.SAMPLE refusals (exact values in-message)."""
    if temperature < 0.0:
        raise ValueError(
            f"GENERATE.SAMPLE.TEMPERATURE={temperature} must be >= 0 "
            "(0 = greedy argmax)"
        )
    if top_k < 0:
        raise ValueError(
            f"GENERATE.SAMPLE.TOP_K={top_k} must be >= 0 (0 = disabled)"
        )
    if not 0.0 < top_p <= 1.0:
        raise ValueError(
            f"GENERATE.SAMPLE.TOP_P={top_p} must lie in (0, 1] "
            "(1.0 = disabled)"
        )


def sample_params(obj: SampleParams | dict | None = None) -> SampleParams:
    """Resolve request-side sampling knobs: a :class:`SampleParams`
    passes through, a dict (the ctrl-frame fields) overlays the
    ``GENERATE.SAMPLE`` defaults, ``None`` IS the defaults. Validated."""
    if isinstance(obj, SampleParams):
        sp = obj
    else:
        d = dict(obj or {})
        node = cfg.GENERATE.SAMPLE
        sp = SampleParams(
            temperature=float(d.get("temperature", node.TEMPERATURE)),
            top_k=int(d.get("top_k", node.TOP_K)),
            top_p=float(d.get("top_p", node.TOP_P)),
            seed=int(d.get("seed", node.SEED)),
        )
    validate_sample_cfg(sp.temperature, sp.top_k, sp.top_p)
    return sp


def _uniform(seed: int, stream: int, n: int) -> float:
    """The (seed, stream, n) → [0, 1) uniform every sampled decision
    consumes: a fresh Philox generator per draw, so draw ``n`` is a pure
    function of its coordinates and replay needs no RNG state carry."""
    return float(
        np.random.default_rng(
            [int(seed) % (2 ** 63), int(stream), int(n)]
        ).random()
    )


def warp_probs(logits, sp: SampleParams) -> np.ndarray:
    """Temperature / top-k / top-p warped probabilities of ONE logit row
    (float64 numpy, ties broken by vocab id) — the single distribution
    both plain sampling and the speculative accept/reject rule read."""
    x = np.asarray(logits, np.float64) / float(sp.temperature)
    if sp.top_k and sp.top_k < x.size:
        # keep everything >= the k-th largest logit (ties keep extras —
        # deterministic, and renormalization absorbs them)
        x = np.where(x >= np.sort(x)[-sp.top_k], x, -np.inf)
    x = x - x.max()
    p = np.exp(x)
    p /= p.sum()
    if sp.top_p < 1.0:
        # minimal probability-sorted prefix with cumulative mass >= top_p
        order = np.argsort(-p, kind="stable")
        cut = int(np.searchsorted(np.cumsum(p[order]), sp.top_p)) + 1
        keep = order[:cut]
        masked = np.zeros_like(p)
        masked[keep] = p[keep]
        p = masked / masked.sum()
    return p


def _pick(p: np.ndarray, u: float) -> int:
    """Inverse-CDF selection in vocab-id order — deterministic in
    ``(p, u)``, always lands on a positive-mass token."""
    cum = np.cumsum(p)
    return int(min(np.searchsorted(cum, u * cum[-1], side="right"),
                   p.size - 1))


def sample_token(logits, sp: SampleParams, u: float | None = None) -> int:
    """One token from one logit row: greedy argmax when
    ``sp.temperature <= 0`` (``u`` unused), else inverse-CDF over the
    warped distribution with the caller-supplied uniform."""
    if sp.greedy:
        return int(np.asarray(logits).argmax())
    return _pick(warp_probs(logits, sp), u)


def validate_speculate_cfg(k: int, target_model, draft_model,
                           prompt_len: int, max_new: int,
                           cache_tiles: list[int]):
    """The GENERATE.SPECULATE refusals, exact arithmetic in-message
    (ISSUE 17 satellite): draft/target pairing and draft-K cache-tile
    headroom — a speculative round may write K+1 positions past the
    current length, so the largest cache tile needs K more rows than the
    plain-decode bound."""
    if k < 1:
        raise ValueError(f"GENERATE.SPECULATE.K={k} must be >= 1")
    tv, dv = int(target_model.vocab_size), int(draft_model.vocab_size)
    if tv != dv:
        raise ValueError(
            f"GENERATE.SPECULATE draft/target vocab mismatch: draft "
            f"vocab_size={dv} != target vocab_size={tv} — the accept/"
            "reject rule compares the two distributions token by token, "
            "which is undefined across vocabularies"
        )
    need = prompt_len + max_new + k
    if cache_tiles[-1] < need:
        raise ValueError(
            f"largest GENERATE.CACHE_TILES entry {cache_tiles[-1]} cannot "
            f"hold a speculative round: GENERATE.PROMPT_LEN={prompt_len} + "
            f"MAX_NEW_TOKENS={max_new} + SPECULATE.K={k} = {need} cached "
            f"positions — raise CACHE_TILES to >= {need} or lower "
            "K/MAX_NEW_TOKENS/PROMPT_LEN"
        )
    ds = int(draft_model.seq_len)
    if cache_tiles[-1] > ds:
        raise ValueError(
            f"GENERATE.CACHE_TILES largest entry {cache_tiles[-1]} exceeds "
            f"the draft model's trained context LM.SEQ_LEN={ds}: the draft "
            "mirrors every cached position and its learned position table "
            "has no entry past that — use a draft trained for the context "
            "or lower the cache tiles"
        )


# -------------------------------------------------------------- the engine


class GenStream:
    """Per-request streamed result: iterate for tokens as they decode, or
    ``result()`` for the full list. Closed exactly once at retire.

    ``request_id`` is the engine's local counter — or, for a TRACED
    request (ISSUE 20), the fleet-wide trace id: one identity from the
    client edge's ctrl frame to the done frame. ``trace``/``span_id``/
    ``t_submit`` feed the engine's per-request ``trace.span`` tree
    (queue wait at admit, decode/speculation steps, the
    ``engine.request`` root at retire)."""

    def __init__(self, request_id, prompt_len: int, trace=None):
        self.request_id = request_id
        self.prompt_len = prompt_len
        self.trace = trace
        self.t_submit = time.perf_counter()
        # the engine-side root span id, minted NOW so every child span
        # (queue_wait, prefill, decode steps) can parent onto it before
        # the root itself is emitted at retire
        self.span_id = "" if trace is None else tracectx.new_span_id()
        self._q: deque = deque()
        self._cond = threading.Condition()
        self._done = False
        self._error: Exception | None = None
        self.reason: str | None = None

    # engine side
    def _emit(self, token: int) -> None:
        with self._cond:
            self._q.append(int(token))
            self._cond.notify_all()

    def _close(self, reason: str, error: Exception | None = None) -> None:
        with self._cond:
            self._done = True
            self.reason = reason
            self._error = error
            self._cond.notify_all()

    # client side
    def __iter__(self):
        while True:
            with self._cond:
                while not self._q and not self._done:
                    self._cond.wait(timeout=0.1)
                if self._q:
                    yield self._q.popleft()
                    continue
                if self._error is not None:
                    raise self._error
                return

    def result(self, timeout: float | None = 60.0) -> list[int]:
        deadline = None if timeout is None else time.perf_counter() + timeout
        out = []
        with self._cond:
            while True:
                out.extend(self._q)
                self._q.clear()
                if self._done:
                    if self._error is not None:
                        raise self._error
                    return out
                wait = (
                    None if deadline is None
                    else max(0.0, deadline - time.perf_counter())
                )
                if wait == 0.0:
                    raise TimeoutError(
                        f"generation {self.request_id} incomplete after "
                        f"{timeout}s"
                    )
                self._cond.wait(timeout=wait)


class _Slot:
    __slots__ = ("stream", "length", "last_token", "new_tokens", "max_new",
                 "sample", "draws", "draft_len", "history")

    def __init__(self, stream, length, last_token, max_new, sample):
        self.stream = stream
        self.length = length          # cached positions (prompt + generated-1)
        self.last_token = last_token  # feeds the next decode step
        self.new_tokens = 0
        self.max_new = max_new
        self.sample = sample          # SampleParams for this request
        self.draws = [0, 0, 0, 0]     # per-stream uniform draw counters
        # speculative bookkeeping: token at every position 0..length (the
        # last entry is ``last_token``, not yet cached) and how many
        # positions the DRAFT cache holds (it can trail the target by one
        # after a fully-accepted round)
        self.draft_len = 0
        self.history: list[int] = []


class GenerateEngine:
    """Continuous-batching generation over one device — or one dp×tp
    replica (``mesh=``, ISSUE 17a): with a model axis > 1 the param tree
    is placed by the SAME ``lm_spec_table`` rules that place training
    state (the decoder mirrors the training module names), the paged
    cache shards its heads on ``model`` (``specs.lm_cache_spec``), and
    the head stays vocab-parallel inside each executable with logits
    gathered at the output — pinned logit-identical to the single-device
    path.

    ``variables`` is ``{"params": ...}`` — the TRAINING param tree (no
    batch_stats: the LM is LayerNorm-only). All tile executables compile
    AOT at construction; ``start()`` runs the scheduler thread; ``submit``
    returns a :class:`GenStream`.

    ``draft_model``/``draft_variables`` switch on speculative decoding
    (ISSUE 17c): the draft proposes ``spec_k`` tokens per round, the
    target verifies all of them in ONE prefill-shaped call, and the
    standard accept/reject + bonus rule keeps the emitted stream
    IDENTICAL to target-only decoding (greedy: exact match for ANY
    draft; sampled: same seed ⇒ same stream as the acceptance-rule
    reference).
    """

    def __init__(
        self,
        model,
        variables: dict,
        *,
        max_new_tokens: int | None = None,
        prompt_len: int | None = None,
        batch_tiles: list[int] | None = None,
        cache_tiles: list[int] | None = None,
        eos_id: int | None = None,
        max_queue: int | None = None,
        long_prompt_threshold: int | None = None,
        long_max_queue: int | None = None,
        poll_s: float | None = None,
        emit_interval_s: float = 10.0,
        mesh=None,
        draft_model=None,
        draft_variables: dict | None = None,
        spec_k: int | None = None,
        sample: SampleParams | dict | None = None,
        chunk_prefill: int | None = None,
    ):
        self.model = model
        self.decoder = decoder_for(model)
        self._variables = {"params": variables["params"]}
        self.max_new = int(
            max_new_tokens if max_new_tokens is not None
            else cfg.GENERATE.MAX_NEW_TOKENS
        )
        self.prompt_len = int(
            prompt_len if prompt_len is not None else cfg.GENERATE.PROMPT_LEN
        )
        self.eos_id = int(
            eos_id if eos_id is not None else cfg.GENERATE.EOS_ID
        )
        self._poll_s = float(
            poll_s if poll_s is not None else cfg.GENERATE.POLL_S
        )
        self.batch_tiles, self.cache_tiles = validate_generate_cfg(
            model.seq_len, self.prompt_len, self.max_new,
            list(batch_tiles if batch_tiles is not None
                 else cfg.GENERATE.BATCH_TILES),
            list(cache_tiles if cache_tiles is not None
                 else cfg.GENERATE.CACHE_TILES),
        )
        # kernel-tier refusal (KERNELS.DECODE_ATTN=pallas forced): every
        # decode executable is one (batch, cache) tile, and the fused
        # kernel tiles each cache page into DECODE_BLOCK-key blocks — a
        # tile the block cannot cover would silently decode on the dense
        # path, so the forced knob refuses with the arithmetic up front
        # (`auto` quietly keeps such tiles on the reference path instead).
        from distribuuuu_tpu.ops import pallas as kernel_tier

        kernel_tier.validate_kernels_cfg()
        if kernel_tier.requested("decode_attn") == "pallas":
            from distribuuuu_tpu.ops.pallas import decode_attn as _dk

            blk = int(cfg.KERNELS.DECODE_BLOCK)
            for c in self.cache_tiles:
                if _dk.resolve_block(c, blk) is None:
                    raise ValueError(
                        f"KERNELS.DECODE_ATTN=pallas: KERNELS.DECODE_BLOCK="
                        f"{blk} does not divide GENERATE.CACHE_TILES entry "
                        f"{c} ({c} % {blk} = {c % blk}) — use cache tiles "
                        f"that are multiples of {blk} (e.g. "
                        f"{-(-c // blk) * blk}), a DECODE_BLOCK that "
                        f"divides {c}, or KERNELS.DECODE_ATTN=auto/xla"
                    )
        self.prompt_tiles = [
            t for t in default_tiles(self.prompt_len)
        ]
        # chunked paged prefill (ISSUE 19): > 0 replaces the whole-prompt
        # prefill buckets with ONE fixed-width chunk executable per cache
        # tile — the prompt streams into its page chunk by chunk, so a 4k
        # prompt needs no 4k bucket and may exceed PROMPT_LEN up to what
        # the largest cache tile holds next to max_new (+ spec K)
        self.chunk_prefill = int(
            chunk_prefill if chunk_prefill is not None
            else cfg.GENERATE.CHUNK_PREFILL
        )
        if self.chunk_prefill:
            validate_chunk_prefill_cfg(self.chunk_prefill, self.cache_tiles)
        self._default_sample = sample_params(sample)

        # -- tensor-parallel decode (ISSUE 17a) ---------------------------
        self._mesh = None
        self._tp = 1
        if mesh is not None and int(dict(mesh.shape).get("model", 1)) > 1:
            tp = int(dict(mesh.shape)["model"])
            if model.num_heads % tp:
                raise ValueError(
                    f"MESH.MODEL={tp} does not divide the LM's num_heads="
                    f"{model.num_heads} ({model.num_heads} % {tp} = "
                    f"{model.num_heads % tp}) — TP decode shards attention "
                    "heads (and the cache's head dim) over the model axis"
                )
            if model.vocab_size % tp:
                raise ValueError(
                    f"MESH.MODEL={tp} does not divide vocab_size="
                    f"{model.vocab_size} ({model.vocab_size} % {tp} = "
                    f"{model.vocab_size % tp}) — the vocab-parallel head "
                    "splits logits over the model axis"
                )
            self._mesh = mesh
            self._tp = tp

        # -- speculative decoding (ISSUE 17c) -----------------------------
        self.spec_k = 0
        if draft_model is not None:
            k = int(spec_k if spec_k is not None else cfg.GENERATE.SPECULATE.K)
            validate_speculate_cfg(
                k, model, draft_model, self.prompt_len, self.max_new,
                self.cache_tiles,
            )
            if self._mesh is not None and draft_model.num_heads % self._tp:
                raise ValueError(
                    f"MESH.MODEL={self._tp} does not divide the DRAFT "
                    f"model's num_heads={draft_model.num_heads} "
                    f"({draft_model.num_heads} % {self._tp} = "
                    f"{draft_model.num_heads % self._tp}) — the draft "
                    "shards its heads over the same model axis"
                )
            self.spec_k = k
            self.draft_model = draft_model
            self.draft_decoder = decoder_for(draft_model)
            self._draft_variables = {"params": draft_variables["params"]}

        self.n_slots = self.batch_tiles[-1]
        # length-aware admission (the long-context plane): prompts of
        # >= long_threshold tokens are the "long" class, capped at
        # long_max_queue of the max_queue slots so a burst of chunked
        # long prefills cannot starve short decode traffic
        self.long_threshold = int(
            long_prompt_threshold if long_prompt_threshold is not None
            else cfg.SERVE.LONG_PROMPT_THRESHOLD
        )
        self._admission = AdmissionController(
            max_queue if max_queue is not None else cfg.SERVE.MAX_QUEUE,
            long_max_queue=int(
                long_max_queue if long_max_queue is not None
                else cfg.SERVE.LONG_MAX_QUEUE
            ),
        )
        if self._admission.long_max_queue and not self.long_threshold:
            raise ValueError(
                f"SERVE.LONG_MAX_QUEUE={self._admission.long_max_queue} "
                "without SERVE.LONG_PROMPT_THRESHOLD — the long-class "
                "reservation needs the prompt-token threshold that "
                "defines the long class (set SERVE.LONG_PROMPT_THRESHOLD "
                ">= 1)"
            )
        self._emit_interval_s = emit_interval_s
        self._dtype = model.dtype
        self._heads = model.num_heads
        self._head_dim = model.dim // model.num_heads
        self._depth = model.depth
        if self.spec_k:
            dm = self.draft_model
            self._d_dtype = dm.dtype
            self._d_heads = dm.num_heads
            self._d_head_dim = dm.dim // dm.num_heads
            self._d_depth = dm.depth

        # TP placement: params by the lm_spec_table path rules (the
        # decoder tree IS the training tree), cache heads on ``model``.
        # On a dp×tp mesh the data axis appears in no decode spec — a
        # replica's whole request stream is replicated over dp.
        if self._mesh is not None:
            from distribuuuu_tpu.parallel.partition import specs as pspecs

            self._cache_sharding = NamedSharding(
                self._mesh, pspecs.lm_cache_spec()
            )
            self._rep_sharding = NamedSharding(self._mesh, P())
            self._var_shardings = pspecs.lm_decode_shardings(
                self._mesh, self._variables
            )
            self._variables = jax.device_put(
                self._variables, self._var_shardings
            )
            if self.spec_k:
                self._draft_var_shardings = pspecs.lm_decode_shardings(
                    self._mesh, self._draft_variables
                )
                self._draft_variables = jax.device_put(
                    self._draft_variables, self._draft_var_shardings
                )

        # -- AOT compile every tile shape, exactly once, at startup -------
        # (the serve-engine bucket discipline generalized to 2D tiles)
        self.n_compiles = 0
        self._decode_exec: dict[tuple[int, int], Any] = {}
        self._prefill_exec: dict[int, Any] = {}
        self._chunk_exec: dict[int, Any] = {}
        self._draft_chunk_exec: dict[int, Any] = {}
        self._insert_exec: dict[tuple[int, int, int], Any] = {}
        self._grow_exec: dict[tuple, Any] = {}
        self._verify_exec: dict[tuple[int, int], Any] = {}
        self._draft_decode_exec: dict[tuple[int, int], Any] = {}
        self._draft_propose_exec: dict[tuple[int, int, int], Any] = {}
        self._draft_prefill_exec: dict[int, Any] = {}
        self._draft_insert_exec: dict[tuple[int, int, int], Any] = {}
        self._draft_grow_exec: dict[tuple, Any] = {}
        # every tile executable traces inside the engine's kernel scope
        with self._kernel_scope():
            self._compile_tiles()
            if self.spec_k:
                self._compile_draft_tiles()

        # -- live state ----------------------------------------------------
        self._lock = threading.Condition()
        self._waiting: deque = deque()
        self._slots: list[_Slot | None] = [None] * self.n_slots
        self._b_tile = self.batch_tiles[0]
        self._c_tile = self.cache_tiles[0]
        self._cache = self._zero_cache(self._b_tile, self._c_tile)
        if self.spec_k:
            self._draft_cache = self._zero_cache(
                self._b_tile, self._c_tile, draft=True
            )
        self._draining = False
        self._started = False
        self._next_id = 0
        self._t0 = time.perf_counter()
        self._counters = {
            "prompt_tokens": 0, "new_tokens": 0, "decode_steps": 0,
            "requests": 0, "retired": 0,
        }
        if self.spec_k:
            self._counters.update(
                spec_rounds=0, spec_proposed=0, spec_accepted=0,
                spec_bonus=0,
            )
        if self.chunk_prefill:
            self._counters.update(chunk_prefills=0, chunk_calls=0)
        if self.long_threshold:
            self._counters.update(long_admitted=0, long_rejected=0)
        self._decode_ms: deque = deque(maxlen=4096)
        self._prefill_ms: deque = deque(maxlen=1024)
        self._thread = threading.Thread(
            target=self._scheduler, name="gen-scheduler", daemon=True
        )

    # ------------------------------------------------------------ compiles
    def _cache_dims(self, draft: bool) -> tuple:
        if draft:
            return (self._d_depth, self._d_heads, self._d_head_dim,
                    self._d_dtype)
        return (self._depth, self._heads, self._head_dim, self._dtype)

    def _cache_sds(self, b: int, c: int, *, draft: bool = False):
        depth, heads, hdim, dtype = self._cache_dims(draft)
        shape = (depth, b, heads, c, hdim)
        kw = {} if self._mesh is None else {"sharding": self._cache_sharding}
        return {
            "k": jax.ShapeDtypeStruct(shape, dtype, **kw),
            "v": jax.ShapeDtypeStruct(shape, dtype, **kw),
        }

    def _tok_sds(self, shape):
        kw = {} if self._mesh is None else {"sharding": self._rep_sharding}
        return jax.ShapeDtypeStruct(shape, jnp.int32, **kw)

    def _vars_sds(self, variables, shardings):
        if self._mesh is None:
            return jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype),
                variables,
            )
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(
                jnp.shape(x), x.dtype, sharding=s
            ),
            variables, shardings,
        )

    def _kernel_scope(self):
        """Trace-time scope of the tile executables: an engine without a
        mesh compiles for its one device, so the decode kernel may engage
        (ops/pallas/__init__.py); the TP engine's cache is head-sharded
        under GSPMD, which cannot partition a Mosaic call."""
        from distribuuuu_tpu.ops import pallas as kernel_tier

        if self._mesh is None:
            return kernel_tier.single_device_program()
        return contextlib.nullcontext()

    def _jit(self, fn, *, donate=()):
        """jax.jit with the TP output contract pinned when a mesh is
        live: logits gathered (replicated — the 'gathered argmax/sample'
        happens at executable exit), cache outputs kept head-sharded.
        Without a mesh this is plain jit (the single-device path,
        byte-identical to pre-TP behaviour)."""
        if self._mesh is None:
            return jax.jit(fn, donate_argnums=donate)
        name = getattr(fn, "func", fn).__name__
        cs, rs = self._cache_sharding, self._rep_sharding
        cdict = {"k": cs, "v": cs}
        # decode/verify/prefill return (logits, cache); insert/grow the
        # cache alone
        outs = (rs, cdict) if name in (
            "decode_fn", "verify_fn", "prefill_fn"
        ) else cdict
        return jax.jit(fn, donate_argnums=donate, out_shardings=outs)

    def _compile_tiles(self) -> None:
        from distribuuuu_tpu.serve.engine import COMPILE_EVENTS

        def decode_fn(variables, tokens, lengths, cache):
            logits, cache = self.decoder.apply(
                variables, tokens[:, None], lengths, cache
            )
            return logits[:, 0], cache

        def verify_fn(variables, tokens, lengths, cache):
            # ONE prefill-shaped call over [last_token, d_1..d_K]: logits
            # at all K+1 positions for the accept/reject rule — the
            # memory-bound decode's roofline-native batching (K+1 target
            # positions for barely more HBM traffic than 1)
            return self.decoder.apply(variables, tokens, lengths, cache)

        def prefill_fn(variables, tokens):
            # fresh page: the prompt's K/V builds in a zeros cache sized
            # exactly to the prompt tile; insert_fn pages it into the slot
            B, Pt = tokens.shape
            zero = {
                "k": jnp.zeros(
                    (self._depth, B, self._heads, Pt, self._head_dim),
                    self._dtype,
                ),
                "v": jnp.zeros(
                    (self._depth, B, self._heads, Pt, self._head_dim),
                    self._dtype,
                ),
            }
            lengths = jnp.zeros((B,), jnp.int32)
            return self.decoder.apply(variables, tokens, lengths, zero)

        def chunk_fn(variables, tokens, lengths, cache):
            # one fixed-width prompt chunk appended into the B=1 page at
            # the chunk's start offset — prefill re-expressed as
            # verify-shaped calls against a page-sized cache, so the page
            # builds in ceil(plen/W) precompiled steps of ONE width
            return self.decoder.apply(variables, tokens, lengths, cache)

        chunk_fn.__name__ = "verify_fn"  # TP out contract: (logits, cache)

        def insert_fn(cache, kv, slot):
            return jax.tree.map(
                lambda c, n: jax.lax.dynamic_update_slice(
                    c, n, (0, slot, 0, 0, 0)
                ),
                cache, kv,
            )

        def grow_fn(cache, b, c):
            def pad(x):
                db = b - x.shape[1]
                dc = c - x.shape[3]
                return jnp.pad(x, ((0, 0), (0, db), (0, 0), (0, dc), (0, 0)))

            return jax.tree.map(pad, cache)

        vars_sds = self._vars_sds(
            self._variables, getattr(self, "_var_shardings", None)
        )
        tok1 = self._tok_sds
        for b in self.batch_tiles:
            for c in self.cache_tiles:
                self._decode_exec[(b, c)] = (
                    self._jit(decode_fn, donate=(3,))
                    .lower(vars_sds, tok1((b,)), tok1((b,)),
                           self._cache_sds(b, c))
                    .compile()
                )
                self.n_compiles += 1
                COMPILE_EVENTS.append(b)
                if self.spec_k:
                    self._verify_exec[(b, c)] = (
                        self._jit(verify_fn, donate=(3,))
                        .lower(vars_sds, tok1((b, self.spec_k + 1)),
                               tok1((b,)), self._cache_sds(b, c))
                        .compile()
                    )
                    self.n_compiles += 1
                    COMPILE_EVENTS.append(b)
        if self.chunk_prefill:
            W = self.chunk_prefill
            page_tiles = [c for c in self.cache_tiles if c >= W]
            for c in page_tiles:
                self._chunk_exec[c] = (
                    self._jit(chunk_fn, donate=(3,))
                    .lower(vars_sds, tok1((1, W)), tok1((1,)),
                           self._cache_sds(1, c))
                    .compile()
                )
                self.n_compiles += 1
                COMPILE_EVENTS.append(1)
        else:
            page_tiles = self.prompt_tiles
            for p in self.prompt_tiles:
                self._prefill_exec[p] = (
                    self._jit(prefill_fn)
                    .lower(vars_sds, tok1((1, p)))
                    .compile()
                )
                self.n_compiles += 1
        for p in page_tiles:
            for b in self.batch_tiles:
                for c in self.cache_tiles:
                    if p > c:
                        continue
                    self._insert_exec[(p, b, c)] = (
                        self._jit(insert_fn, donate=(0,))
                        .lower(self._cache_sds(b, c), self._cache_sds(1, p),
                               self._tok_sds(()))
                        .compile()
                    )
                    self.n_compiles += 1
        tiles = [(b, c) for b in self.batch_tiles for c in self.cache_tiles]
        for (b1, c1) in tiles:
            for (b2, c2) in tiles:
                if (b2, c2) != (b1, c1) and b2 >= b1 and c2 >= c1:
                    self._grow_exec[(b1, c1, b2, c2)] = (
                        self._jit(functools.partial(grow_fn, b=b2, c=c2))
                        .lower(self._cache_sds(b1, c1))
                        .compile()
                    )
                    self.n_compiles += 1
        telemetry_registry.get_registry().counter(
            "serve.aot_compiles"
        ).inc(self.n_compiles)
        # cost-model ledger per tile (telemetry/costmodel.py): read off the
        # executables just built — free. Decode's verdict is the point:
        # per-token flops over the whole cache+params traffic is far below
        # any ridge, i.e. memory-bound — the canonical kernel target.
        if cfg.TELEMETRY.COSTMODEL:
            from distribuuuu_tpu.telemetry import costmodel

            for (b, c), ex in self._decode_exec.items():
                costmodel.capture_compiled(
                    ex, label=f"gen_decode_b{b}_c{c}", phase="generate",
                    images=b, arch=cfg.MODEL.ARCH,
                )
            for p, ex in self._prefill_exec.items():
                costmodel.capture_compiled(
                    ex, label=f"gen_prefill_p{p}", phase="generate",
                    images=1, arch=cfg.MODEL.ARCH,
                )
            for c, ex in self._chunk_exec.items():
                costmodel.capture_compiled(
                    ex,
                    label=f"gen_chunk_prefill_w{self.chunk_prefill}_c{c}",
                    phase="generate", images=1, arch=cfg.MODEL.ARCH,
                )

    def _compile_draft_tiles(self) -> None:
        """The draft model's mirror of the target tile set: T=1 decode
        per (batch, cache) tile (the K proposal steps), prefill + insert
        per prompt tile (the draft caches the prompt at admit), grow per
        tile pair — so a speculative round never recompiles either
        model."""
        from distribuuuu_tpu.serve.engine import COMPILE_EVENTS

        def draft_decode_fn(variables, tokens, lengths, cache):
            logits, cache = self.draft_decoder.apply(
                variables, tokens[:, None], lengths, cache
            )
            return logits[:, 0], cache

        def draft_propose_fn(variables, feed, lags, lens0, cache):
            # the whole greedy propose phase in ONE executable: a scan
            # over the round's S draft steps with argmax feedback, so a
            # speculative round costs 2 device calls (propose + verify)
            # instead of K+2. The K-1 intermediate host syncs it deletes
            # cost ~0.5 ms each on CPU — more than a nano draft step.
            # Step s feeds history (the feed matrix) while s <= lag, the
            # previous step's argmax after; exactly the per-step loop's
            # catch-up rule. Sampled slots never take this path: their
            # proposals are drawn host-side in float64 (the replay
            # contract), one decode step at a time.
            def step(carry, xs):
                cache, prev = carry
                f, s = xs
                tok = jnp.where(s <= lags, f, prev)
                logits, cache = self.draft_decoder.apply(
                    variables, tok[:, None], lens0 + s, cache
                )
                out = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                return (cache, out), out

            S = feed.shape[1]
            xs = (feed.T, jnp.arange(S, dtype=jnp.int32))
            (cache, _), outs = jax.lax.scan(
                step, (cache, jnp.zeros_like(lens0)), xs
            )
            return outs, cache  # [S, b] per-step argmaxes

        def draft_chunk_fn(variables, tokens, lengths, cache):
            # the draft's page builds through the same chunk stream, so a
            # chunk-admitted request speculates with a fully-mirrored
            # prompt (logits discarded — only the K/V matter here)
            return self.draft_decoder.apply(variables, tokens, lengths, cache)

        draft_chunk_fn.__name__ = "verify_fn"

        def draft_prefill_fn(variables, tokens):
            B, Pt = tokens.shape
            zero = {
                "k": jnp.zeros(
                    (self._d_depth, B, self._d_heads, Pt, self._d_head_dim),
                    self._d_dtype,
                ),
                "v": jnp.zeros(
                    (self._d_depth, B, self._d_heads, Pt, self._d_head_dim),
                    self._d_dtype,
                ),
            }
            lengths = jnp.zeros((B,), jnp.int32)
            return self.draft_decoder.apply(variables, tokens, lengths, zero)

        def draft_insert_fn(cache, kv, slot):
            return jax.tree.map(
                lambda c, n: jax.lax.dynamic_update_slice(
                    c, n, (0, slot, 0, 0, 0)
                ),
                cache, kv,
            )

        def draft_grow_fn(cache, b, c):
            def pad(x):
                db = b - x.shape[1]
                dc = c - x.shape[3]
                return jnp.pad(x, ((0, 0), (0, db), (0, 0), (0, dc), (0, 0)))

            return jax.tree.map(pad, cache)

        # the TP output contract matches the target's executables: logits
        # gathered, cache head-sharded (_jit keys on the fn name)
        draft_decode_fn.__name__ = "decode_fn"
        draft_propose_fn.__name__ = "decode_fn"  # (tokens, cache) out pair
        draft_prefill_fn.__name__ = "prefill_fn"

        vars_sds = self._vars_sds(
            self._draft_variables, getattr(self, "_draft_var_shardings", None)
        )
        tok1 = self._tok_sds
        n0 = self.n_compiles
        for b in self.batch_tiles:
            for c in self.cache_tiles:
                self._draft_decode_exec[(b, c)] = (
                    self._jit(draft_decode_fn, donate=(3,))
                    .lower(vars_sds, tok1((b,)), tok1((b,)),
                           self._cache_sds(b, c, draft=True))
                    .compile()
                )
                self.n_compiles += 1
                COMPILE_EVENTS.append(b)
                # a round runs K steps (every draft cache caught up) or
                # K+1 (some slot one behind after a fully-accepted
                # round) — the only two lags the reconciliation rule can
                # leave, so two static shapes cover every greedy round
                for S in (self.spec_k, self.spec_k + 1):
                    self._draft_propose_exec[(b, c, S)] = (
                        self._jit(draft_propose_fn, donate=(4,))
                        .lower(vars_sds, tok1((b, S)), tok1((b,)),
                               tok1((b,)),
                               self._cache_sds(b, c, draft=True))
                        .compile()
                    )
                    self.n_compiles += 1
                    COMPILE_EVENTS.append(b)
        if self.chunk_prefill:
            W = self.chunk_prefill
            page_tiles = [c for c in self.cache_tiles if c >= W]
            for c in page_tiles:
                self._draft_chunk_exec[c] = (
                    self._jit(draft_chunk_fn, donate=(3,))
                    .lower(vars_sds, tok1((1, W)), tok1((1,)),
                           self._cache_sds(1, c, draft=True))
                    .compile()
                )
                self.n_compiles += 1
        else:
            page_tiles = self.prompt_tiles
            for p in self.prompt_tiles:
                self._draft_prefill_exec[p] = (
                    self._jit(draft_prefill_fn)
                    .lower(vars_sds, tok1((1, p)))
                    .compile()
                )
                self.n_compiles += 1
        for p in page_tiles:
            for b in self.batch_tiles:
                for c in self.cache_tiles:
                    if p > c:
                        continue
                    self._draft_insert_exec[(p, b, c)] = (
                        self._jit(draft_insert_fn, donate=(0,))
                        .lower(self._cache_sds(b, c, draft=True),
                               self._cache_sds(1, p, draft=True),
                               self._tok_sds(()))
                        .compile()
                    )
                    self.n_compiles += 1
        tiles = [(b, c) for b in self.batch_tiles for c in self.cache_tiles]
        for (b1, c1) in tiles:
            for (b2, c2) in tiles:
                if (b2, c2) != (b1, c1) and b2 >= b1 and c2 >= c1:
                    self._draft_grow_exec[(b1, c1, b2, c2)] = (
                        self._jit(functools.partial(draft_grow_fn, b=b2, c=c2))
                        .lower(self._cache_sds(b1, c1, draft=True))
                        .compile()
                    )
                    self.n_compiles += 1
        telemetry_registry.get_registry().counter(
            "serve.aot_compiles"
        ).inc(self.n_compiles - n0)

    def _zero_cache(self, b: int, c: int, *, draft: bool = False):
        depth, heads, hdim, dtype = self._cache_dims(draft)
        shape = (depth, b, heads, c, hdim)
        z = {
            "k": jnp.zeros(shape, dtype),
            "v": jnp.zeros(shape, dtype),
        }
        if self._mesh is not None:
            z = jax.device_put(z, self._cache_sharding)
        return z

    # ------------------------------------------------------- client surface
    def start(self) -> "GenerateEngine":
        self._thread.start()
        self._started = True
        return self

    def __enter__(self) -> "GenerateEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.drain()

    def submit(self, prompt, max_new_tokens: int | None = None,
               sample: SampleParams | dict | None = None,
               trace=None) -> GenStream:
        """Enqueue one prompt (iterable of token ids). Returns the token
        stream. Raises ``QueueFullError``/``EngineClosedError`` like the
        image engine's admission contract. ``sample`` overrides the
        engine's default :class:`SampleParams` for this request (the
        ctrl-frame temperature/top_k/top_p/seed fields land here).

        ``trace`` (a ``tracectx.TraceContext`` or its ctrl-frame dict)
        unifies the stream's ``request_id`` with the fleet-wide trace id
        and turns on per-request span emission — purely observational:
        admission, scheduling, and every token are bit-identical with or
        without it."""
        if isinstance(trace, dict):
            trace = tracectx.from_fields(trace)
        sp = (
            self._default_sample if sample is None
            else sample_params(sample)
        )
        ids = np.asarray(list(prompt), np.int32)
        if ids.ndim != 1 or len(ids) < 1:
            raise ValueError("prompt must be a non-empty 1-D token list")
        max_new = min(
            self.max_new,
            int(max_new_tokens) if max_new_tokens else self.max_new,
        )
        if self.chunk_prefill:
            # chunked prefill unpins the prompt bound from PROMPT_LEN:
            # any prompt the cache can hold next to its decode budget
            bound = self.cache_tiles[-1] - max_new - self.spec_k
            if len(ids) > bound:
                spec = (
                    f" + SPECULATE.K={self.spec_k}" if self.spec_k else ""
                )
                raise ValueError(
                    f"prompt of {len(ids)} tokens cannot fit the cache: "
                    f"{len(ids)} + max_new={max_new}{spec} > largest "
                    f"GENERATE.CACHE_TILES entry {self.cache_tiles[-1]} — "
                    "chunked prefill admits any prompt the cache holds; "
                    "shorten the prompt, lower max_new_tokens, or raise "
                    "CACHE_TILES"
                )
        elif len(ids) > self.prompt_len:
            raise ValueError(
                f"prompt of {len(ids)} tokens exceeds "
                f"GENERATE.PROMPT_LEN={self.prompt_len}"
            )
        if int(ids.max()) >= self.model.vocab_size or int(ids.min()) < 0:
            raise ValueError(
                f"prompt token ids must lie in [0, {self.model.vocab_size})"
            )
        lc = self._length_class(len(ids))
        with self._lock:
            try:
                self._admission.admit(
                    len(self._waiting), self._retry_after_ms(),
                    length_class=lc,
                    class_depth=sum(
                        1 for (_s, w, _m, _p) in self._waiting
                        if self._length_class(len(w)) == "long"
                    ),
                )
            except QueueFullError:
                if self.long_threshold and lc == "long":
                    self._counters["long_rejected"] += 1
                raise
            stream = GenStream(
                self._next_id if trace is None else trace.trace_id,
                len(ids), trace=trace,
            )
            self._next_id += 1
            self._waiting.append((stream, ids, max_new, sp))
            self._counters["requests"] += 1
            if self.long_threshold and lc == "long":
                self._counters["long_admitted"] += 1
            self._lock.notify_all()
        return stream

    def _length_class(self, prompt_tokens: int) -> str:
        """"long" when classification is on and the prompt reaches
        SERVE.LONG_PROMPT_THRESHOLD tokens; "short" otherwise."""
        return (
            "long"
            if self.long_threshold and prompt_tokens >= self.long_threshold
            else "short"
        )

    def drain(self, timeout: float | None = 60.0) -> None:
        """Stop admitting, finish every queued and in-flight request,
        stop the scheduler. Idempotent."""
        with self._lock:
            self._draining = True
            self._admission.close()
            self._lock.notify_all()
        if self._started:
            self._thread.join(timeout)
            self._started = False
        else:
            from distribuuuu_tpu.serve.admission import EngineClosedError

            with self._lock:
                while self._waiting:
                    stream = self._waiting.popleft()[0]
                    stream._close(
                        "drained",
                        EngineClosedError("engine drained before start()"),
                    )

    def _retry_after_ms(self) -> float:
        ms = list(self._decode_ms)[-64:]
        per_tok = (sum(ms) / len(ms)) if ms else 10.0
        return max(50.0, per_tok * self.max_new / max(1, self.n_slots))

    def stats(self) -> dict:
        """The fleet pool/router stats contract (pool.warmed_up reads
        ``buckets``/``n_compiles``; the router reads ``queue_depth``) plus
        the generation-plane view."""
        with self._lock:
            waiting = len(self._waiting)
            waiting_long = sum(
                1 for (_s, w, _m, _p) in self._waiting
                if self._length_class(len(w)) == "long"
            )
            active = sum(1 for s in self._slots if s is not None)
        dm = sorted(self._decode_ms)
        pm = sorted(self._prefill_ms)

        def pct(v, q):
            return round(v[min(len(v) - 1, int(q * len(v)))], 3) if v else 0.0

        el = max(time.perf_counter() - self._t0, 1e-9)
        return {
            "queue_depth": waiting,
            "queue_depth_long": waiting_long,
            "long_threshold": self.long_threshold,
            "long_max_queue": self._admission.long_max_queue,
            "active": active,
            "slots": self.n_slots,
            "chunk_prefill": self.chunk_prefill,
            "n_compiles": self.n_compiles,
            "buckets": [list(t) for t in sorted(self._decode_exec)],
            "max_batch": self.n_slots,
            "batch_occupancy": active / max(1, self.n_slots),
            "decode_p50_ms": pct(dm, 0.50),
            "decode_p99_ms": pct(dm, 0.99),
            "prefill_p50_ms": pct(pm, 0.50),
            "prefill_p99_ms": pct(pm, 0.99),
            "tokens_per_s": round(self._counters["new_tokens"] / el, 2),
            **self._counters,
        }

    # ---------------------------------------------------------- scheduling
    def _free_slot(self) -> int | None:
        for i, s in enumerate(self._slots):
            if s is None:
                return i
        return None

    def _ensure_tile(self, b_need: int, c_need: int) -> None:
        """Grow the live cache to the smallest tile covering the need
        (precompiled pad — never a recompile, never a shrink mid-flight)."""
        b = tile_for(self.batch_tiles, max(b_need, self._b_tile))
        c = tile_for(self.cache_tiles, max(c_need, self._c_tile))
        if (b, c) == (self._b_tile, self._c_tile):
            return
        key = (self._b_tile, self._c_tile, b, c)
        self._cache = self._grow_exec[key](self._cache)
        if self.spec_k:
            self._draft_cache = self._draft_grow_exec[key](self._draft_cache)
        self._b_tile, self._c_tile = b, c

    def _admit_chunked(self, slot: int, stream: GenStream, ids: np.ndarray,
                       max_new: int, sp: SampleParams) -> None:
        """Chunked paged prefill (ISSUE 19): the prompt streams into a
        fresh B=1 page in fixed CHUNK_PREFILL-token appends — every call
        a precompiled chunk executable — then the page inserts into the
        slot exactly like whole-prompt prefill. The final chunk is padded;
        its pad K/V land past ``plen`` where the ragged mask never looks
        and the decode writes overwrite position by position. The first
        generated token comes off the last chunk's logit row at the
        prompt's final position — pinned logit-identical (float tol) to
        whole-prompt prefill by tests/test_lm_chunk_prefill.py."""
        from distribuuuu_tpu.telemetry import spans

        t0 = time.perf_counter()
        W = self.chunk_prefill
        plen = len(ids)
        n_chunks = -(-plen // W)
        ct = tile_for(self.cache_tiles, n_chunks * W)
        self._ensure_tile(slot + 1, max(plen + max_new + self.spec_k, ct))
        page = self._zero_cache(1, ct)
        logits = None
        for k in range(n_chunks):
            seg = ids[k * W:(k + 1) * W]
            chunk = np.zeros((1, W), np.int32)
            chunk[0, :len(seg)] = seg
            logits, page = self._chunk_exec[ct](
                self._variables, jnp.asarray(chunk),
                jnp.full((1,), k * W, jnp.int32), page,
            )
        self._cache = self._insert_exec[(ct, self._b_tile, self._c_tile)](
            self._cache, page, jnp.int32(slot)
        )
        s = _Slot(stream, plen, 0, max_new, sp)
        first = self._select(
            s, np.asarray(logits[0, (plen - 1) - (n_chunks - 1) * W])
        )
        s.last_token = first
        s.history = list(int(t) for t in ids) + [first]
        self._slots[slot] = s
        if self.spec_k:
            dpage = self._zero_cache(1, ct, draft=True)
            for k in range(n_chunks):
                seg = ids[k * W:(k + 1) * W]
                chunk = np.zeros((1, W), np.int32)
                chunk[0, :len(seg)] = seg
                _, dpage = self._draft_chunk_exec[ct](
                    self._draft_variables, jnp.asarray(chunk),
                    jnp.full((1,), k * W, jnp.int32), dpage,
                )
            self._draft_cache = self._draft_insert_exec[
                (ct, self._b_tile, self._c_tile)
            ](self._draft_cache, dpage, jnp.int32(slot))
            s.draft_len = plen
        self._counters["prompt_tokens"] += plen
        self._counters["chunk_prefills"] += 1
        self._counters["chunk_calls"] += n_chunks * (2 if self.spec_k else 1)
        ms = (time.perf_counter() - t0) * 1e3
        self._prefill_ms.append(ms)
        stream._emit(first)
        s.new_tokens = 1
        self._counters["new_tokens"] += 1
        if spans.enabled():
            spans.emit_event(
                "gen.admit", slot=slot, prompt_tokens=plen,
                request=stream.request_id,
                length_class=self._length_class(plen),
            )
            spans.emit_event(
                "gen.chunk_prefill", tokens=plen, chunk=W,
                chunks=n_chunks, tile=ct, ms=round(ms, 3),
            )
            if not sp.greedy:
                spans.emit_event(
                    "gen.sample", request=stream.request_id,
                    temperature=sp.temperature, top_k=sp.top_k,
                    top_p=sp.top_p, seed=sp.seed,
                )
            tracectx.emit_trace_span(
                stream.trace, "queue_wait", stream.t_submit,
                t0 - stream.t_submit, parent=stream.span_id, slot=slot,
            )
            tracectx.emit_trace_span(
                stream.trace, "chunk_prefill", t0, ms / 1e3,
                parent=stream.span_id, tokens=plen, chunk=W,
                chunks=n_chunks, tile=ct,
            )
        self._maybe_finish(slot, first)

    def _admit(self, stream: GenStream, ids: np.ndarray, max_new: int,
               sp: SampleParams) -> None:
        from distribuuuu_tpu.telemetry import spans

        slot = self._free_slot()
        assert slot is not None
        if self.chunk_prefill:
            return self._admit_chunked(slot, stream, ids, max_new, sp)
        t0 = time.perf_counter()
        plen = len(ids)
        ptile = tile_for(self.prompt_tiles, plen)
        self._ensure_tile(slot + 1, plen + max_new + self.spec_k)
        padded = np.zeros((1, ptile), np.int32)
        padded[0, :plen] = ids
        logits, kv = self._prefill_exec[ptile](
            self._variables, jnp.asarray(padded)
        )
        self._cache = self._insert_exec[(ptile, self._b_tile, self._c_tile)](
            self._cache, kv, jnp.int32(slot)
        )
        s = _Slot(stream, plen, 0, max_new, sp)
        first = self._select(s, np.asarray(logits[0, plen - 1]))
        s.last_token = first
        s.history = list(int(t) for t in ids) + [first]
        self._slots[slot] = s
        if self.spec_k:
            # the draft mirrors the prompt into its own paged cache
            _, dkv = self._draft_prefill_exec[ptile](
                self._draft_variables, jnp.asarray(padded)
            )
            self._draft_cache = self._draft_insert_exec[
                (ptile, self._b_tile, self._c_tile)
            ](self._draft_cache, dkv, jnp.int32(slot))
            s.draft_len = plen
        self._counters["prompt_tokens"] += plen
        ms = (time.perf_counter() - t0) * 1e3
        self._prefill_ms.append(ms)
        stream._emit(first)
        s.new_tokens = 1  # prefill produced token #1
        self._counters["new_tokens"] += 1
        if spans.enabled():
            spans.emit_event(
                "gen.admit", slot=slot, prompt_tokens=plen,
                request=stream.request_id,
                length_class=self._length_class(plen),
            )
            spans.emit_event(
                "gen.prefill", tokens=plen, tile=ptile, ms=round(ms, 3),
            )
            if not sp.greedy:
                spans.emit_event(
                    "gen.sample", request=stream.request_id,
                    temperature=sp.temperature, top_k=sp.top_k,
                    top_p=sp.top_p, seed=sp.seed,
                )
            tracectx.emit_trace_span(
                stream.trace, "queue_wait", stream.t_submit,
                t0 - stream.t_submit, parent=stream.span_id, slot=slot,
            )
            tracectx.emit_trace_span(
                stream.trace, "prefill", t0, ms / 1e3,
                parent=stream.span_id, tokens=plen, tile=ptile,
            )
        self._maybe_finish(slot, first)

    def _retire(self, slot: int, reason: str) -> None:
        from distribuuuu_tpu.telemetry import spans

        s = self._slots[slot]
        self._slots[slot] = None
        self._counters["retired"] += 1
        s.stream._close(reason)
        if spans.enabled():
            spans.emit_event(
                "gen.retire", slot=slot, new_tokens=s.new_tokens,
                reason=reason, request=s.stream.request_id,
            )
            # the engine-side ROOT of a traced request's span tree:
            # submit → retire, under the router's dispatch span; its
            # pre-minted span_id is what queue_wait/prefill/decode
            # children already parented onto
            tr = s.stream.trace
            tracectx.emit_trace_span(
                tr, "engine.request", s.stream.t_submit,
                time.perf_counter() - s.stream.t_submit,
                parent="" if tr is None else tr.parent_span,
                span_id=s.stream.span_id, reason=reason,
                new_tokens=s.new_tokens,
                prompt_tokens=s.stream.prompt_len,
                length_class=self._length_class(s.stream.prompt_len),
            )

    def _maybe_finish(self, slot: int, token: int) -> bool:
        s = self._slots[slot]
        if token == self.eos_id:
            self._retire(slot, "eos")
            return True
        if s.new_tokens >= s.max_new:
            self._retire(slot, "max_new_tokens")
            return True
        if s.length + 1 >= self.cache_tiles[-1]:
            self._retire(slot, "cache_full")
            return True
        return False

    @staticmethod
    def _select(s: _Slot, row, stream: int = _U_PLAIN) -> int:
        """One token off one logit row for slot ``s``: greedy argmax
        draws nothing; sampled selection consumes the slot's next
        counter-based uniform on ``stream``."""
        if s.sample.greedy:
            return int(np.asarray(row).argmax())
        u = _uniform(s.sample.seed, stream, s.draws[stream])
        s.draws[stream] += 1
        return _pick(warp_probs(row, s.sample), u)

    def _emit_tok(self, i: int, tok: int) -> bool:
        """Emit one generated token on slot ``i`` (the length/history
        bookkeeping shared by the plain and speculative paths); returns
        True if the slot retired."""
        s = self._slots[i]
        s.length += 1
        s.last_token = tok
        s.history.append(tok)
        s.new_tokens += 1
        self._counters["new_tokens"] += 1
        s.stream._emit(tok)
        return self._maybe_finish(i, tok)

    def _decode_step(self) -> None:
        from distribuuuu_tpu.telemetry import spans

        t0 = time.perf_counter()
        live = [i for i, s in enumerate(self._slots) if s is not None]
        # snapshot the traced residents NOW — _emit_tok may retire a
        # slot mid-loop, but its wall-clock share of THIS step is real
        traced = [
            (i, self._slots[i]) for i in live
            if self._slots[i].stream.trace is not None
        ]
        c_need = max(self._slots[i].length for i in live) + 1
        self._ensure_tile(max(live) + 1, c_need)
        b = self._b_tile
        tokens = np.zeros((b,), np.int32)
        lengths = np.zeros((b,), np.int32)
        for i in live:
            tokens[i] = self._slots[i].last_token
            lengths[i] = self._slots[i].length
        logits, self._cache = self._decode_exec[(b, self._c_tile)](
            self._variables, jnp.asarray(tokens), jnp.asarray(lengths),
            self._cache,
        )
        logits = np.asarray(logits)
        ms = (time.perf_counter() - t0) * 1e3
        self._decode_ms.append(ms)
        self._counters["decode_steps"] += 1
        for i in live:
            self._emit_tok(i, self._select(self._slots[i], logits[i]))
        if spans.enabled():
            spans.emit_event(
                "gen.decode", active=len(live), tile_b=b,
                tile_c=self._c_tile, ms=round(ms, 3),
            )
            # wall-clock attribution per TRACED resident: the request
            # was live for the whole batched step, so the full step
            # duration is its decode share (residency, not cost split)
            for i, s in traced:
                tracectx.emit_trace_span(
                    s.stream.trace, "decode_step", t0, ms / 1e3,
                    parent=s.stream.span_id, slot=i, tile_b=b,
                    tile_c=self._c_tile, active=len(live),
                )

    def _spec_propose_steps(self, live, props, qrows, steps, b, c) -> None:
        """Per-step propose path: one draft decode call (and one host
        sync) per step, with proposals selected host-side in float64.
        Any sampled slot in the round lands here — the replay contract
        pins sampled selection to the host's numpy math. All-greedy
        rounds take the fused propose executable instead."""
        K = self.spec_k
        for s_idx in range(steps):
            tokens = np.zeros((b,), np.int32)
            lengths = np.zeros((b,), np.int32)
            for i in live:
                sl = self._slots[i]
                pos = sl.draft_len + s_idx  # the position this step feeds
                if pos <= sl.length:
                    tokens[i] = sl.history[pos]
                else:
                    tokens[i] = props[i][pos - sl.length - 1]
                lengths[i] = pos
            dlogits, self._draft_cache = self._draft_decode_exec[(b, c)](
                self._draft_variables, jnp.asarray(tokens),
                jnp.asarray(lengths), self._draft_cache,
            )
            dlogits = np.asarray(dlogits)
            for i in live:
                sl = self._slots[i]
                if sl.draft_len + s_idx >= sl.length and len(props[i]) < K:
                    row = dlogits[i]
                    props[i].append(self._select(sl, row, _U_DRAFT))
                    if not sl.sample.greedy:
                        qrows.setdefault(i, []).append(row)

    def _spec_round(self) -> None:
        """One speculative round over every live slot (ISSUE 17c).

        1. PROPOSE — K batched T=1 draft decode steps sample K proposals
           per slot from the warped draft distribution (greedy: draft
           argmax). A slot whose draft cache trails the target by one
           position (the previous round fully accepted — its d_K was
           never fed to the draft) catches up inside the same loop: its
           first step feeds history instead of proposing, and the loop
           runs one extra step so every slot still proposes K. An
           all-greedy round runs the whole loop as ONE fused scan
           executable (argmax feedback on-device); any sampled slot
           drops the round to the per-step host path, whose float64
           numpy selection is what the replay contract pins.
        2. VERIFY — ONE prefill-shaped target call over
           ``[last_token, d_1..d_K]`` per slot returns target logits at
           all K+1 positions.
        3. ACCEPT — per slot, left to right: greedy accepts d_j iff it
           equals the target argmax; sampled accepts iff
           ``u·q(d_j) <= p(d_j)`` and resamples a rejected position from
           the residual ``max(p−q, 0)``. All K accepted ⇒ a bonus token
           from the (K+1)-th verify row. Rejection costs NOTHING in the
           cache: stale positions past a slot's length are invisible to
           the ragged mask and get overwritten by the next write there.
        """
        from distribuuuu_tpu.telemetry import spans

        t0 = time.perf_counter()
        K = self.spec_k
        live = [i for i, s in enumerate(self._slots) if s is not None]
        traced = [
            (i, self._slots[i]) for i in live
            if self._slots[i].stream.trace is not None
        ]
        max_len = max(self._slots[i].length for i in live)
        self._ensure_tile(max(live) + 1, max_len + K + 1)
        b, c = self._b_tile, self._c_tile

        props: dict[int, list[int]] = {i: [] for i in live}
        qrows: dict[int, list[np.ndarray]] = {}
        steps = K + max(
            self._slots[i].length - self._slots[i].draft_len for i in live
        )
        all_greedy = all(self._slots[i].sample.greedy for i in live)
        if all_greedy and (b, c, steps) in self._draft_propose_exec:
            # fused propose: all S draft steps in one executable, no
            # per-step host sync. Proposal j for a slot with lag L is
            # the argmax out of step L+j (step L both feeds
            # history[length] and yields proposal #1).
            feed = np.zeros((b, steps), np.int32)
            lags = np.zeros((b,), np.int32)
            lens0 = np.zeros((b,), np.int32)
            for i in live:
                sl = self._slots[i]
                lag = sl.length - sl.draft_len
                lags[i] = lag
                lens0[i] = sl.draft_len
                for s in range(lag + 1):
                    feed[i, s] = sl.history[sl.draft_len + s]
            outs, self._draft_cache = self._draft_propose_exec[
                (b, c, steps)
            ](
                self._draft_variables, jnp.asarray(feed),
                jnp.asarray(lags), jnp.asarray(lens0), self._draft_cache,
            )
            outs = np.asarray(outs)
            for i in live:
                lag = int(lags[i])
                props[i] = [int(outs[s, i]) for s in range(lag, lag + K)]
        else:
            self._spec_propose_steps(live, props, qrows, steps, b, c)

        tokens = np.zeros((b, K + 1), np.int32)
        lengths = np.zeros((b,), np.int32)
        for i in live:
            sl = self._slots[i]
            tokens[i, 0] = sl.last_token
            tokens[i, 1:] = props[i]
            lengths[i] = sl.length
        vlogits, self._cache = self._verify_exec[(b, c)](
            self._variables, jnp.asarray(tokens), jnp.asarray(lengths),
            self._cache,
        )
        vlogits = np.asarray(vlogits)  # [b, K+1, V]

        n_acc = n_bonus = 0
        for i in live:
            sl = self._slots[i]
            old_draft_len = sl.draft_len
            for j in range(K):
                d = int(props[i][j])
                trow = vlogits[i, j]
                if sl.sample.greedy:
                    tgt = int(trow.argmax())
                    if d == tgt:
                        n_acc += 1
                        if self._emit_tok(i, d):
                            break
                        continue
                    # greedy rejection: the corrective token IS the
                    # target argmax — exactly what target-only greedy
                    # decode would have emitted here
                    self._emit_tok(i, tgt)
                    break
                p = warp_probs(trow, sl.sample)
                q = warp_probs(qrows[i][j], sl.sample)
                u = _uniform(sl.sample.seed, _U_ACCEPT, sl.draws[_U_ACCEPT])
                sl.draws[_U_ACCEPT] += 1
                if u * q[d] <= p[d]:
                    n_acc += 1
                    if self._emit_tok(i, d):
                        break
                    continue
                # rejected: resample from the residual max(p − q, 0)
                r = np.maximum(p - q, 0.0)
                if r.sum() <= 0.0:
                    r = p
                u = _uniform(sl.sample.seed, _U_RESID, sl.draws[_U_RESID])
                sl.draws[_U_RESID] += 1
                self._emit_tok(i, _pick(r, u))
                break
            else:
                # every draft accepted and the slot is still live: the
                # bonus token comes free off the (K+1)-th verify row
                n_bonus += 1
                self._emit_tok(i, self._select(sl, vlogits[i, K]))
            if self._slots[i] is not None:
                # draft-cache reconciliation: valid through the last
                # accepted position, capped by what this round's steps
                # actually wrote (a fully-accepted round leaves the draft
                # one position behind — next round's catch-up)
                sl.draft_len = min(old_draft_len + steps, sl.length)

        ms = (time.perf_counter() - t0) * 1e3
        self._decode_ms.append(ms)
        self._counters["decode_steps"] += 1
        self._counters["spec_rounds"] += 1
        self._counters["spec_proposed"] += K * len(live)
        self._counters["spec_accepted"] += n_acc
        self._counters["spec_bonus"] += n_bonus
        if spans.enabled():
            spans.emit_event(
                "gen.speculate", k=K, active=len(live),
                proposed=K * len(live), accepted=n_acc, bonus=n_bonus,
                ms=round(ms, 3),
            )
            for i, s in traced:
                tracectx.emit_trace_span(
                    s.stream.trace, "spec_round", t0, ms / 1e3,
                    parent=s.stream.span_id, slot=i, k=K,
                    accepted=n_acc, bonus=n_bonus, active=len(live),
                )

    def _emit_token_counters(self) -> None:
        from distribuuuu_tpu.telemetry import spans

        if spans.enabled():
            spans.emit_event(
                "lm.tokens",
                prompt_tokens=self._counters["prompt_tokens"],
                new_tokens=self._counters["new_tokens"],
                decode_steps=self._counters["decode_steps"],
                elapsed_s=round(time.perf_counter() - self._t0, 3),
            )

    def _scheduler(self) -> None:
        last_emit = time.perf_counter()
        while True:
            with self._lock:
                # CONTINUOUS BATCHING: admit into free slots at every step
                # boundary — a retired sequence's page is reusable on the
                # very next step, ragged completions never stall the batch
                while self._waiting and self._free_slot() is not None:
                    stream, ids, max_new, sp = self._waiting.popleft()
                    try:
                        self._admit(stream, ids, max_new, sp)
                    except Exception as e:  # noqa: BLE001 — fail ONE request
                        stream._close("error", e)
                active = any(s is not None for s in self._slots)
                if not active:
                    if self._draining and not self._waiting:
                        break
                    self._lock.wait(timeout=self._poll_s)
                    continue
                try:
                    if self.spec_k:
                        self._spec_round()
                    else:
                        self._decode_step()
                except Exception as e:  # noqa: BLE001 — device fault: fail
                    # every in-flight request loudly, keep serving new ones
                    for i, s in enumerate(self._slots):
                        if s is not None:
                            self._slots[i] = None
                            s.stream._close("error", e)
            if time.perf_counter() - last_emit >= self._emit_interval_s:
                self._emit_token_counters()
                last_emit = time.perf_counter()
        self._emit_token_counters()
