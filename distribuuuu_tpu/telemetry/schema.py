"""The ``kind=`` schema registry: every record kind any part of
``distribuuuu_tpu`` emits — through ``utils/jsonlog.metrics_log`` or the
per-rank telemetry sink — is declared here with its required fields.

Two enforcement layers keep emitters and consumers (telemetry/export.py,
tools/run_report.py, external jq/pandas users) from drifting apart:

* **static** — ``tools/check_telemetry_schema.py`` (tier-1 via
  tests/test_telemetry.py) AST-scans every emit call site in the package:
  an undeclared kind string, or a literal-kind call missing a required
  field, fails the build;
* **dynamic** — ``validate_record`` checks real emitted records (tests
  run it over whole rank files and metrics.jsonl).

Required = the fields consumers depend on; emitters may add free-form
extras (span attrs, serve snapshot extensions) without declaring them.
"""

from __future__ import annotations

# kind -> frozenset of required fields (beyond the envelope: jsonlog
# records carry {"t"}, telemetry records {"rank", "t"}).
KINDS: dict[str, frozenset] = {
    # -- train/eval loop (utils/jsonlog.py, primary metrics.jsonl) --------
    "train": frozenset({"epoch", "batch", "loss", "top1", "topk", "lr"}),
    "eval": frozenset({"epoch", "loss", "top1", "topk", "samples"}),
    "epoch": frozenset({"epoch", "acc1", "best_acc1"}),
    "timeline": frozenset({"v", "phase", "epoch", "batch", "n"}),
    # -- parallelism / serving -------------------------------------------
    "pp_bubble": frozenset({"stages", "microbatches", "ticks", "bubble"}),
    # the derived ZeRO collective schedule, once per distinct shape at
    # lowering time (parallel/partition/lowering._log_zero_schedule):
    # leaves resting sharded, entry gathers hoisted by gather-once, and
    # the ZERO.OVERLAP / ZERO.GATHER_AHEAD knobs the step compiled under
    "zero.schedule": frozenset(
        {"stage", "leaves", "sharded", "hoisted", "overlap", "gather_ahead"}
    ),
    "serve": frozenset(
        {"requests", "rejected", "batches", "throughput_rps", "p50_ms",
         "p90_ms", "p99_ms", "batch_occupancy"}
    ),
    # -- serving fleet (serve/fleet/: router + pool + autoscaler) --------
    "fleet.stats": frozenset(
        {"replicas", "routable", "requests", "rejected", "rerouted",
         "p50_ms", "p90_ms", "p99_ms"}
    ),
    "fleet.replica": frozenset(
        {"replica", "routable", "inflight", "queue_depth", "ewma_ms",
         "requests"}
    ),
    "fleet.scale": frozenset({"action", "reason", "n_before", "n_after"}),
    # -- resilience (rank-local: mirrored to the per-rank sink) ----------
    "stall": frozenset({"age_s", "count"}),
    "data_error": frozenset({"index", "attempts", "error"}),
    "nonfinite": frozenset({"epoch", "batch", "policy"}),
    # -- telemetry layer (per-rank sink, telemetry/spans.py) -------------
    "clock": frozenset({"unix", "mono"}),
    "span": frozenset({"v", "name", "t0", "dur", "track"}),
    "registry": frozenset({"v", "counters", "gauges", "histograms"}),
    "compile": frozenset({"event", "dur_s", "mono"}),
    "memstats": frozenset({"device", "bytes_in_use", "peak_bytes_in_use"}),
    # -- async execution plane (asyncplane/) -----------------------------
    # one per async checkpoint save: the on-path (device→host snapshot)
    # vs off-path (background payload+manifest commit) time split
    "ckpt.async": frozenset({"ckpt", "snapshot_s", "commit_s", "ok"}),
    # one per persistent-compilation-cache lookup (telemetry/runtime.py):
    # event "hit"|"miss" + the process-lifetime running tallies
    "compile.cache": frozenset({"event", "hits", "misses"}),
    # dispatch sequencer stats (asyncplane/sequencer.py), emitted at
    # epoch boundaries: running token/fence aggregates of the ring
    "dispatch.token": frozenset({"tokens", "max_wait_s", "fence_waits"}),
    # a wedged dispatcher flagged by the sequencer's watchdog (the
    # monitor's dispatch-wedge rule input)
    "dispatch.wedge": frozenset({"age_s", "holder", "count"}),
    # cross-host dispatch ring aggregates (asyncplane/ring.py), emitted
    # at epoch boundaries next to dispatch.token: role is "leader" |
    # "follower", slots/waits are the ring-granted dispatch counts
    "dispatch.ring": frozenset(
        {"host", "hosts", "role", "slots", "max_wait_s", "wedged"}
    ),
    # one per host per multi-host async save: the cross-host commit
    # barrier wait (asyncplane/committer.py multihost_commit)
    "ckpt.barrier": frozenset({"ckpt", "host", "hosts", "wait_s"}),
    # one per host per SHARDED async save (utils/checkpoint._save_sharded):
    # this host's own-shard write — count, bytes, duration
    "ckpt.shard": frozenset(
        {"ckpt", "host", "hosts", "shards", "bytes", "write_s"}
    ),
    # -- XLA cost-model ledger (telemetry/costmodel.py) ------------------
    # per-step flops/bytes from cost_analysis (source "xla") or the hand
    # table (source "analytic"); peak_flops is the full-mesh peak so
    # post-mortem consumers (run_report, monitor) need no jax
    "cost.step": frozenset(
        {"v", "label", "phase", "flops", "images", "steps_per_call",
         "peak_flops", "source"}
    ),
    # executable HBM footprint vs device capacity (memory_analysis)
    "cost.memory": frozenset(
        {"v", "label", "phase", "total_bytes", "capacity_bytes",
         "headroom_pct", "source"}
    ),
    # arithmetic intensity vs the device ridge point
    "cost.roofline": frozenset(
        {"v", "label", "phase", "arithmetic_intensity", "ridge_intensity",
         "bound", "source"}
    ),
    # -- LM workload plane (lm/generate.py + lm/service.py, ISSUE 12) ----
    # cumulative token counters of a generation engine (interval + drain):
    # run_report's tokens/s source
    "lm.tokens": frozenset(
        {"prompt_tokens", "new_tokens", "decode_steps", "elapsed_s"}
    ),
    # one per request admission into a continuous-batching slot
    "gen.admit": frozenset({"slot", "prompt_tokens", "request"}),
    # one per prompt prefill (the compute-bound half)
    "gen.prefill": frozenset({"tokens", "tile", "ms"}),
    # one per CHUNKED prompt prefill (ISSUE 19): the prompt streamed into
    # the paged cache in `chunks` fixed `chunk`-token appends against a
    # `tile`-wide page — the long-context admission path (run_report's
    # chunked-prefill ms source)
    "gen.chunk_prefill": frozenset(
        {"tokens", "chunk", "chunks", "tile", "ms"}
    ),
    # one per decode step over the live (batch, cache-len) tile (the
    # memory-bound half — run_report's decode p50/p99 source)
    "gen.decode": frozenset({"active", "tile_b", "tile_c", "ms"}),
    # one per sequence retirement (reason: eos/max_new_tokens/cache_full)
    "gen.retire": frozenset({"slot", "new_tokens", "reason", "request"}),
    # one per speculative round (ISSUE 17c): K drafted, `proposed` actual
    # proposals across active slots, `accepted` + `bonus` tokens emitted —
    # run_report's acceptance-ratio source. accepted/proposed ≈ draft
    # quality; (accepted+bonus)/rounds > 1 is the speedup condition.
    "gen.speculate": frozenset(
        {"k", "active", "proposed", "accepted", "bonus", "ms"}
    ),
    # one per non-greedy admission: the ctrl-frame sampling params that
    # replay this stream bit-identically on any replica (ISSUE 17b)
    "gen.sample": frozenset(
        {"request", "temperature", "top_k", "top_p", "seed"}
    ),
    # -- Pallas kernel tier (ops/pallas/, ISSUE 13) ----------------------
    # one per kernel-impl resolution (ops.pallas.select): which impl
    # actually runs for an op vs what KERNELS.* requested — the source
    # of run_report's `kernels` section. Where the kernel runs, a knobless
    # op adds what it chose, once a traced shape: `moe_gmm` tm, tk, tn,
    # pad_row_share, calls_a_step, experts_held (of experts_total the router
    # ranges over), rows_bound (the rows the buffer is sized for; the tile
    # is decided on the expected rows * held / total); `moe_rows` (the row
    # movers of a held share of the experts, ops/pallas/moe_rows.py) tm,
    # rows_bound, experts_held, experts_total; `flash_attn` L, d,
    # causal, blk_q, blk_k,
    # and a sequence's tiles_visited, tiles_crossed (by the diagonal or the
    # padding), tiles_masked (those that run the mask), bwd_matmuls_a_tile;
    # `short_conv` (ops/pallas/short_conv.py) seq_block, row_chunk,
    # lane_chunk, taps, channels, tokens; `head_prologue`
    # (ops/pallas/head_prologue.py) rows, heads, head_dim, rotary,
    # row_block, row_chunk; `ssd` (ops/ssd.py: Mamba-2's chunked scan, impl
    # "pallas" where ops/pallas/ssd.py's two calls run it and "xla" where
    # the jax.numpy body does: whichever, the operation's own shape) chunk,
    # chunks_a_sequence, heads, groups, state, head_dim
    "kernel.select": frozenset({"op", "impl", "requested"}),
    # a forced-but-unsupported site degrading to the XLA reference, with
    # the disqualifying reason (also warn-once logged)
    "kernel.fallback": frozenset({"op", "requested", "reason"}),
    # one per traced shape of a looped stack (models/ouro.py): R passes over
    # L blocks, what the R x L block applications keep for the backward
    # (bytes a step: their float32 inputs, kept_branch_bytes, the outputs of
    # the branches the backward reads again, and kept_flash_bytes, the flash
    # kernel's output, log-sum-exp, q, k and v, 0 where the scan path ran;
    # and kept_proj_bytes, the gated MLP's two products in the LAST
    # kept_proj_applications of the forward), what the backward computes
    # again, and the numbers that count was planned from at trace time
    # (models/ouro.plan_kept_proj): the capacity of the device the step is
    # compiled for (None: nothing planned), the bytes the step is known to
    # hold from shapes, and the reserve left unplanned
    "loop.plan": frozenset(
        {"layers", "passes", "block_applications", "kept_bytes",
         "kept_branch_bytes", "kept_flash_bytes", "recomputed",
         "kept_proj_applications", "kept_proj_bytes", "capacity_bytes",
         "planned_bytes", "reserve_bytes"}
    ),
    # one per traced shape of a model that is one chip's share of an
    # expert-parallel group (models/glm_moe.py): how many chips share each
    # layer and which of them this is, what it holds of the routed experts
    # and of the vocabulary's rows, what its recomputed blocks keep (as
    # loop.plan) and what its backward computes again; models/lfm2_moe.py
    # adds layer_kinds (layer_types' word for each layer it built, in order)
    # and dense_layers (how many of them carry the dense MLP);
    # models/nemotron_h.py, whose share has a second divisor, tensor_chips
    # (of the chips, those that divide the vocabulary and each mixer's heads)
    # and the heads held and published: mamba_heads_held/_total,
    # attn_heads_held/_total, kv_heads_held/_total
    "share.plan": frozenset(
        {"share_chips", "share_rank", "experts_held", "experts_total", "vocab_held",
         "vocab_total", "kept_bytes", "kept_branch_bytes", "kept_flash_bytes",
         "recomputed"}
    ),
    # -- live observability plane (telemetry/live.py, tools/monitor.py) --
    # one windowed aggregate per monitor tick (MONITOR.jsonl)
    "monitor.snapshot": frozenset(
        {"v", "window_s", "steps", "straggler_skew", "events", "compiles",
         "totals"}
    ),
    # a rule firing (alert-rule engine; dedup'd per excursion)
    "alert": frozenset({"rule", "value", "threshold", "message"}),
    # -- soak referee (soak.py / tools/soak.py) --------------------------
    # one per soak interval: injected fault class vs raised alerts + gate
    "soak.interval": frozenset(
        {"interval", "name", "expected_alerts", "raised_alerts", "ok"}
    ),
    # the final verdict record mirrored into SOAK_*.json
    "soak.verdict": frozenset(
        {"ok", "intervals", "alerts_exact", "control_clean",
         "gates_evaluated"}
    ),
    # -- traffic-campaign plane (serve/campaign/, ISSUE 16) --------------
    # one per campaign phase: expected vs raised alerts + the phase gate
    "campaign.phase": frozenset(
        {"campaign", "phase", "expected_alerts", "raised_alerts", "ok"}
    ),
    # the final per-campaign verdict mirrored into SERVE_CAMPAIGN_*.json
    "campaign.verdict": frozenset(
        {"campaign", "phases", "alerts_exact", "control_clean", "ok"}
    ),
    # per-model routing stats on a multi-model fleet (router telemetry)
    "fleet.model_route": frozenset(
        {"model", "requests", "rejected", "degraded_in", "degraded_out",
         "p99_ms"}
    ),
    # per-length-class routing stats on a length-aware fleet (ISSUE 19):
    # one row per observed class ("short" / "long" by the router's
    # SERVE.LONG_PROMPT_THRESHOLD token split) — run_report's evidence
    # that long-prompt admission backpressured while short-class p99 held
    "fleet.length_class": frozenset(
        {"length_class", "threshold", "requests", "rejected", "p99_ms"}
    ),
    # one per quantized engine start: the weight repack's footprint
    "serve.quantized": frozenset(
        {"arch", "mode", "bytes_before", "bytes_after", "leaves"}
    ),
    # -- request-scoped tracing plane (telemetry/tracectx.py, ISSUE 20) --
    # one stage of one traced request's span tree: `trace` is the fleet-
    # wide trace id opened at the client edge, `span` this stage's id,
    # `parent` the parent span id ("" at the root) — together the records
    # from N rank files reassemble into one connected tree per request
    # (export.py renders one track per request; tools/trace_request.py
    # renders the waterfall). `t0` is THIS rank's mono clock (anchor-
    # mapped like kind="span"); free-form extras carry stage detail
    # (replica, tokens, chunk, reason, ...).
    "trace.span": frozenset({"v", "trace", "span", "parent", "name",
                             "t0", "dur"}),
    # one per exemplar a fired alert names (ISSUE 20 satellite): the
    # worst-latency trace ids inside the breaching window, so a p99
    # breach points at concrete requests instead of a percentile
    "trace.exemplar": frozenset({"v", "rule", "trace", "latency_ms"}),
}

# -- program spans (telemetry/spans.py) ----------------------------------
# span name -> layer, for every name the package passes to ``span()``,
# ``emit_span()`` or ``annotate()`` (static check: analysis/passes/
# telemetry.py). The JSONL record keeps the bare name; the profiler-side
# twin of the same interval is the ``jax.profiler.TraceAnnotation``
# ``dtpu.<layer>.<name>`` (``ANNOTATIONS``), which lands in any profiler
# capture on the device's clock: every name has its annotation site, the
# loader's worker threads (decode, assemble) included. PERF.md "spans,
# counters and scopes" says which metric reads each.
ANNOTATION_PREFIX = "dtpu."
SPANS: dict[str, str] = {
    # trainer loop (trainer.train_epoch and validate, data/loader.
    # device_prefetch): ``epoch`` holds the other four on the loop's thread
    "epoch": "trainer",
    "wait": "trainer",
    "h2d": "trainer",
    "step": "trainer",
    "metrics_fetch": "trainer",
    # loader worker threads (data/loader.py)
    "decode": "loader",
    "assemble": "loader",
    # checkpoint save/restore (utils/checkpoint.py)
    "ckpt_save": "ckpt",
    "ckpt_snapshot": "ckpt",
    "ckpt_commit": "ckpt",
    "ckpt_restore": "ckpt",
}
ANNOTATIONS: dict[str, str] = {
    name: f"{ANNOTATION_PREFIX}{layer}.{name}" for name, layer in SPANS.items()
}


# -- device scopes (jax.named_scope) and kernel names -----------------------
# What a device trace can be split by: the named scopes the step program is
# traced under (HLO ``op_name`` metadata; the backward carries the forward's
# scopes under ``bwd``) and the ``name=`` of the Pallas calls, each with the
# layer of PERF.md's table whose metrics read it. XLA:TPU's own kernels
# (``ragged-dot-*``, the grouped expert matmuls where no tile of
# ``dtpu_moe_gmm_*`` fits) drop their scope and are found by name.
DEVICE_SCOPES: dict[str, str] = {
    # parallel/partition/lowering.py
    "fwd": "models",
    "bwd": "models",
    "lm_head": "models",
    "optimizer_update": "kernels",
    "eval_fwd": "models",
    # models/olmoe.py, ops/moe.py
    "attn": "models",
    "moe": "models",
    "moe_route": "models",
    "moe_experts": "kernels",
    # models/ouro.py (``attn`` and ``lm_head`` as above)
    "mlp": "models",
    "exit_gate": "models",
    # models/glm_moe.py (``attn``, ``moe``, ``mlp`` and ``lm_head`` as
    # above): latent attention's projections, norms and rotary inside
    # ``attn``; the shared expert inside ``moe``; the MTP module
    "mla_latent": "models",
    "moe_shared": "models",
    "mtp": "models",
    # models/lfm2_moe.py (``attn``, ``mlp``, ``moe`` and ``lm_head`` as
    # above): the gated short-convolution mixer whole, and inside it all
    # that is no matmul (the two gates and the filter; ops/short_conv.py)
    "short_conv": "models",
    "short_conv_gate": "kernels",
    # models/afmoe.py (``attn``, ``mlp``, ``moe``, ``moe_shared`` and
    # ``lm_head`` as above): inside ``attn``, a sliding-window layer's mixer
    # whole (a full-attention layer's carries no second scope), and the
    # attention output's gate (projection, sigmoid, product) of either kind
    "attn_window": "models",
    "attn_gate": "models",
    # models/sdar_moe.py (``attn``, ``moe`` and ``lm_head`` as above): the
    # draws of the diffusion objective's noise and the noised copy of the
    # tokens; inside ``attn``, the mixer under the block-diffusion mask
    "diffusion_noise": "models",
    "attn_diffusion": "models",
    # models/lfm2_moe.HeadNorm (inside ``attn``, and inside ``attn_window`` /
    # ``attn_diffusion`` where a layer has one): a q or k projection's way to
    # the attention kernels and NOT the projection: the heads-major layout,
    # the per-head norm, the rotary, the cast (ops/head_prologue.py)
    "attn_prologue": "kernels",
    # models/nemotron_h.py (``attn``, ``moe``, ``moe_shared`` and ``lm_head``
    # as above): a Mamba-2 mixer whole and, inside it, all but its two
    # projections (the convolution and its silu, dt's softplus, ops/ssd.py's
    # chunked scan, D's skip, the gated norm); inside ``moe``, the two
    # projections into and out of LatentMoE's latent
    "ssm": "models",
    "ssm_scan": "kernels",
    "moe_latent": "models",
    # ops/pallas/opt_update.py
    "opt_tile": "kernels",
    "opt_kernel": "kernels",
}
KERNEL_NAMES: tuple[str, ...] = (
    "dtpu_opt_update_sgd", "dtpu_opt_update_sgd_plain", "dtpu_opt_update_adamw",
    "dtpu_conv_epilogue", "dtpu_decode_attn",
    "dtpu_flash_fwd", "dtpu_flash_bwd",
    # empty calls, kept for the benchmark's ``trace_kernels`` alone
    # (ops/flash_attention._under_the_old_name)
    "dtpu_flash_dq", "dtpu_flash_dkdv",
    # ops/pallas/moe_gmm.py, one prefix: _gate_up, _fwd, _act_bwd,
    # _dx_gate_up, _dw_down, _dw_gate_up (and _dx, _dw of the bare calls);
    # the two-matrix relu2 expert's _up_sq, _fwd, _sq_bwd, _dx, _dw_down, _dw_up
    "dtpu_moe_gmm",
    # ops/pallas/moe_rows.py, one prefix: _pack, _take, _combine (under
    # ``moe_route``: the rows of the live tiles into and out of the buffer)
    "dtpu_moe_rows",
    # ops/pallas/short_conv.py: _fwd, _bwd (under ``short_conv_gate``: the
    # two gates and the filter of a ``conv`` layer, one call each way)
    "dtpu_short_conv",
    # ops/pallas/head_prologue.py: _fwd, _bwd (under ``attn_prologue``: q's
    # and k's norm, rotary and layout, one call each way)
    "dtpu_head_prologue",
    # ops/pallas/ssd.py: _fwd, _bwd (under ``ssm_scan``: Mamba-2's chunked
    # scan, one call each way)
    "dtpu_ssd",
)


class SchemaError(ValueError):
    """A record (or call site) violates the declared kind schema."""


def check_fields(kind: str, fields) -> None:
    """Raise SchemaError on an undeclared kind or missing required
    fields; ``fields`` is any iterable of field names."""
    if kind not in KINDS:
        raise SchemaError(
            f"undeclared kind {kind!r} — declare it (with its required "
            "fields) in distribuuuu_tpu/telemetry/schema.py"
        )
    missing = KINDS[kind] - set(fields)
    if missing:
        raise SchemaError(
            f"kind {kind!r} missing required fields {sorted(missing)} "
            f"(declared in telemetry/schema.py)"
        )


def validate_record(rec: dict) -> None:
    """Dynamic check of one emitted record (a parsed JSONL line)."""
    kind = rec.get("kind")
    if kind is None:
        raise SchemaError(f"record has no 'kind': {rec}")
    check_fields(kind, rec.keys())
