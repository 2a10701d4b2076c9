"""Run health + regression report from a finished run's telemetry.

Merges the per-rank telemetry files (``{run}/telemetry/rank*.jsonl`` —
spans, compile events, registry snapshots, mirrored resilience events)
with the primary ``metrics.jsonl`` and answers the operator questions the
scattered sinks couldn't: is a rank straggling, is the run input-bound,
did anything recompile mid-run, what did checkpoints cost, did resilience
machinery fire — printed as a table and written as ``RUN_REPORT.json``.

    # report + merged Perfetto trace (trace.json) in one command:
    python tools/run_report.py --trace out/

    # regression gate against a committed reference point:
    python tools/run_report.py out/ --compare BENCH_INDEX.json --tol-pct 10

Metrics:

* **step time** — per-rank p50/p90/p99/mean from the per-rank ``step``
  spans; straggler skew = slowest rank p50 / fastest rank p50 (1.0 =
  lockstep).
* **data-wait fraction** — tools/overlap_report.py's exact attribution
  when timeline records exist (reused, not reimplemented); otherwise the
  per-rank ``wait`` span fraction of the pipeline wall.
* **resilience events** — stall / data_error / nonfinite counts across
  ALL ranks (the per-rank sink is what makes ranks > 0 visible).
* **recompiles** — ``kind="compile"`` count + wall seconds per rank.
* **checkpoints** — save/restore span count, mean, max — split into
  on-critical-path time (synchronous ``ckpt_save`` spans + async
  ``ckpt_snapshot`` spans: what the trainer actually blocked for) and
  off-path time (``ckpt_commit`` spans: the background committer's wall,
  ``CHECKPOINT.ASYNC`` — asyncplane/).
* **compile cache** — persistent-compilation-cache hits/misses
  (``kind="compile.cache"``): a warm restart shows hits ≈ programs and
  recompiles ≈ 0.

``--compare BASELINE.json`` accepts a previous ``RUN_REPORT.json``, a
repo ``BENCH_*.json`` artifact (its ``parsed.value`` img/s becomes the
throughput reference), or the ``BENCH_INDEX.json`` trajectory written by
``tools/bench_history.py`` (the latest point of each throughput series —
the gate tracks the newest committed bench automatically). Direction-aware thresholds: ``--tol-pct`` (global,
default 10%) and repeatable ``--tol METRIC=PCT`` overrides; any metric
worse than its tolerance FAILs and the exit code is 1 — the CI gate
(tests/test_telemetry.py exercises both directions against a bench
record so the gate itself can't rot).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import _path  # noqa: F401  (repo root onto sys.path)

from distribuuuu_tpu.telemetry import export
from distribuuuu_tpu.telemetry.registry import percentile

REPORT_SCHEMA = 1

# direction-aware comparison sets: a metric is a regression when it moves
# the WRONG way by more than its tolerance
LOWER_BETTER = (
    "step_ms_p50", "step_ms_p90", "step_ms_p99", "data_wait_frac",
    "straggler_skew", "recompiles", "ckpt_save_max_s",
)
HIGHER_BETTER = ("img_per_sec", "mfu", "hbm_headroom_pct")


def _load_ranks(run_dir: str) -> dict[int, list[dict]]:
    return {
        rank: export.read_jsonl(path)
        for rank, path in export.rank_files(run_dir).items()
    }


def _spans(recs: list[dict], name: str, phase: str | None = None) -> list[dict]:
    out = []
    for r in recs:
        if r.get("kind") != "span" or r.get("name") != name:
            continue
        if phase is not None and r.get("phase") != phase:
            continue
        out.append(r)
    return out


def _step_durs(recs: list[dict], phase: str) -> list[float]:
    """Per-step durations (seconds) for one rank, from its ``step`` spans."""
    return [float(r["dur"]) for r in _spans(recs, "step", phase)]


def _summary_ms(durs: list[float]) -> dict:
    vals = sorted(durs)
    ms = 1e3
    return {
        "count": len(vals),
        "mean_ms": round(sum(vals) / len(vals) * ms, 3) if vals else 0.0,
        "p50_ms": round(percentile(vals, 0.50) * ms, 3),
        "p90_ms": round(percentile(vals, 0.90) * ms, 3),
        "p99_ms": round(percentile(vals, 0.99) * ms, 3),
        "max_ms": round(vals[-1] * ms, 3) if vals else 0.0,
    }


def _wait_frac_from_spans(recs: list[dict], phase: str) -> float | None:
    """Fallback data-wait fraction for one rank: wait seconds over the
    pipeline-track wall (first span start → last span end)."""
    pipeline = [
        r for r in recs
        if r.get("kind") == "span" and r.get("track") == "pipeline"
        and (r.get("phase") == phase)
    ]
    if not pipeline:
        return None
    t0 = min(float(r["t0"]) for r in pipeline)
    t1 = max(float(r["t0"]) + float(r["dur"]) for r in pipeline)
    wall = max(t1 - t0, 1e-9)
    wait = sum(float(r["dur"]) for r in pipeline if r.get("name") == "wait")
    return wait / wall


def _cost_section(ranks: dict[int, list[dict]], phase: str,
                  mean_step_s: float | None) -> dict | None:
    """The MFU / roofline / HBM-headroom section from the cost-model
    ledger records (telemetry/costmodel.py emits them once per step
    program; the latest phase-matching record wins). Measured MFU =
    XLA flops/step ÷ measured mean step time ÷ mesh peak — the peak was
    resolved at capture time, so this stays jax-free post-mortem.
    ``source`` is "xla" or the flagged "analytic" fallback."""
    step_rec = roof_rec = None
    mem_recs: dict[str, dict] = {}
    for recs in ranks.values():
        for r in recs:
            kind = r.get("kind")
            if kind == "cost.step" and r.get("phase") == phase:
                step_rec = r
            elif kind == "cost.roofline" and r.get("phase") == phase:
                roof_rec = r
            elif kind == "cost.memory":
                mem_recs[str(r.get("label"))] = r
    if step_rec is None and not mem_recs:
        return None
    out = {
        "source": step_rec.get("source") if step_rec else None,
        "flops_per_step": step_rec.get("flops") if step_rec else None,
        "bytes_per_step": step_rec.get("bytes_accessed") if step_rec else None,
        "images_per_step": step_rec.get("images") if step_rec else None,
        "device_kind": step_rec.get("device_kind") if step_rec else None,
        "peak_flops": step_rec.get("peak_flops") if step_rec else None,
        "mfu": None,
        "roofline": None,
        "hbm": None,
    }
    if (
        step_rec and step_rec.get("flops") and step_rec.get("peak_flops")
        and mean_step_s
    ):
        out["mfu"] = round(
            float(step_rec["flops"]) / mean_step_s
            / float(step_rec["peak_flops"]), 4
        )
    if roof_rec is not None:
        out["roofline"] = {
            "arithmetic_intensity": roof_rec.get("arithmetic_intensity"),
            "ridge_intensity": roof_rec.get("ridge_intensity"),
            "bound": roof_rec.get("bound"),
            "nominal_peaks": roof_rec.get("nominal_peaks"),
        }
    if mem_recs:
        per_label = {
            label: {
                "total_bytes": r.get("total_bytes"),
                "capacity_bytes": r.get("capacity_bytes"),
                "headroom_pct": r.get("headroom_pct"),
            }
            for label, r in sorted(mem_recs.items())
        }
        headrooms = [
            v["headroom_pct"] for v in per_label.values()
            if v["headroom_pct"] is not None
        ]
        out["hbm"] = {
            "per_executable": per_label,
            "headroom_pct": min(headrooms) if headrooms else None,
            "capacity_source": next(iter(mem_recs.values())).get(
                "capacity_source"
            ),
        }
    return out


def _count_events(ranks: dict[int, list[dict]], metrics: list[dict]) -> dict:
    """stall/data_error/nonfinite tallies. Rank files carry every record
    (jsonlog mirrors into them), so they are authoritative when present;
    a telemetry-off run falls back to the primary metrics.jsonl (which
    only ever saw rank 0)."""
    kinds = ("stall", "data_error", "nonfinite")
    out = {k: 0 for k in kinds}
    source = ranks.values() if ranks else [metrics]
    for recs in source:
        for r in recs:
            if r.get("kind") in kinds:
                out[r["kind"]] += 1
    return out


def _lm_section(ranks: dict[int, list[dict]]) -> dict | None:
    """The LM workload plane (lm/generate.py): generation tokens/s from
    the cumulative ``lm.tokens`` counters (last record per rank wins) and
    prefill/decode latency percentiles from the per-step ``gen.*``
    records — the ISSUE 12 surfacing satellite. None when the run has no
    LM records (image runs are untouched)."""
    last_tokens: dict[int, dict] = {}
    dec_ms: list[float] = []
    pre_ms: list[float] = []
    chunk_ms: list[float] = []
    chunk_calls = 0
    admits = retires = 0
    reasons: dict[str, int] = {}
    admit_classes: dict[str, int] = {}
    spec_rounds = spec_proposed = spec_accepted = spec_bonus = 0
    for rank, recs in sorted(ranks.items()):
        for r in recs:
            kind = r.get("kind")
            if kind == "lm.tokens":
                last_tokens[rank] = r
            elif kind == "gen.decode":
                dec_ms.append(float(r["ms"]))
            elif kind == "gen.prefill":
                pre_ms.append(float(r["ms"]))
            elif kind == "gen.chunk_prefill":
                chunk_ms.append(float(r["ms"]))
                chunk_calls += int(r.get("chunks", 0))
            elif kind == "gen.admit":
                admits += 1
                lc = r.get("length_class")
                if lc:
                    admit_classes[str(lc)] = admit_classes.get(str(lc), 0) + 1
            elif kind == "gen.retire":
                retires += 1
                reason = str(r.get("reason"))
                reasons[reason] = reasons.get(reason, 0) + 1
            elif kind == "gen.speculate":
                spec_rounds += 1
                spec_proposed += int(r.get("proposed", 0))
                spec_accepted += int(r.get("accepted", 0))
                spec_bonus += int(r.get("bonus", 0))
    if not (last_tokens or dec_ms or pre_ms):
        return None
    new_tokens = sum(int(r.get("new_tokens", 0)) for r in last_tokens.values())
    prompt_tokens = sum(
        int(r.get("prompt_tokens", 0)) for r in last_tokens.values()
    )
    decode_steps = sum(
        int(r.get("decode_steps", 0)) for r in last_tokens.values()
    )
    tokens_per_s = round(sum(
        int(r.get("new_tokens", 0)) / max(float(r.get("elapsed_s", 0.0)), 1e-9)
        for r in last_tokens.values()
    ), 3) if last_tokens else None
    out = {
        "prompt_tokens": prompt_tokens,
        "new_tokens": new_tokens,
        "decode_steps": decode_steps,
        "tokens_per_s": tokens_per_s,
        "admits": admits,
        "retires": retires,
        "retire_reasons": reasons,
        "decode": _summary_ms([v / 1e3 for v in dec_ms]),
        "prefill": _summary_ms([v / 1e3 for v in pre_ms]),
    }
    if chunk_ms:
        # chunked paged prefill (ISSUE 19c): per-prompt wall + total
        # fixed-width chunk appends — the long-context admission path
        out["chunk_prefill"] = {
            "prompts": len(chunk_ms),
            "chunk_calls": chunk_calls,
            **_summary_ms([v / 1e3 for v in chunk_ms]),
        }
    if admit_classes:
        out["admit_length_classes"] = admit_classes
    if spec_rounds:
        # acceptance ratio = accepted/proposed (draft quality); tokens
        # per round = (accepted+bonus+rejections-resampled)/rounds — the
        # roofline win condition is emitted tokens/round > 1 (ISSUE 17)
        out["speculate"] = {
            "rounds": spec_rounds,
            "proposed": spec_proposed,
            "accepted": spec_accepted,
            "bonus": spec_bonus,
            "acceptance_ratio": round(
                spec_accepted / max(spec_proposed, 1), 4
            ),
            "accepted_per_round": round(
                (spec_accepted + spec_bonus) / spec_rounds, 3
            ),
        }
    return out


def _trace_section(run_dir: str, ranks: dict[int, list[dict]]) -> dict | None:
    """The request-tracing plane (ISSUE 20): per-length-class p50/p99 of
    total latency and of each stage's SHARE of it (queue wait, prefill,
    decode residency, speculation), computed from the ``trace.span``
    records tools/trace_request.py reassembles. The share percentiles
    answer "where do slow requests spend their time" without opening a
    single waterfall. None when the run was untraced."""
    if not any(
        r.get("kind") == "trace.span" for recs in ranks.values()
        for r in recs
    ):
        return None
    import trace_request

    traces = trace_request.collect_traces(run_dir)
    breakdown = trace_request.breakdown_by_class(traces)
    exemplars = sorted(
        {
            str(r.get("trace")) for recs in ranks.values() for r in recs
            if r.get("kind") == "trace.exemplar"
        }
    )
    return {
        "requests": len(traces),
        "connected": sum(
            1 for spans in traces.values()
            if trace_request.is_connected(spans)
        ),
        "by_length_class": breakdown,
        "exemplar_trace_ids": exemplars or None,
    }


def _campaign_section(ranks: dict[int, list[dict]]) -> dict | None:
    """The traffic-campaign plane (serve/campaign/): per-campaign verdicts
    (``campaign.verdict``), per-phase expected-vs-raised alert gates
    (``campaign.phase``), per-model routing totals on multi-model fleets
    (``fleet.model_route``, last record per model wins), per-length-class
    routing totals on length-aware fleets (``fleet.length_class``,
    ISSUE 19c), and any quantized engine starts (``serve.quantized``).
    None when the run carried no campaign records (training and plain
    serve runs are untouched)."""
    phases: list[dict] = []
    verdicts: list[dict] = []
    model_route: dict[str, dict] = {}
    length_classes: dict[str, dict] = {}
    quantized: list[dict] = []
    for recs in ranks.values():
        for r in recs:
            kind = r.get("kind")
            if kind == "campaign.phase":
                phases.append({
                    "campaign": r.get("campaign"), "phase": r.get("phase"),
                    "expected_alerts": r.get("expected_alerts"),
                    "raised_alerts": r.get("raised_alerts"),
                    "ok": r.get("ok"),
                })
            elif kind == "campaign.verdict":
                verdicts.append({
                    "campaign": r.get("campaign"),
                    "phases": r.get("phases"),
                    "alerts_exact": r.get("alerts_exact"),
                    "control_clean": r.get("control_clean"),
                    "ok": r.get("ok"),
                })
            elif kind == "fleet.model_route":
                model_route[str(r.get("model"))] = {
                    "requests": r.get("requests"),
                    "rejected": r.get("rejected"),
                    "degraded_in": r.get("degraded_in"),
                    "degraded_out": r.get("degraded_out"),
                    "p99_ms": r.get("p99_ms"),
                }
            elif kind == "fleet.length_class":
                # length-aware routing (ISSUE 19c): last record per class
                # wins — the long-vs-short admission/latency evidence
                length_classes[str(r.get("length_class"))] = {
                    "threshold": r.get("threshold"),
                    "requests": r.get("requests"),
                    "rejected": r.get("rejected"),
                    "p99_ms": r.get("p99_ms"),
                }
            elif kind == "serve.quantized":
                quantized.append({
                    "arch": r.get("arch"), "mode": r.get("mode"),
                    "bytes_before": r.get("bytes_before"),
                    "bytes_after": r.get("bytes_after"),
                })
    if not (phases or verdicts or model_route or length_classes
            or quantized):
        return None
    return {
        "campaigns": len(verdicts),
        "ok": all(v["ok"] for v in verdicts) if verdicts else None,
        "verdicts": verdicts,
        "phases": phases,
        "model_route": model_route or None,
        "length_classes": length_classes or None,
        "quantized": quantized or None,
    }


# what every kernel.select record holds; the rest is the op's own detail
_RECORD_KEYS = ("kind", "rank", "t", "v", "op", "impl", "requested")


def _recomputed(plan: dict) -> str:
    """What a ``share.plan``/``loop.plan`` record says its backward
    computes again and, where the record has them, the bytes kept."""
    said = f"recomputed: {plan['recomputed']}"
    if plan.get("kept_bytes") is not None:
        said += f"; kept {plan['kept_bytes'] / 2**20:.1f} MiB a step"
        if plan.get("kept_flash_bytes") is not None:
            said += (f", {plan['kept_flash_bytes'] / 2**20:.1f} of them the "
                     "flash kernel's output, log-sum-exp, q, k and v")
        if plan.get("kept_branch_bytes") is not None:
            said += (f", {plan['kept_branch_bytes'] / 2**20:.1f} the branches' "
                     "outputs")
        if plan.get("kept_proj_applications") is not None:
            said += (f", {plan['kept_proj_bytes'] / 2**20:.1f} the MLP's two "
                     f"products in the last {plan['kept_proj_applications']} "
                     "applications")
            if plan.get("capacity_bytes"):
                said += (f" (planned {plan['planned_bytes'] / 2**30:.2f} GiB of "
                         f"{plan['capacity_bytes'] / 2**30:.2f} less a reserve "
                         f"of {plan['reserve_bytes'] / 2**30:.2f})")
            else:
                said += " (no device to plan for)"
    return said


def _kernels_section(ranks: dict[int, list[dict]]) -> dict | None:
    """The Pallas kernel tier (ops/pallas/): which impl actually ran per
    op (``kernel.select``; with what a knobless op says it chose: the tiles
    of ``moe_gmm``, the blocks and tile counts of ``flash_attn``, the
    record traced last), every forced-but-unsupported fallback with
    its reason (``kernel.fallback``), and — when the run carried
    ``kernel_*``-labeled cost records (tools/kernel_bench.py emits them)
    — the per-kernel A/B deltas. Beside them ``share_plan``: what of each
    layer a chip of an expert-parallel group holds (``share.plan``,
    models/glm_moe.py), and ``loop_plan``: what a looped stack runs
    (``loop.plan``, models/ouro.py); both say what their recomputed blocks
    keep (the record traced last). None when the run never consulted the
    tier (pre-tier runs are untouched)."""
    selected: dict[str, dict] = {}
    fallbacks: list[dict] = []
    ab: dict[str, dict] = {}
    plans: dict[str, dict] = {}
    for recs in ranks.values():
        for r in recs:
            kind = r.get("kind")
            if kind == "kernel.select":
                op = str(r.get("op"))
                selected[op] = {
                    "impl": r.get("impl"), "requested": r.get("requested"),
                    **{k: v for k, v in r.items() if k not in _RECORD_KEYS},
                }
            elif kind in ("share.plan", "loop.plan"):
                plans[kind] = {
                    k: v for k, v in r.items() if k not in _RECORD_KEYS}
            elif kind == "kernel.fallback":
                fallbacks.append({
                    "op": r.get("op"), "requested": r.get("requested"),
                    "reason": r.get("reason"),
                })
            elif kind == "cost.step" and str(r.get("label", "")).startswith(
                "kernel_"
            ):
                ab[str(r["label"])] = {
                    "flops": r.get("flops"),
                    "bytes_accessed": r.get("bytes_accessed"),
                }
    if not (selected or fallbacks):
        return None
    return {
        "selected": selected,
        "fallbacks": fallbacks,
        "ab": ab or None,
        "share_plan": plans.get("share.plan"),
        "loop_plan": plans.get("loop.plan"),
    }


def build_report(run_dir: str, phase: str = "train") -> dict:
    ranks = _load_ranks(run_dir)
    metrics_path = os.path.join(run_dir, "metrics.jsonl")
    metrics = export.read_jsonl(metrics_path) if os.path.exists(metrics_path) else []
    if not ranks and not metrics:
        raise FileNotFoundError(
            f"no telemetry under {run_dir}: expected telemetry/rank*.jsonl "
            "(TELEMETRY.ENABLED) and/or metrics.jsonl"
        )

    # -- cross-rank step time + straggler skew ---------------------------
    per_rank, pooled = {}, []
    for rank, recs in sorted(ranks.items()):
        durs = _step_durs(recs, phase)
        if not durs:
            continue
        per_rank[str(rank)] = _summary_ms(durs)
        pooled.extend(durs)
    rank_p50s = [s["p50_ms"] for s in per_rank.values() if s["count"]]
    straggler = (
        round(max(rank_p50s) / max(min(rank_p50s), 1e-9), 4)
        if len(rank_p50s) >= 2 else 1.0
    )

    # -- data-wait fraction + throughput ---------------------------------
    data_wait_frac = None
    img_per_sec = None
    timeline = [r for r in metrics if r.get("kind") == "timeline"]
    if timeline:
        import overlap_report

        try:
            att = overlap_report.attribute(timeline, phase=phase)
            data_wait_frac = att["data_wait_frac"]
            img_per_sec = att["img_per_sec"]
        except ValueError:
            pass
    if data_wait_frac is None:
        fracs = [
            f for f in (
                _wait_frac_from_spans(recs, phase) for recs in ranks.values()
            ) if f is not None
        ]
        if fracs:
            data_wait_frac = round(sum(fracs) / len(fracs), 4)

    # -- dispatch sequencer (asyncplane/sequencer.py) --------------------
    # running aggregates: the LAST dispatch.token record per rank wins;
    # dispatch.wedge flags are counted outright
    seq_last: dict[int, dict] = {}
    ring_last: dict[str, dict] = {}
    wedges = 0
    barrier_waits: dict[str, list[float]] = {}
    shard_recs: dict[str, list[dict]] = {}
    for rank, recs in sorted(ranks.items()):
        for r in recs:
            kind = r.get("kind")
            if kind == "dispatch.token":
                seq_last[rank] = r
            elif kind == "dispatch.ring":
                ring_last[str(r.get("host", rank))] = r
            elif kind == "dispatch.wedge":
                wedges += 1
            elif kind == "ckpt.barrier":
                barrier_waits.setdefault(
                    str(r.get("host", rank)), []
                ).append(float(r.get("wait_s", 0.0)))
            elif kind == "ckpt.shard":
                shard_recs.setdefault(
                    str(r.get("host", rank)), []
                ).append(r)
    sequencer = None
    if seq_last:
        sequencer = {
            "tokens": sum(int(s.get("tokens", 0)) for s in seq_last.values()),
            "streams": {
                k: v for s in seq_last.values()
                for k, v in (s.get("streams") or {}).items()
            },
            "max_wait_s": max(
                float(s.get("max_wait_s", 0.0)) for s in seq_last.values()
            ),
            "total_wait_s": round(sum(
                float(s.get("total_wait_s", 0.0)) for s in seq_last.values()
            ), 6),
            "fence_waits": sum(
                int(s.get("fence_waits", 0)) for s in seq_last.values()
            ),
            "fence_wait_s": round(sum(
                float(s.get("fence_wait_s", 0.0)) for s in seq_last.values()
            ), 6),
            "wedges": wedges,
        }
        # cross-host dispatch ring (asyncplane/ring.py, multi-host runs):
        # the LAST dispatch.ring record per host — per-host slot counts
        # and ring waits, plus the wedge/detach degradation flags
        if ring_last:
            sequencer["ring"] = {
                "hosts": len(ring_last),
                "per_host": {
                    host: {
                        "role": r.get("role"),
                        "slots": int(r.get("slots", 0)),
                        "total_wait_s": round(
                            float(r.get("total_wait_s", 0.0)), 6
                        ),
                        "max_wait_s": round(
                            float(r.get("max_wait_s", 0.0)), 6
                        ),
                        "deadline_misses": int(r.get("deadline_misses", 0)),
                        "wedged": bool(r.get("wedged", False)),
                        "detached": bool(r.get("detached", False)),
                    }
                    for host, r in sorted(ring_last.items())
                },
            }

    # -- recompiles / checkpoints / resilience events --------------------
    compiles = {"count": 0, "wall_s": 0.0}
    cache = {"hits": 0, "misses": 0}
    ckpt = {"saves": 0, "save_mean_s": 0.0, "save_max_s": 0.0,
            "restores": 0, "restore_mean_s": 0.0,
            "snapshots": 0, "snapshot_mean_s": 0.0, "snapshot_max_s": 0.0,
            "commits": 0, "commit_mean_s": 0.0, "commit_max_s": 0.0,
            "on_path_s": 0.0, "off_path_s": 0.0}
    saves, restores, snaps, commits = [], [], [], []
    for recs in ranks.values():
        for r in recs:
            if r.get("kind") == "compile":
                compiles["count"] += 1
                compiles["wall_s"] += float(r["dur_s"])
            elif r.get("kind") == "compile.cache":
                if r.get("event") == "hit":
                    cache["hits"] += 1
                elif r.get("event") == "miss":
                    cache["misses"] += 1
        saves += [float(r["dur"]) for r in _spans(recs, "ckpt_save")]
        restores += [float(r["dur"]) for r in _spans(recs, "ckpt_restore")]
        snaps += [float(r["dur"]) for r in _spans(recs, "ckpt_snapshot")]
        commits += [float(r["dur"]) for r in _spans(recs, "ckpt_commit")]
    compiles["wall_s"] = round(compiles["wall_s"], 3)
    if saves:
        ckpt.update(saves=len(saves),
                    save_mean_s=round(sum(saves) / len(saves), 3),
                    save_max_s=round(max(saves), 3))
    if restores:
        ckpt.update(restores=len(restores),
                    restore_mean_s=round(sum(restores) / len(restores), 3))
    # async checkpointing (CHECKPOINT.ASYNC): the trainer blocks only for
    # the snapshot spans; commit spans run on the background committer —
    # on_path vs off_path is the headline the async plane is gated on
    if snaps:
        ckpt.update(snapshots=len(snaps),
                    snapshot_mean_s=round(sum(snaps) / len(snaps), 6),
                    snapshot_max_s=round(max(snaps), 6))
    if commits:
        ckpt.update(commits=len(commits),
                    commit_mean_s=round(sum(commits) / len(commits), 6),
                    commit_max_s=round(max(commits), 6))
    ckpt["on_path_s"] = round(sum(saves) + sum(snaps), 6)
    ckpt["off_path_s"] = round(sum(commits), 6)
    # multi-host async commit: the cross-host barrier wait per host
    # (ckpt.barrier records — asyncplane/committer.py multihost_commit)
    if barrier_waits:
        ckpt["barrier"] = {
            "hosts": len(barrier_waits),
            "per_host": {
                host: {
                    "saves": len(ws),
                    "mean_wait_s": round(sum(ws) / len(ws), 6),
                    "max_wait_s": round(max(ws), 6),
                }
                for host, ws in sorted(barrier_waits.items())
            },
        }
    # sharded multi-host saves (ckpt.shard records — utils/checkpoint.py
    # _save_sharded): each host writes its OWN shards; per-host commit cost
    if shard_recs:
        ckpt["shards"] = {
            "hosts": len(shard_recs),
            "per_host": {
                host: {
                    "saves": len(rs),
                    "shards": int(rs[-1].get("shards", 0)),
                    "bytes": int(rs[-1].get("bytes", 0)),
                    "mean_write_s": round(
                        sum(float(r.get("write_s", 0.0)) for r in rs)
                        / len(rs), 6,
                    ),
                    "max_write_s": round(
                        max(float(r.get("write_s", 0.0)) for r in rs), 6
                    ),
                }
                for host, rs in sorted(shard_recs.items())
            },
        }

    step_summary = _summary_ms(pooled)
    mean_step_s = (
        step_summary["mean_ms"] / 1e3 if step_summary["count"] else None
    )
    report = {
        "schema": REPORT_SCHEMA,
        "run_dir": os.path.abspath(run_dir),
        "phase": phase,
        "n_ranks": len(ranks),
        "step": step_summary,
        "per_rank_step": per_rank,
        "straggler_skew": straggler,
        "data_wait_frac": data_wait_frac,
        "img_per_sec": img_per_sec,
        "cost": _cost_section(ranks, phase, mean_step_s),
        "events": _count_events(ranks, metrics),
        "recompiles": compiles,
        "compile_cache": cache if (cache["hits"] or cache["misses"]) else None,
        "checkpoint": ckpt,
        "sequencer": sequencer,
        "lm": _lm_section(ranks),
        "kernels": _kernels_section(ranks),
        "campaign": _campaign_section(ranks),
        "trace": _trace_section(run_dir, ranks),
    }
    return report


# ------------------------------------------------------------- comparison
def comparable_metrics(doc: dict) -> dict:
    """Flatten a baseline/current document into the named comparison
    metrics. Accepts a RUN_REPORT.json (ours), a repo BENCH_*.json
    artifact (``parsed.metric``/``value`` — img/s becomes the throughput
    reference), or a BENCH_INDEX.json trajectory
    (tools/bench_history.py — the LATEST point of each throughput
    series, so the gate tracks the newest committed bench)."""
    out = {}
    if doc.get("bench_index"):
        for metric, points in (doc.get("series") or {}).items():
            if not points or metric.endswith("_vs_baseline"):
                continue  # ratios are derived, not a throughput reference
            if (
                ("images_per_sec" in metric or "img_per_sec" in metric)
                and not metric.endswith("_mfu")  # bench MFU series: a
                # ratio riding the throughput metric's name, not img/s
            ):
                out["img_per_sec"] = float(points[-1]["value"])
            # the cost-model series (tools/bench_history.py folds them in
            # from COSTMODEL_r*.json / bench mfu) gate like throughput
            elif metric == "train_step_mfu":
                out["mfu"] = float(points[-1]["value"])
            elif metric == "train_step_hbm_headroom_pct":
                out["hbm_headroom_pct"] = float(points[-1]["value"])
        return out
    if "step" in doc and isinstance(doc.get("step"), dict):
        for q in ("p50", "p90", "p99"):
            v = doc["step"].get(f"{q}_ms")
            if v:
                out[f"step_ms_{q}"] = float(v)
        if doc.get("straggler_skew") is not None:
            out["straggler_skew"] = float(doc["straggler_skew"])
        if doc.get("data_wait_frac") is not None:
            out["data_wait_frac"] = float(doc["data_wait_frac"])
        if doc.get("img_per_sec"):
            out["img_per_sec"] = float(doc["img_per_sec"])
        rc = doc.get("recompiles", {})
        if rc:
            out["recompiles"] = float(rc.get("count", 0))
        ck = doc.get("checkpoint", {})
        if ck.get("saves"):
            out["ckpt_save_max_s"] = float(ck["save_max_s"])
        cost = doc.get("cost") or {}
        if cost.get("mfu") is not None:
            out["mfu"] = float(cost["mfu"])
        hbm = cost.get("hbm") or {}
        if hbm.get("headroom_pct") is not None:
            out["hbm_headroom_pct"] = float(hbm["headroom_pct"])
    parsed = doc.get("parsed")
    if parsed and "value" in parsed:
        metric = str(parsed.get("metric", ""))
        if "images_per_sec" in metric or "img_per_sec" in metric:
            out["img_per_sec"] = float(parsed["value"])
    return out


def compare(current: dict, baseline: dict, tol_pct: float,
            tol_overrides: dict[str, float]) -> dict:
    """Direction-aware regression check over the metrics both sides
    have. Returns {"ok", "checked", "rows": [...]}; a row FAILs when the
    current value is worse than baseline by more than its tolerance."""
    cur = comparable_metrics(current)
    base = comparable_metrics(baseline)
    rows = []
    for name in sorted(set(cur) & set(base)):
        b, c = base[name], cur[name]
        tol = tol_overrides.get(name, tol_pct)
        delta_pct = (c - b) / abs(b) * 100.0 if b else (100.0 if c else 0.0)
        if name in HIGHER_BETTER:
            ok = c >= b * (1.0 - tol / 100.0)
        else:
            ok = c <= b * (1.0 + tol / 100.0)
        rows.append({
            "metric": name, "baseline": b, "current": c,
            "delta_pct": round(delta_pct, 2), "tol_pct": tol, "ok": ok,
            "direction": "higher" if name in HIGHER_BETTER else "lower",
        })
    return {
        "ok": all(r["ok"] for r in rows),
        "checked": len(rows),
        "rows": rows,
    }


# ---------------------------------------------------------------- output
def _print_report(rep: dict) -> None:
    print(f"run {rep['run_dir']}  phase={rep['phase']}  "
          f"ranks={rep['n_ranks']}")
    s = rep["step"]
    print(f"{'step time':<24}{'count':>8}{'mean':>10}{'p50':>10}"
          f"{'p90':>10}{'p99':>10}{'max':>10}   (ms)")
    print(f"{'  all ranks':<24}{s['count']:>8}{s['mean_ms']:>10.3f}"
          f"{s['p50_ms']:>10.3f}{s['p90_ms']:>10.3f}{s['p99_ms']:>10.3f}"
          f"{s['max_ms']:>10.3f}")
    for rank, rs in sorted(rep["per_rank_step"].items(), key=lambda kv: int(kv[0])):
        print(f"{'  rank ' + rank:<24}{rs['count']:>8}{rs['mean_ms']:>10.3f}"
              f"{rs['p50_ms']:>10.3f}{rs['p90_ms']:>10.3f}"
              f"{rs['p99_ms']:>10.3f}{rs['max_ms']:>10.3f}")
    print(f"straggler_skew (p50 max/min): {rep['straggler_skew']}")
    dwf = rep["data_wait_frac"]
    ips = rep["img_per_sec"]
    print(f"data_wait_frac: {'n/a' if dwf is None else dwf}"
          + (f"   img_per_sec: {ips}" if ips else ""))
    cost = rep.get("cost")
    if cost:
        flops = cost.get("flops_per_step")
        mfu = cost.get("mfu")
        src = cost.get("source") or "n/a"
        print(
            "cost model"
            + (f" [{src}]" if src else "")
            + (f": {flops / 1e9:.2f} GFLOP/step" if flops else ": flops n/a")
            + (f"  mfu {mfu:.4f}" if mfu is not None else "  mfu n/a")
            + (f"  peak {cost['peak_flops'] / 1e12:.1f} TFLOP/s"
               f" ({cost.get('device_kind')})"
               if cost.get("peak_flops") else "")
        )
        roof = cost.get("roofline")
        if roof and roof.get("arithmetic_intensity") is not None:
            nominal = " (nominal peaks)" if roof.get("nominal_peaks") else ""
            ridge = roof.get("ridge_intensity")
            print(
                f"roofline: intensity {roof['arithmetic_intensity']:.1f} "
                f"flop/byte vs ridge "
                + (f"{ridge:.1f}" if ridge is not None else "n/a")
                + f" -> {roof.get('bound') or 'n/a'}-bound{nominal}"
            )
        hbm = cost.get("hbm")
        if hbm:
            hr = hbm.get("headroom_pct")
            print(
                "hbm ledger: headroom "
                + (f"{hr:.1f}%" if hr is not None else "n/a")
                + f" (tightest of {len(hbm['per_executable'])} "
                f"executable(s), capacity per {hbm.get('capacity_source')})"
            )
            for label, row in hbm["per_executable"].items():
                tb, cap = row["total_bytes"], row["capacity_bytes"]
                print(
                    f"  {label:<18} {tb / 2**20:10.1f} MiB"
                    + (f" / {cap / 2**30:.1f} GiB"
                       f"  ({row['headroom_pct']:.1f}% free)"
                       if cap and row["headroom_pct"] is not None else "")
                )
    ev = rep["events"]
    print(f"resilience events: stall={ev['stall']} "
          f"data_error={ev['data_error']} nonfinite={ev['nonfinite']}")
    rc = rep["recompiles"]
    print(f"recompiles: {rc['count']} ({rc['wall_s']}s)")
    cache = rep.get("compile_cache")
    if cache:
        print(f"compile cache: {cache['hits']} hits, "
              f"{cache['misses']} misses"
              + ("  (warm restart: previously-compiled programs "
                 "deserialized, not recompiled)"
                 if cache["hits"] and not rc["count"] else ""))
    ck = rep["checkpoint"]
    print(f"checkpoints: {ck['saves']} saves "
          f"(mean {ck['save_mean_s']}s, max {ck['save_max_s']}s), "
          f"{ck['restores']} restores (mean {ck['restore_mean_s']}s)")
    if ck["commits"] or ck["snapshots"]:
        blocked = ck["on_path_s"]
        off = ck["off_path_s"]
        print(f"  async commit split: trainer blocked {blocked}s "
              f"({ck['snapshots']} snapshots, mean "
              f"{ck['snapshot_mean_s']}s) vs {off}s committed in the "
              f"background ({ck['commits']} commits, mean "
              f"{ck['commit_mean_s']}s)")
    barrier = ck.get("barrier")
    if barrier:
        print(f"  cross-host commit barrier ({barrier['hosts']} host(s)):")
        for host, row in barrier["per_host"].items():
            print(f"    host {host}: {row['saves']} save(s), barrier "
                  f"wait mean {row['mean_wait_s']}s max {row['max_wait_s']}s")
    shards = ck.get("shards")
    if shards:
        print(f"  sharded saves ({shards['hosts']} host(s), each writing "
              f"its own shards):")
        for host, row in shards["per_host"].items():
            print(f"    host {host}: {row['saves']} save(s), "
                  f"{row['shards']} shard(s) ({row['bytes']} B), write "
                  f"mean {row['mean_write_s']}s max {row['max_write_s']}s")
    lm = rep.get("lm")
    if lm:
        tps = lm["tokens_per_s"]
        print(
            f"lm generation: {lm['new_tokens']} new tokens over "
            f"{lm['decode_steps']} decode steps"
            + (f" ({tps} tokens/s)" if tps is not None else "")
            + f", {lm['admits']} admit(s) / {lm['retires']} retire(s) "
            + str(lm["retire_reasons"])
        )
        for name in ("prefill", "decode"):
            row = lm[name]
            if row["count"]:
                print(f"  {name:<8} {row['count']:>6} calls  "
                      f"mean {row['mean_ms']:.3f}  p50 {row['p50_ms']:.3f}  "
                      f"p99 {row['p99_ms']:.3f}  max {row['max_ms']:.3f}  (ms)")
        ck = lm.get("chunk_prefill")
        if ck:
            print(f"  chunked prefill: {ck['prompts']} prompt(s) in "
                  f"{ck['chunk_calls']} chunk call(s)  "
                  f"mean {ck['mean_ms']:.3f}  p50 {ck['p50_ms']:.3f}  "
                  f"p99 {ck['p99_ms']:.3f}  (ms)")
        if lm.get("admit_length_classes"):
            mix = ", ".join(f"{k}={v}" for k, v in
                            sorted(lm["admit_length_classes"].items()))
            print(f"  admit length classes: {mix}")
    kern = rep.get("kernels")
    if kern:
        chosen = ", ".join(
            f"{op}={row['impl']}"
            + (f" (requested {row['requested']})"
               if row["requested"] not in (row["impl"], "auto") else "")
            for op, row in sorted(kern["selected"].items())
        )
        print(f"kernel tier: {chosen or 'no selections'}"
              + (f", {len(kern['fallbacks'])} fallback(s)"
                 if kern["fallbacks"] else ""))
        for op, row in sorted(kern["selected"].items()):
            detail = {k: v for k, v in row.items()
                      if k not in ("impl", "requested")}
            if detail:  # what a knobless op says it chose
                print(f"  {op}: " + ", ".join(
                    f"{k}={v}" for k, v in sorted(detail.items())))
        for fb in kern["fallbacks"]:
            print(f"  fallback {fb['op']}: {fb['reason']}")
        plan = kern.get("share_plan")
        if plan:
            print(f"  share of a layer: rank {plan['share_rank']} of "
                  f"{plan['share_chips']} chips holds {plan['experts_held']} of "
                  f"{plan['experts_total']} experts and {plan['vocab_held']} "
                  f"of {plan['vocab_total']} vocabulary rows; "
                  + _recomputed(plan))
        plan = kern.get("loop_plan")
        if plan:
            print(f"  looped stack: {plan['passes']} passes over "
                  f"{plan['layers']} layers, {plan['block_applications']} "
                  "block applications; " + _recomputed(plan))
        if kern.get("ab"):
            for label, row in sorted(kern["ab"].items()):
                ba = row.get("bytes_accessed")
                print(f"  {label:<28}"
                      + (f" {ba / 1e6:9.2f} MB accessed" if ba else "")
                      + (f"  {row['flops'] / 1e6:.2f} MFLOP"
                         if row.get("flops") else ""))
    seq = rep.get("sequencer")
    if seq:
        streams = ", ".join(
            f"{k}={v}" for k, v in sorted(seq["streams"].items())
        )
        print(f"dispatch sequencer: {seq['tokens']} tokens ({streams}), "
              f"max token-wait {seq['max_wait_s']}s (total "
              f"{seq['total_wait_s']}s), {seq['fence_waits']} fence "
              f"wait(s) ({seq['fence_wait_s']}s)"
              + (f", {seq['wedges']} WEDGE flag(s)" if seq["wedges"]
                 else ""))
        ring = seq.get("ring")
        if ring:
            print(f"  cross-host dispatch ring ({ring['hosts']} host(s)):")
            for host, row in ring["per_host"].items():
                flags = "".join(
                    f" {f.upper()}" for f in ("wedged", "detached")
                    if row.get(f)
                )
                print(f"    host {host} [{row['role']}]: {row['slots']} "
                      f"slot(s), ring wait total {row['total_wait_s']}s "
                      f"max {row['max_wait_s']}s, "
                      f"{row['deadline_misses']} deadline miss(es)"
                      + flags)
    tr = rep.get("trace")
    if tr:
        print(f"request tracing: {tr['requests']} traced request(s), "
              f"{tr['connected']} with connected span trees"
              + (f", exemplars: {', '.join(tr['exemplar_trace_ids'])}"
                 if tr.get("exemplar_trace_ids") else ""))
        for lc, row in (tr.get("by_length_class") or {}).items():
            sh = row["shares"]
            mix = "  ".join(
                f"{k} p50 {sh[k]['p50'] * 100:.0f}%/p99 "
                f"{sh[k]['p99'] * 100:.0f}%"
                for k in ("queue", "prefill", "decode", "speculation")
            )
            print(f"  class {lc:<8} n={row['requests']:<4} total p50 "
                  f"{row['total_ms_p50']}ms p99 {row['total_ms_p99']}ms  "
                  f"{mix}")
    camp = rep.get("campaign")
    if camp:
        verdict = {True: "PASS", False: "FAIL", None: "n/a"}[camp["ok"]]
        print(f"traffic campaigns: {camp['campaigns']} verdict(s), "
              f"gate {verdict}")
        for v in camp["verdicts"]:
            print(f"  {v['campaign']:<24} phases={v['phases']} "
                  f"alerts_exact={v['alerts_exact']} "
                  f"control_clean={v['control_clean']} "
                  f"{'ok' if v['ok'] else 'FAIL'}")
        for p in camp["phases"]:
            if not p["ok"]:
                print(f"  PHASE FAIL {p['campaign']}/{p['phase']}: "
                      f"expected {p['expected_alerts']} "
                      f"raised {p['raised_alerts']}")
        if camp.get("model_route"):
            for name, row in sorted(camp["model_route"].items()):
                print(f"  model {name:<12} requests={row['requests']} "
                      f"rejected={row['rejected']} "
                      f"spill_out={row['degraded_out']} "
                      f"spill_in={row['degraded_in']} "
                      f"p99={row['p99_ms']}ms")
        if camp.get("length_classes"):
            for name, row in sorted(camp["length_classes"].items()):
                print(f"  length {name:<11} (>= {row['threshold']} tokens "
                      f"is long): requests={row['requests']} "
                      f"rejected={row['rejected']} p99={row['p99_ms']}ms")
        for q in camp.get("quantized") or []:
            ratio = (q["bytes_after"] / q["bytes_before"]
                     if q.get("bytes_before") else None)
            print(f"  quantized {q['arch']} [{q['mode']}]"
                  + (f": weights x{ratio:.2f}" if ratio else ""))


def _print_compare(cmp: dict, baseline_path: str) -> None:
    print(f"\nregression gate vs {baseline_path}:")
    print(f"{'metric':<18}{'baseline':>12}{'current':>12}{'delta%':>9}"
          f"{'tol%':>7}{'dir':>8}  verdict")
    for r in cmp["rows"]:
        verdict = "PASS" if r["ok"] else "FAIL"
        print(f"{r['metric']:<18}{r['baseline']:>12.3f}{r['current']:>12.3f}"
              f"{r['delta_pct']:>9.2f}{r['tol_pct']:>7.1f}"
              f"{r['direction']:>8}  {verdict}")
    if not cmp["rows"]:
        print("  (no overlapping metrics — nothing gated)")
    print("gate:", "PASS" if cmp["ok"] else "FAIL")


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    ap.add_argument("run_dir", nargs="?", default=None,
                    help="finished run OUT_DIR (telemetry/ + metrics.jsonl)")
    ap.add_argument("--trace", nargs="?", const="__default__", default=None,
                    metavar="RUN_DIR",
                    help="also export the merged Perfetto trace "
                         "(trace.json in the run dir); the run dir may be "
                         "given here instead of positionally")
    ap.add_argument("--phase", default="train", choices=["train", "eval"])
    ap.add_argument("--json-out", default=None,
                    help="report destination (default {run}/RUN_REPORT.json)")
    ap.add_argument("--compare", default=None, metavar="BASELINE.json",
                    help="regression-gate against a RUN_REPORT.json or "
                         "BENCH_*.json; exit 1 on any FAIL")
    ap.add_argument("--tol-pct", type=float, default=10.0,
                    help="global regression tolerance percent (default 10)")
    ap.add_argument("--tol", action="append", default=[], metavar="METRIC=PCT",
                    help="per-metric tolerance override (repeatable), e.g. "
                         "--tol img_per_sec=5")
    args = ap.parse_args(argv)

    run_dir = args.run_dir
    if run_dir is None and args.trace not in (None, "__default__"):
        run_dir = args.trace  # `run_report.py --trace out/` one-command form
    if run_dir is None or not os.path.isdir(run_dir):
        ap.error(f"need a run directory (got {run_dir!r})")

    tol_overrides = {}
    for item in args.tol:
        name, _, pct = item.partition("=")
        if not pct:
            ap.error(f"--tol wants METRIC=PCT, got {item!r}")
        tol_overrides[name] = float(pct)

    try:
        report = build_report(run_dir, phase=args.phase)
    except FileNotFoundError as e:
        raise SystemExit(str(e))

    if args.trace is not None:
        trace_path = export.export_trace(run_dir)
        n_tracks = len(report["per_rank_step"]) or report["n_ranks"]
        print(f"merged Perfetto trace -> {trace_path} "
              f"({n_tracks or 1} rank track(s); open at ui.perfetto.dev)")

    exit_code = 0
    if args.compare:
        with open(args.compare) as f:
            baseline = json.load(f)
        cmp = compare(report, baseline, args.tol_pct, tol_overrides)
        report["compare"] = {"baseline": os.path.abspath(args.compare), **cmp}
        if not cmp["ok"]:
            exit_code = 1

    out_path = args.json_out or os.path.join(run_dir, "RUN_REPORT.json")
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    _print_report(report)
    if args.compare:
        _print_compare(report["compare"], args.compare)
    print(f"report -> {out_path}")
    return exit_code


if __name__ == "__main__":
    sys.exit(main())
