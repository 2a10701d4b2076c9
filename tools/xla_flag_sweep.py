"""Adversarial XLA compiler-flag sweep on the flagship train step
(VERDICT r4 #1: "XLA latency-hiding/scheduler flag sweep" before the
roofline proof stands).

Methodology: for each candidate option set, the FULL bench workload
(jitted ResNet-50 fold-4 train step, batch 128) is rebuilt with the
options applied through ``jax.jit(compiler_options=...)``, then timed
in interleaved rounds against the same-process baseline so drift cancels
(the ab_bench methodology). Candidates the compiler rejects are reported
as "rejected", not silently skipped.

    python tools/xla_flag_sweep.py [--rounds 3] [--iters 8]

Prints one line per candidate and a JSON summary.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import statistics

import _path  # noqa: F401


# Each candidate: (label, "k=v;k=v"). Latency-hiding / scheduler /
# fusion-cost knobs that plausibly shift a bandwidth-bound conv step.
CANDIDATES = [
    ("lhs-on", "xla_tpu_enable_latency_hiding_scheduler=true"),
    ("lhs-rerun3", "xla_latency_hiding_scheduler_rerun=3"),
    ("no-rwb-fusion", "xla_tpu_rwb_fusion=false"),
    ("multi-level-loop-fusion", "xla_tpu_enable_multi_level_nested_loop_fusion=true"),
    ("no-multi-level-loop-fusion", "xla_tpu_enable_multi_level_nested_loop_fusion=false"),
    ("bundle-cost-model", "xla_tpu_use_bundle_aware_cost_model_for_fusions=true"),
    ("experimental-fusion-cost", "xla_tpu_enable_experimental_fusion_cost_model=true"),
    ("vmem-128M", "xla_tpu_scoped_vmem_limit_kib=131072"),
    ("prefetch-repeat", "xla_tpu_use_repeated_instance_for_preferred_prefetch_time=true"),
    ("async-sort", "xla_tpu_enable_async_collective_fusion=true"),
]


@contextlib.contextmanager
def _env(overrides: dict[str, str]):
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--iters", type=int, default=8)
    ap.add_argument("--fold", type=int, default=4)
    ap.add_argument("--only", default="", help="comma-separated label subset")
    args = ap.parse_args()

    import bench

    print("building baseline ...", flush=True)
    base_window, meta = bench.build_workload(fold=args.fold)
    imgs = meta["batch"] * meta["fold"] * args.iters

    results = {}
    cands = CANDIDATES
    if args.only:
        keep = set(args.only.split(","))
        cands = [c for c in CANDIDATES if c[0] in keep]
    for label, opts in cands:
        print(f"building {label} ({opts}) ...", flush=True)
        try:
            with _env({"DISTRIBUUUU_XLA_OPTS": opts}):
                cand_window, _ = bench.build_workload(fold=args.fold)
        except Exception as e:  # noqa: BLE001 — remote compiler rejection
            results[label] = {"opts": opts, "rejected": str(e)[:200]}
            print(f"  {label}: REJECTED {str(e)[:120]}", flush=True)
            continue
        ratios, base_rates, cand_rates = [], [], []
        for r in range(args.rounds):
            pair = (
                (base_window, cand_window) if r % 2 == 0
                else (cand_window, base_window)
            )
            t1 = pair[0](args.iters)
            t2 = pair[1](args.iters)
            tb, tc = (t1, t2) if r % 2 == 0 else (t2, t1)
            base_rates.append(imgs / tb / meta["n_chips"])
            cand_rates.append(imgs / tc / meta["n_chips"])
            ratios.append(tb / tc)  # >1 ⇒ candidate faster
        med = statistics.median(ratios)
        results[label] = {
            "opts": opts,
            "base_median_img_s": round(statistics.median(base_rates), 1),
            "cand_median_img_s": round(statistics.median(cand_rates), 1),
            "paired_speedup_median": round(med, 4),
            "paired_speedup_range": [
                round(min(ratios), 4), round(max(ratios), 4)
            ],
        }
        print(
            f"  {label}: {results[label]['cand_median_img_s']} vs base "
            f"{results[label]['base_median_img_s']} img/s — paired "
            f"speedup {med:.4f} [{min(ratios):.4f}, {max(ratios):.4f}]",
            flush=True,
        )
    print(json.dumps({
        "metric": "xla_flag_sweep_resnet50",
        "device_kind": meta["device_kind"],
        "results": results,
    }))


if __name__ == "__main__":
    main()
