"""Paired A/B throughput harness for in-graph math changes (VERDICT r3 #8).

Round 3's lesson: a 6-line BN numerics change silently cost 7.5% of
flagship throughput, and best-of-windows runs taken hours apart could not
distinguish it from run-to-run drift. RULE (PERF.md "Costing changes"): any
change that touches in-graph math ships with a paired delta measured by
this tool.

Methodology — the same two hazards tools/flash_bench.py burns:
  * both variants are built IN ONE PROCESS and timed in interleaved
    rounds (A B / B A alternating), so drift hits both equally and
    the reported number is the MEDIAN of per-round paired ratios;
  * every window is fenced on the updated params (bench.py's window).

Variants are expressed as trace-time environment variables (the repo's
debug knobs, e.g. ``DISTRIBUUUU_BN_VARIANCE``) applied while the variant's
train step is built and compiled, then restored. Both variants run the
full bench.py workload: jitted ResNet-50 train step, fold=4, batch 128.

Usage:
    python tools/ab_bench.py --b DISTRIBUUUU_BN_VARIANCE=centered
    python tools/ab_bench.py --a DISTRIBUUUU_BN_VARIANCE=uncentered \
        --b DISTRIBUUUU_BN_VARIANCE=centered --rounds 5 --iters 10

Prints per-variant img/s medians ± spread and the paired B/A ratio, plus
one machine-readable JSON line.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import statistics

import _path  # noqa: F401  (repo root onto sys.path)


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


# Named A/B presets for the standing experiments (expanded into --a/--b
# env pairs before parsing): each is a knob bench.build_workload reads at
# trace time.
PRESETS = {
    # remat-for-traffic (VERDICT r5 #3): TRAIN.REMAT on ResNet stages 1-2
    # vs HEAD — the one untried roofline lever on the 93%-HBM-bus step.
    "remat": {"b": ["DISTRIBUUUU_REMAT=1"]},
}


def _parse_kv(pairs: list[str]) -> dict[str, str]:
    out = {}
    for p in pairs:
        if "=" not in p:
            raise SystemExit(f"expected KEY=VALUE, got {p!r}")
        k, v = p.split("=", 1)
        out[k] = v
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--a", action="append", default=[], metavar="K=V",
                    help="env for variant A (default: inherited env = HEAD)")
    ap.add_argument("--b", action="append", default=[], metavar="K=V",
                    help="env for variant B (repeatable)")
    ap.add_argument("--preset", choices=sorted(PRESETS),
                    help="named A/B (e.g. 'remat' = HEAD vs "
                         "DISTRIBUUUU_REMAT=1); composes with --a/--b")
    ap.add_argument("--rounds", type=int, default=5,
                    help="interleaved timing rounds (paired ratios)")
    ap.add_argument("--iters", type=int, default=10,
                    help="train-step calls per window (fold steps each)")
    ap.add_argument("--fold", type=int, default=4)
    ap.add_argument("--per-chip-batch", type=int, default=128)
    args = ap.parse_args()

    if args.preset:
        args.a = PRESETS[args.preset].get("a", []) + args.a
        args.b = PRESETS[args.preset].get("b", []) + args.b
    a_env, b_env = _parse_kv(args.a), _parse_kv(args.b)
    if not b_env and not a_env:
        raise SystemExit(
            "nothing to compare: pass at least --b KEY=VALUE or --preset"
        )

    import bench  # repo-root bench.py via _path

    variants = {}
    for name, env in (("A", a_env), ("B", b_env)):
        print(f"building {name} ({env or 'HEAD env'}) ...", flush=True)
        with _env(env):
            variants[name] = bench.build_workload(
                fold=args.fold, per_chip_batch=args.per_chip_batch
            )

    _, meta = variants["A"]
    imgs_per_window = meta["batch"] * meta["fold"] * args.iters

    # interleave, alternating order each round so neither variant always
    # runs first after the other's cache effects
    times = {"A": [], "B": []}
    for r in range(args.rounds):
        order = ("A", "B") if r % 2 == 0 else ("B", "A")
        for name in order:
            window, _ = variants[name]
            times[name].append(window(args.iters))

    rate = {
        n: [imgs_per_window / t / meta["n_chips"] for t in ts]
        for n, ts in times.items()
    }
    for name, env in (("A", a_env), ("B", b_env)):
        rs = sorted(rate[name])
        print(
            f"{name} ({env or 'HEAD'}): "
            f"median {statistics.median(rs):8.2f} img/s/chip "
            f"[{rs[0]:.2f}, {rs[-1]:.2f}]"
        )
    ratios = sorted(b / a for a, b in zip(rate["A"], rate["B"]))
    med_ratio = statistics.median(ratios)
    print(
        f"paired B/A per-round ratios: median {med_ratio:.4f} "
        f"[{ratios[0]:.4f}, {ratios[-1]:.4f}]"
    )
    print(json.dumps({
        "metric": "ab_bench_resnet50_img_per_sec_per_chip",
        "a_env": a_env, "b_env": b_env,
        "a_median": round(statistics.median(rate["A"]), 2),
        "b_median": round(statistics.median(rate["B"]), 2),
        "paired_ratio_median": round(med_ratio, 4),
        "paired_ratio_range": [round(ratios[0], 4), round(ratios[-1], 4)],
        "rounds": args.rounds, "iters": args.iters,
        "device_kind": meta["device_kind"],
    }))


if __name__ == "__main__":
    main()
