"""Exact wall-time attribution of an input pipeline from timeline records.

Ingests the per-batch ``kind="timeline"`` records a run leaves in
``{OUT_DIR}/metrics.jsonl`` (utils/jsonlog.timeline_log — stage-boundary
``time.perf_counter`` stamps written by the trainer's per-step dispatch
path and by validate) and decomposes the epoch wall time into measured
intervals instead of the old coarse meter ratios:

  * consumer-side (disjoint by construction — one sequential consumer
    thread): ``data_wait`` (blocked on the host batch), ``h2d`` (sharded
    device_put dispatch), ``step`` (compiled step dispatch), and the
    residual ``other`` (un-instrumented consumer time: PRINT_FREQ metric
    flush/device sync, python overhead, idle). These four SUM TO THE WALL
    EXACTLY — the attribution is a partition, not an estimate.
  * worker-side (overlapping the consumer and each other): ``decode``
    (decode+augment busy seconds summed over batches), ``assemble``
    (stack/pad), and ``decode_busy`` — the union length of the per-batch
    decode intervals, i.e. the wall fraction during which at least one
    worker was decoding. For an input-bound run the decode union IS the
    pipeline's critical path, so

        overlap_efficiency = decode_busy / wall
                           = (images/wall) / (images/decode_busy)
                           = achieved rate / in-run decode ceiling

    — the same ratio REALDATA reports historically, now from measured
    intervals. It is meaningful when the run is input-bound
    (``data_wait_frac`` large); a step-bound run legitimately scores low.

    python tools/overlap_report.py --metrics OUT/metrics.jsonl \
        [--phase train] [--epoch N]

Prints a per-stage table plus one machine-readable JSON line; importable
(``load_timeline`` / ``attribute``) — tools/realdata_bench.py embeds the
same attribution into its REALDATA artifact.
"""

from __future__ import annotations

import argparse
import json

import _path  # noqa: F401  (repo root onto sys.path)


def load_timeline(path: str) -> list[dict]:
    """All kind="timeline" records of a metrics.jsonl file."""
    recs = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            r = json.loads(line)
            if r.get("kind") == "timeline":
                recs.append(r)
    return recs


def _union_len(intervals: list[tuple[float, float]]) -> float:
    """Total length of the union of [a, b] intervals."""
    total, cur_a, cur_b = 0.0, None, None
    for a, b in sorted(intervals):
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def attribute(recs: list[dict], phase: str = "train",
              epoch: int | None = None) -> dict:
    """Attribution over one phase (and optionally one epoch) of timeline
    records. ``epoch=None`` selects the LAST epoch present — the steady
    state (earlier epochs pay compile). Raises ValueError when no records
    match."""
    recs = [r for r in recs if r.get("phase") == phase]
    if epoch is None and recs:
        epoch = max(r["epoch"] for r in recs)
    recs = [r for r in recs if r.get("epoch") == epoch]
    if not recs:
        raise ValueError(
            f"no timeline records for phase={phase!r} epoch={epoch!r} — "
            "TRAIN.TIMELINE off?"
        )
    recs = sorted(recs, key=lambda r: r["batch"])
    wall = max(r["step1"] for r in recs) - min(r["get0"] for r in recs)
    wall = max(wall, 1e-9)
    data_wait = sum(r["get1"] - r["get0"] for r in recs)
    h2d = sum(r["put1"] - r["put0"] for r in recs)
    step = sum(r["step1"] - r["step0"] for r in recs)
    other = wall - data_wait - h2d - step  # exact residual, ≥ 0 up to clock
    has_dec = all("dec0" in r and "asm1" in r for r in recs)
    decode = sum(r["dec1"] - r["dec0"] for r in recs) if has_dec else 0.0
    assemble = sum(r["asm1"] - r["dec1"] for r in recs) if has_dec else 0.0
    decode_busy = (
        _union_len([(r["dec0"], r["asm1"]) for r in recs]) if has_dec else 0.0
    )
    images = sum(r.get("n", 0) for r in recs)
    out = {
        "phase": phase,
        "epoch": epoch,
        "n_batches": len(recs),
        "images": images,
        "wall_s": round(wall, 4),
        "img_per_sec": round(images / wall, 2),
        # the exact partition (sums to wall_s by construction)
        "data_wait_s": round(data_wait, 4),
        "h2d_s": round(h2d, 4),
        "step_s": round(step, 4),
        "other_s": round(other, 4),
        # worker-side, overlapped
        "decode_s": round(decode, 4),
        "assemble_s": round(assemble, 4),
        "decode_busy_s": round(decode_busy, 4),
        # headline ratios, from measured intervals
        "data_wait_frac": round(data_wait / wall, 4),
        "overlap_efficiency": round(min(1.0, decode_busy / wall), 4),
        # partition self-check: |sum(components) - wall| / wall — exactly 0
        # up to the rounding above (the acceptance gate is ≤ 0.05)
        "attribution_residual_frac": round(
            abs(data_wait + h2d + step + other - wall) / wall, 6
        ),
    }
    return out


def _print_table(att: dict) -> None:
    wall = att["wall_s"]
    print(f"phase={att['phase']} epoch={att['epoch']}: "
          f"{att['n_batches']} batches, {att['images']} images, "
          f"wall {wall:.3f}s  ({att['img_per_sec']} img/s)")
    print(f"{'consumer stage':<22}{'seconds':>10}{'frac':>8}")
    for key, label in (
        ("data_wait_s", "wait on host batch"),
        ("h2d_s", "H2D dispatch"),
        ("step_s", "step dispatch"),
        ("other_s", "other (sync/python)"),
    ):
        print(f"{label:<22}{att[key]:>10.3f}{att[key] / wall:>8.3f}")
    print(f"{'(sums to wall)':<22}{att['data_wait_s'] + att['h2d_s'] + att['step_s'] + att['other_s']:>10.3f}")
    print(f"{'worker decode busy':<22}{att['decode_busy_s']:>10.3f}"
          f"{att['decode_busy_s'] / wall:>8.3f}   (union; overlaps consumer)")
    print(f"{'  decode':<22}{att['decode_s']:>10.3f}")
    print(f"{'  assemble':<22}{att['assemble_s']:>10.3f}")
    print(f"overlap_efficiency {att['overlap_efficiency']:.3f}   "
          f"data_wait_frac {att['data_wait_frac']:.3f}")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--metrics", required=True,
                    help="path to a run's metrics.jsonl")
    ap.add_argument("--phase", default="train", choices=["train", "eval"])
    ap.add_argument("--epoch", type=int, default=None,
                    help="1-based epoch (default: last = steady state)")
    args = ap.parse_args()
    recs = load_timeline(args.metrics)
    try:
        att = attribute(recs, phase=args.phase, epoch=args.epoch)
    except ValueError as e:
        raise SystemExit(str(e))
    _print_table(att)
    print(json.dumps({"metric": "overlap_report", **att}))


if __name__ == "__main__":
    main()
