"""Real-JPEG training throughput through the actual CLI (VERDICT r2 #1).

Drives ``python train_net.py`` over a synthetic ImageFolder JPEG tree
(tools/make_imagefolder.py — real files, varied sizes, learnable classes)
on whatever device is attached (the real TPU chip under the driver), then
reports achieved steady-state img/s and the decode↔step overlap from the
run's own metrics.jsonl (batch_time vs data_time per print window).

Context for reading the numbers on THIS dev box (see PERF.md "Input
pipeline"): the box has ONE CPU core, so host decode (~100-130 img/s/core)
— not the chip (~2600 img/s for ResNet-50) — is the binding constraint;
a real v5e host has >100 vCPUs for 4-8 chips. The interesting outputs are
(a) the end-to-end path works and trains from JPEGs on the chip, and
(b) overlap efficiency: achieved rate ÷ the pipeline's own decode rate.

    python tools/realdata_bench.py [--backend native|pil] [--arch resnet50]
        [--batch 64] [--epochs 2] [--classes 10] [--per-class 100]
        [--im-size 224] [--out /tmp/realdata_bench]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import _path  # noqa: F401  (repo root onto sys.path)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cli(tree, out, args, backend):
    cmd = [
        sys.executable, os.path.join(REPO, "train_net.py"),
        "--cfg", os.path.join(REPO, "config", f"{args.arch}.yaml"),
        "MODEL.NUM_CLASSES", str(args.classes),
        "MODEL.SYNCBN", "True",
        "TRAIN.DATASET", tree, "TEST.DATASET", tree,
        "TRAIN.BATCH_SIZE", str(args.batch),
        "TEST.BATCH_SIZE", str(args.batch),
        "TRAIN.IM_SIZE", str(args.im_size),
        # val: shorter-side resize keeps the train/test 224/256 ratio
        "TEST.IM_SIZE", str(int(args.im_size * 8 / 7)),
        "TRAIN.WORKERS", str(args.workers),
        "TRAIN.PREFETCH_DEVICE", str(args.prefetch_device),
        "TRAIN.PRINT_FREQ", "4",
        "OPTIM.MAX_EPOCH", str(args.epochs),
        "OPTIM.BASE_LR", str(args.lr),
        # linear warmup stabilizes the early high-LR epochs (VERDICT r4
        # #6: the r4 curve collapsed 25 points mid-run with no warmup)
        "OPTIM.WARMUP_EPOCHS", str(args.warmup_epochs),
        "DATA.BACKEND", backend,
        "DATA.DEVICE_NORMALIZE", str(bool(args.device_normalize)),
        "RNG_SEED", "1",
        "OUT_DIR", out,
    ]
    if args.profile_steps > 0:
        # jax.profiler window over a real-data span: steps [2, 2+N) of the
        # first epoch land in {out}/profile (TensorBoard/XProf format) —
        # the trace-level companion to the timeline attribution
        cmd += [
            "PROF.ENABLED", "True", "PROF.START_STEP", "2",
            "PROF.NUM_STEPS", str(args.profile_steps),
        ]
    env = dict(os.environ)
    if args.bn_momentum > 0:
        env["DISTRIBUUUU_BN_MOMENTUM"] = str(args.bn_momentum)
    else:
        # an ambient knob from a previous experiment must not silently
        # contradict the bn_momentum the result JSON records
        env.pop("DISTRIBUUUU_BN_MOMENTUM", None)
    t0 = time.perf_counter()
    proc = subprocess.run(
        cmd, capture_output=True, text=True, timeout=3600, cwd=REPO,
        env=env,
    )
    wall = time.perf_counter() - t0
    if proc.returncode != 0:
        raise SystemExit(
            f"train_net.py failed ({proc.returncode}):\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
        )
    return wall


def analyze(out, args, n_devices):
    from tools.overlap_report import attribute, load_timeline

    metrics_path = os.path.join(out, "metrics.jsonl")
    with open(metrics_path) as f:
        recs = [json.loads(line) for line in f]
    # steady state: the final epoch's train windows (epoch 1 pays compile)
    last_ep = max(r["epoch"] for r in recs if r["kind"] == "train")
    wins = [
        r for r in recs if r["kind"] == "train" and r["epoch"] == last_ep
    ]
    # batch_time/data_time are the meter's running within-epoch averages;
    # the LAST window's avg covers the whole epoch steady state
    bt = wins[-1]["batch_time"]
    dt = wins[-1]["data_time"]
    evals = [r for r in recs if r["kind"] == "eval"]
    train_loss = {
        r["epoch"]: r["loss"]
        for r in recs
        if r["kind"] == "train" and "loss" in r
    }
    # exact per-stage attribution of the steady-state epoch from the
    # per-batch timeline records (tools/overlap_report.py) — the measured
    # replacement for the meter-ratio data_wait_frac
    attribution = attribute(
        load_timeline(metrics_path), phase="train", epoch=last_ep
    )
    per_host = args.batch * n_devices
    return {
        "img_per_sec": per_host / bt,
        "batch_time": bt,
        "data_wait_frac_meter": dt / bt,
        "attribution": attribution,
        "final_top1": evals[-1]["top1"] if evals else None,
        # full per-epoch convergence series (the regression reference)
        "curve_top1": [r["top1"] for r in evals],
        "curve_train_loss": [
            train_loss[e] for e in sorted(train_loss)
        ],
        "epochs": last_ep,
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", default="native", choices=["native", "pil"])
    ap.add_argument("--device-normalize", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="DATA.DEVICE_NORMALIZE: ship uint8, normalize "
                         "in-graph (4× fewer H2D bytes). Defaults to True — "
                         "the framework default since r4 — so a plain bench "
                         "run measures the default pipeline; "
                         "--no-device-normalize for the host-float path")
    ap.add_argument("--arch", default="resnet50")
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--per-class", type=int, default=100)
    ap.add_argument("--im-size", type=int, default=224)
    # conservative default for a ~30-step from-scratch run with no warmup
    # (the linear-scaled 0.05 for batch 64 diverges in the first steps)
    ap.add_argument("--lr", type=float, default=0.0125)
    ap.add_argument("--warmup-epochs", type=int, default=-1,
                    help="OPTIM.WARMUP_EPOCHS for the recipe. Default -1 "
                         "= min(2, epochs//2), so short smoke runs are "
                         "not spent entirely inside the warmup ramp")
    ap.add_argument("--bn-momentum", type=float, default=0.0,
                    help="if >0, DISTRIBUUUU_BN_MOMENTUM for the run — "
                         "faster-tracking running stats for eval stability "
                         "at high LR (0 = torch-parity 0.9)")
    ap.add_argument("--min-size", type=int, default=256,
                    help="source JPEG shorter bound")
    ap.add_argument("--max-size", type=int, default=320)
    ap.add_argument("--noise", type=float, default=0.06,
                    help="per-pixel render noise (hard tree: 0.12)")
    ap.add_argument("--label-noise", type=float, default=0.0,
                    help="fraction of TRAIN samples rendered from a wrong "
                         "class (VERDICT r3 #5 hardness)")
    ap.add_argument("--hue-jitter", type=float, default=0.0,
                    help="per-sample hue/angle jitter in hue-wheel units; "
                         "~1/classes makes adjacent classes overlap "
                         "irreducibly (VERDICT r3 #5 hardness)")
    ap.add_argument("--workers", type=int, default=os.cpu_count() or 4)
    ap.add_argument("--prefetch-device", type=int, default=2,
                    help="TRAIN.PREFETCH_DEVICE: device-side prefetch ring "
                         "depth (0 = unoverlapped put-then-step)")
    ap.add_argument("--profile-steps", type=int, default=0,
                    help="if >0, capture a jax.profiler trace over this "
                         "many real-data train steps (PROF.*) into "
                         "{out}/profile")
    ap.add_argument("--json-out", default="",
                    help="also write the result JSON to this path "
                         "(e.g. REALDATA_r06.json)")
    ap.add_argument("--out", default="/tmp/realdata_bench")
    ap.add_argument("--tree", default="/tmp/distribuuuu_synth_rd")
    args = ap.parse_args()
    if args.warmup_epochs < 0:
        args.warmup_epochs = min(2, args.epochs // 2)

    from tools.make_imagefolder import make_tree

    make_tree(
        args.tree, n_classes=args.classes, train_per_class=args.per_class,
        val_per_class=max(4, args.per_class // 10),
        min_size=args.min_size, max_size=args.max_size,
        noise=args.noise, label_noise=args.label_noise,
        hue_jitter=args.hue_jitter,
    )

    import shutil

    out = args.out
    shutil.rmtree(out, ignore_errors=True)
    wall = run_cli(args.tree, out, args, args.backend)

    import jax

    n_dev = jax.local_device_count()
    stats = analyze(out, args, n_dev)

    # the pipeline's own decode ceiling, measured on the same tree/settings
    # (loader only, no device) — the overlap denominator
    from distribuuuu_tpu.data.imagefolder import ImageFolderDataset
    from distribuuuu_tpu.data.loader import Loader

    dataset = ImageFolderDataset(
        args.tree, "train", im_size=args.im_size, train=True,
        base_seed=0, backend=args.backend,
        raw_u8=bool(args.device_normalize),
    )
    loader = Loader(
        dataset, batch_size=args.batch * n_dev, shuffle=True,
        drop_last=True, workers=args.workers, seed=0,
    )
    loader.set_epoch(0)
    for _ in loader:  # warm (thread pool, native build, page cache)
        pass
    n, t0 = 0, time.perf_counter()
    loader.set_epoch(1)
    for batch in loader:
        n += batch["image"].shape[0]
    decode_rate = n / (time.perf_counter() - t0)

    att = stats["attribution"]
    result = {
        "metric": f"realdata_{args.arch}_train_images_per_sec",
        "value": round(stats["img_per_sec"], 1),
        "unit": "images/sec",
        "backend": args.backend,
        "decode_only_images_per_sec": round(decode_rate, 1),
        # headline overlap numbers from MEASURED intervals (the per-batch
        # timeline, tools/overlap_report.py): overlap_efficiency is the
        # wall fraction covered by decode activity ≡ achieved rate over
        # the in-run decode ceiling; *_vs_decode_only keeps the historical
        # external-denominator ratio (loader-only pass below) comparable
        # with REALDATA_r04-r05
        "overlap_efficiency": att["overlap_efficiency"],
        "overlap_efficiency_vs_decode_only": round(
            stats["img_per_sec"] / decode_rate, 3
        ),
        "data_wait_frac": att["data_wait_frac"],
        "data_wait_frac_meter": round(stats["data_wait_frac_meter"], 3),
        "attribution": att,
        "prefetch_device": args.prefetch_device,
        "final_top1": stats["final_top1"],
        "curve_top1": stats["curve_top1"],
        "curve_train_loss": [
            round(x, 4) for x in stats["curve_train_loss"]
        ],
        "wall_seconds": round(wall, 1),
        "workers": args.workers,
        "device_normalize": bool(args.device_normalize),
        "classes": args.classes, "per_class": args.per_class,
        "label_noise": args.label_noise, "noise": args.noise,
        "hue_jitter": args.hue_jitter,
        "arch": args.arch, "im_size": args.im_size,
        "epochs": args.epochs, "lr": args.lr,
        "warmup_epochs": args.warmup_epochs,
        "bn_momentum": args.bn_momentum or 0.9,
    }
    line = json.dumps(result)
    print(line)
    if args.json_out:
        with open(args.json_out, "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
