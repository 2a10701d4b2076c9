"""Benchmark: ResNet-50 training throughput (images/sec/chip) on real hardware.

Runs the framework's actual jitted train step (fwd + CE + bwd + SGD-nesterov
update + in-graph metrics, bf16 compute / fp32 params) on synthetic ImageNet
shapes, steady-state, on however many chips are attached, and prints ONE JSON
line. Also times the EVAL step (``build_eval_workload`` — the forward
test_model and the serving engine run); its ``eval_images_per_sec_per_chip``
is the per-replica serving throughput ceiling.

``vs_baseline``: the reference publishes no throughput numbers
(SURVEY.md §6), so the denominator is the widely-reproduced ~400 img/s/GPU
that torch DDP ResNet-50 fp32 achieves on the reference's A100-class hardware
(README.md:183) — the setup its published baselines were trained with.
"""

from __future__ import annotations

import json
import sys
import time

BASELINE_IMG_PER_SEC_PER_CHIP = 400.0  # A100 fp32 DDP resnet50 (see docstring)

# ResNet-50 @224²: 4.09 GMACs fwd (torchvision count) × 2 FLOPs/MAC ≈ 8.2
# GFLOP; fwd+bwd ≈ 3× fwd. Convention: FLOPs = 2·MACs (the standard MFU
# convention — see PERF.md "Where the time goes" for the derivation).
# Since r10 this hand constant is the CROSS-CHECK, not the source: the
# mfu field comes from XLA's own cost_analysis of the step program
# (telemetry/costmodel.py); the bench warns and records flops_drift_pct
# when the two disagree by more than DRIFT_WARN_PCT — the signal that
# this table rotted as the model changed.
RESNET50_TRAIN_FLOPS_PER_IMG = 3 * 2 * 4.09e9
DRIFT_WARN_PCT = 5.0

# Peak dense bf16 FLOP/s by device kind: ONE table for the whole repo,
# owned by telemetry/costmodel.py (DEVICE_PEAKS — adds HBM bandwidth and
# capacity columns for the roofline/headroom ledger). PEAK_BF16 keeps
# the historical name/shape for existing callers.
from distribuuuu_tpu.telemetry import costmodel  # noqa: E402

PEAK_BF16 = {
    kind: entry["flops"]
    for kind, entry in costmodel.DEVICE_PEAKS.items()
    if kind != "cpu"  # nominal CPU peak is for off-chip roofline tests
}


def build_workload(per_chip_batch: int = 128):
    """Build the bench's compiled+warmed train step.

    Returns ``(window, meta)`` — ``window(iters)`` runs ``iters`` optimizer
    steps and returns elapsed seconds, fenced with ``block_until_ready``
    on the updated state; ``meta`` has batch geometry.
    """
    import jax
    import numpy as np

    import distribuuuu_tpu.config as config
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.asyncplane import compile_cache
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.parallel import mesh as mesh_lib, sharding as sharding_lib
    from distribuuuu_tpu.parallel.partition import lowering as partition_lowering
    from distribuuuu_tpu.utils.optim import construct_optimizer

    config.reset_cfg()
    compile_cache.setup_from_cfg(cfg)
    cfg.MODEL.ARCH = "resnet50"
    cfg.MODEL.NUM_CLASSES = 1000
    n_chips = len(jax.devices())
    batch = per_chip_batch * n_chips

    # the trainer's own path: ONE partition lowering builds the step for
    # whatever mesh the attached chips form (train_model does the same)
    mesh = mesh_lib.build_mesh()
    topo = trainer.check_trainer_mesh()
    model = trainer.build_model_from_cfg(topo)
    lowered = partition_lowering.lower(
        model, construct_optimizer(), 5, mesh=mesh, topology=topo,
        im_size=224,
    )
    state = trainer.create_train_state(
        model, jax.random.key(0), mesh, 224, layout=lowered.layout
    )
    train_step = lowered.train_step

    rng = np.random.default_rng(0)
    gbatch = sharding_lib.shard_batch(mesh, {
        "image": rng.standard_normal((batch, 224, 224, 3)).astype(np.float32),
        "label": rng.integers(0, 1000, size=(batch,)).astype(np.int32),
        "mask": np.ones((batch,), np.float32),
    })

    box = {"state": state}

    def window(iters: int) -> float:
        st = box["state"]
        t0 = time.perf_counter()
        for _ in range(iters):
            st, _metrics = train_step(st, gbatch)
        jax.block_until_ready(st)
        dt = time.perf_counter() - t0
        box["state"] = st
        return dt

    # XLA cost-model ledger of this workload (lowering only re-traces —
    # no extra compile): the measured flops the mfu field is sourced
    # from, extracted BEFORE the warmup donates the state buffers.
    # ``cost`` is per step of ``batch`` images; None when the backend
    # omits cost keys — main() falls back to the hand table, flagged
    # analytic.
    cost = costmodel.normalize_cost(
        train_step.lower(box["state"], gbatch).cost_analysis()
    )

    # compile + warmup
    window(1)
    window(3)

    meta = {
        "n_chips": n_chips,
        "batch": batch,
        "per_chip_batch": per_chip_batch,
        "device_kind": jax.devices()[0].device_kind,
        "cost": cost,  # ONE optimizer step of `batch` images (see above)
    }
    return window, meta


def build_eval_workload(per_chip_batch: int = 128):
    """Compiled+warmed EVAL step (trainer.make_eval_step — the exact
    forward validate()/test_model() and the serving engine run).

    The resulting img/s/chip is the serving engine's single-batch
    ceiling: one replica cannot exceed it at full batch occupancy
    (tools/serve_bench.py measures how close dynamic batching gets).
    Same ``window(iters) -> seconds`` contract as ``build_workload``.
    """
    import jax
    import numpy as np

    import distribuuuu_tpu.config as config
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.asyncplane import compile_cache
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.parallel import mesh as mesh_lib, sharding as sharding_lib

    config.reset_cfg()
    compile_cache.setup_from_cfg(cfg)
    cfg.MODEL.ARCH = "resnet50"
    cfg.MODEL.NUM_CLASSES = 1000
    n_chips = len(jax.devices())
    batch = per_chip_batch * n_chips

    mesh = mesh_lib.build_mesh()
    model = trainer.build_model_from_cfg()
    state = trainer.create_train_state(model, jax.random.key(0), mesh, 224)
    eval_step = trainer.make_eval_step(model, topk=5)

    rng = np.random.default_rng(0)
    gbatch = sharding_lib.shard_batch(mesh, {
        "image": rng.standard_normal((batch, 224, 224, 3)).astype(np.float32),
        "label": rng.integers(0, 1000, size=(batch,)).astype(np.int32),
        "mask": np.ones((batch,), np.float32),
    })

    def window(iters: int) -> float:
        t0 = time.perf_counter()
        m = None
        for _ in range(iters):
            m = eval_step(state, gbatch)
        jax.block_until_ready(m)
        return time.perf_counter() - t0

    window(1)
    window(3)
    meta = {"n_chips": n_chips, "batch": batch,
            "per_chip_batch": per_chip_batch}
    return window, meta


def main():
    import jax

    window, meta = build_workload(per_chip_batch=128)
    n_chips, batch = meta["n_chips"], meta["batch"]
    per_chip_batch = meta["per_chip_batch"]

    # timed steady state — best of three windows
    iters = 40  # optimizer steps
    dt = min(window(iters) for _ in range(3))

    img_per_sec = batch * iters / dt
    img_per_sec_per_chip = img_per_sec / n_chips
    peak = PEAK_BF16.get(jax.devices()[0].device_kind)
    out = {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(img_per_sec_per_chip, 2),
        "unit": "images/sec/chip",
        # bf16-TPU vs the reference's fp32 A100 DDP (the setup its published
        # baselines used; it has no AMP mode) — see module docstring.
        "vs_baseline": round(
            img_per_sec_per_chip / BASELINE_IMG_PER_SEC_PER_CHIP, 3
        ),
        "baseline": "A100 fp32 DDP ~400 img/s/GPU (reference has no AMP)",
        "per_chip_batch": per_chip_batch,
    }
    # mfu: measured flops (XLA cost ledger of the very step program the
    # window timed) over the device peak; the hand table is demoted to a
    # cross-check — flops_drift_pct > ±5% means it rotted (satellite:
    # the table no longer silently drifts as models change).
    cost = meta.get("cost")
    flops_per_img = None
    if cost and cost.get("flops"):
        flops_per_img = cost["flops"] / batch  # cost is per step (meta)
        out["flops_per_img"] = round(flops_per_img, 1)
        out["mfu_source"] = "xla"
        drift = costmodel.drift_pct(
            flops_per_img, RESNET50_TRAIN_FLOPS_PER_IMG
        )
        out["flops_drift_pct"] = round(drift, 2)
        if abs(drift) > DRIFT_WARN_PCT:
            print(
                f"# WARNING: hand FLOP table drifted {drift:+.1f}% from "
                f"the XLA cost model ({flops_per_img / 1e9:.2f} vs "
                f"{RESNET50_TRAIN_FLOPS_PER_IMG / 1e9:.2f} GFLOP/img) — "
                "update RESNET50_TRAIN_FLOPS_PER_IMG",
                file=sys.stderr,
            )
    else:
        # backend omitted cost keys: analytic fallback, flagged
        flops_per_img = RESNET50_TRAIN_FLOPS_PER_IMG
        out["mfu_source"] = "analytic"
    if peak and flops_per_img:
        out["mfu"] = round(img_per_sec_per_chip * flops_per_img / peak, 4)

    # eval path (VERDICT r5 item 5): the inference forward test_model and
    # the serving engine run — its img/s/chip is serving's per-replica
    # throughput ceiling (PERF.md zoo table, eval column).
    eval_window, eval_meta = build_eval_workload(per_chip_batch=128)
    eval_iters = 10
    eval_dt = min(eval_window(eval_iters) for _ in range(3))
    eval_img_per_sec = eval_meta["batch"] * eval_iters / eval_dt
    out["eval_images_per_sec_per_chip"] = round(
        eval_img_per_sec / eval_meta["n_chips"], 2
    )
    out["eval_batch_ms"] = round(
        eval_dt / eval_iters * 1e3, 2
    )
    print(json.dumps(out))


if __name__ == "__main__":
    main()
