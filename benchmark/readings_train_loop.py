"""The readings that ``train_loop``'s limits are set from (PERF.md section 2),
in one process on the chip: for each seed the program's first steps as the
driver takes them (the program's ``Loader`` and ``train_epoch``, the driver's
``FirstSteps``), the float32 reference over the same pool rows, and what the
control and the planted faults read against that reference when they are put
in the program's place:

* ``control``: the reference with every convolution's and the head's operands
  held in the type below the configuration's (fp8 e4m3 under bfloat16;
  bfloat16 under float32);
* ``half_batch``: the reference on the first half of each batch's rows, the
  mean taken over those;
* ``plain_momentum``: the reference with the Nesterov look-ahead left out (a
  wrong update rule).

A state left unchanged reads 1 in ``gradient_norm_worst_leaf`` and
``change_norm_worst_leaf`` by construction and needs no run. One JSON line a
seed on standard output and in ``<--out>/readings_train_loop.jsonl``.

    python benchmark/readings_train_loop.py --workload resnet50.trainloop_hostfed \\
        --seeds 11 2500000011 ... [--rehearse]
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--witness", type=int, default=0, metavar="N",
                   help="a second witness on the first N seeds: the PROGRAM "
                        "computing in float32 (train_job.dtype), which has to "
                        "side with the reference where bfloat16 does not")
    p.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"),
                   help="directory of readings_train_loop.jsonl")
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmark.harness import cli
    from benchmark.harness.discovery import Catalog
    from benchmark.reference import sgd_steps
    from distribuuuu_tpu.asyncplane.compile_cache import setup_from_cfg
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.data.loader import Loader
    from distribuuuu_tpu.trainer import create_train_state, train_epoch
    from distribuuuu_tpu.utils.logger import get_logger

    catalog = Catalog()
    cell = catalog.cell(args.workload)
    argv = ["--workload", args.workload] + (["--rehearse"] if args.rehearse else [])
    run = cli.Run(catalog, cell, argv, time.perf_counter())
    driver, base = catalog.driver("train_loop"), catalog.driver("train_step")
    lowered, job, global_batch = base.build(run, 1, jax.devices()[:1])
    traffic = run.traffic
    cfg.merge_from_list([str(x) for kv in traffic["overrides"].items() for x in kv])
    setup_from_cfg(cfg)
    reference = catalog.reference(cell.config["reference"])
    architecture, sgd = run.section("architecture"), traffic["reference_sgd"]
    group = job["per_chip_batch"]
    below = jnp.float8_e4m3fn if job["dtype"] == "bfloat16" else jnp.bfloat16
    same = jnp.dtype(job["dtype"])
    followers = {
        "reference": sgd_steps.follower(reference, architecture, sgd, group),
        "control": sgd_steps.follower(reference, architecture, sgd, group, below),
        # the configuration's own type on the reference's tensors (XLA:TPU
        # drops a float32 -> bfloat16 -> float32 round trip, so on the chip
        # this is the reference compiled another way)
        "same_type": sgd_steps.follower(reference, architecture, sgd, group, same),
        "half_batch": sgd_steps.follower(reference, architecture, sgd, group // 2),
        "plain_momentum": sgd_steps.follower(
            reference, architecture, {**sgd, "nesterov": False}, group),
    }
    statistics = {
        "reference": sgd_steps.statistics_after(reference, architecture, group),
        "control": sgd_steps.statistics_after(reference, architecture, group, below),
        "same_type": sgd_steps.statistics_after(reference, architecture, group, same),
        "half_batch": sgd_steps.statistics_after(reference, architecture, group // 2),
    }
    statistics["plain_momentum"] = statistics["reference"]
    os.makedirs(args.out, exist_ok=True)
    programs = [("program", lowered, args.seeds)]
    if args.witness:
        wide = cli.Run(catalog, cell, argv + ["--set", 'train_job.dtype="float32"'],
                       time.perf_counter())
        programs.append(("program_float32", None, args.seeds[:args.witness]))
    with open(os.path.join(args.out, "readings_train_loop.jsonl"), "a") as log:
        for side, lowered, seeds in programs:
            if lowered is None:  # built last: ``build`` resets the global config
                lowered, _job, _batch = base.build(wide, 1, jax.devices()[:1])
                cfg.merge_from_list(
                    [str(x) for kv in traffic["overrides"].items() for x in kv])
            for seed in seeds:
                t0 = time.perf_counter()
                images, labels = driver.make_pool(
                    seed, traffic["pool_images"], job["im_size"], cfg.MODEL.NUM_CLASSES)
                warm = Loader(
                    driver.Pool(images, labels, traffic["warmup_steps"] * global_batch),
                    batch_size=global_batch, shuffle=True, drop_last=True,
                    workers=cfg.TRAIN.WORKERS, seed=seed,
                )
                state = create_train_state(
                    lowered.model, jax.random.key(seed), lowered.mesh, job["im_size"],
                    layout=lowered.layout,
                )
                before, stats = jax.device_get((state.params, state.batch_stats))
                first = driver.FirstSteps(lowered.train_step, traffic["follow_steps"])
                state, _, _ = train_epoch(warm, lowered.mesh, state, first, 0, get_logger())
                del state
                drawn, strangers = driver.drawn_from_pool(images, labels, first.batches)
                halves = [
                    {k: v[: global_batch // 2] for k, v in b.items()} for b in drawn]
                want = sgd_steps.follow(
                    followers["reference"], statistics["reference"], before,
                    stats, drawn)
                sides = {side: first.observed(before, stats, sgd["weight_decay"])}
                for name in ("control", "same_type", "half_batch", "plain_momentum"):
                    if side == "program":
                        sides[name] = sgd_steps.follow(
                            followers[name], statistics[name], before, stats,
                            halves if name == "half_batch" else drawn)
                line = {"seed": seed, "rows_not_from_pool": strangers,
                        "reference_loss": want["loss"], "seconds": None,
                        "leaves": sgd_steps.leaf_paths(want["gradient"])}
                for name, got in {"reference": want, **sides}.items():
                    # every leaf's norm, so that any statistic of them can be
                    # read off the record afterwards
                    line[name] = {
                        "loss": got["loss"],
                        "statistics_norms": sgd_steps.leaf_norms(got["statistics"]),
                        "gradient_norms": sgd_steps.leaf_norms(got["gradient"]),
                        "change_norms": sgd_steps.leaf_norms(got["change"]),
                        **({} if name == "reference" else {
                            **sgd_steps.gaps(got, want),
                            **{k: v for k, (v, _where) in
                               sgd_steps.others(got, want).items()}}),
                    }
                line["seconds"] = time.perf_counter() - t0
                text = json.dumps(line)
                print(text, flush=True)
                log.write(text + "\n")
                log.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
