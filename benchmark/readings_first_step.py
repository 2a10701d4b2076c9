"""The readings ``lm_train_step.first_step_errors``'s rules stand on (PERF.md
section 6, PR 52), in one process on the chip with ONE compile of the step:
for each seed the cell's fresh state and batch as its driver makes them, the
program's FIRST step alone (no warm-up, no window), and per leaf

* ``update`` and ``second_moment`` as the drivers read them now (an element
  that is the plain AdamW step's value or a NEIGHBOURING value of its type is
  equal) beside ``update_old`` (every element's difference summed), and how
  many elements lie exactly one spacing off;
* with ``--gradient`` the first gradient against the configuration's float32
  reference on the same batch: ``gradient`` as the drivers read it now (a leaf
  of fewer than 8 elements with its module) beside ``gradient_old`` (every
  leaf alone);
* with ``--faults N``, on the first N seeds, what the plain AdamW step put in
  the program's place reads with one fault: the rate doubled, the weight
  decay left out, the two moments held in bfloat16.

One JSON line a seed in ``<--out>/readings_first_step.jsonl`` (every leaf);
on standard output a line a seed with the worst leaf of each number beside
the configuration's limit.

    python benchmark/readings_first_step.py --workload sdar_30b_a3b.train_seq8192 \\
        --seeds 1332079065 2147485101 ... --faults 3 [--gradient] [--rehearse]
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
    p.add_argument("--gradient", action="store_true",
                   help="also the first gradient against the float32 reference")
    p.add_argument("--faults", type=int, default=0, metavar="N")
    p.add_argument("--rehearse", action="store_true")
    p.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"))
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmark.harness import cli
    from benchmark.harness.discovery import Catalog
    from distribuuuu_tpu.asyncplane.compile_cache import setup_from_cfg
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.trainer import create_train_state

    catalog = Catalog()
    cell = catalog.cell(args.workload)
    argv = ["--workload", args.workload] + (["--rehearse"] if args.rehearse else [])
    run = cli.Run(catalog, cell, argv, time.perf_counter())
    base = catalog.driver("lm_train_step")
    driver = catalog.driver(run.traffic["driver"])
    lowered, job, _state, avals = base.build(run, 1, jax.devices()[:1])
    setup_from_cfg(cfg)
    architecture = run.section("architecture")
    adamw, lr, limits = job["adamw"], job["lr"], job["reference_tolerance"]
    b1, b2, eps, wd = (adamw[k] for k in ("b1", "b2", "eps", "weight_decay"))

    # the batch and the reference's gradient, as each driver's ``run`` has them
    if run.traffic["driver"] == "lm_dense_train_step":
        def batch_of(seed):
            return base.make_batch(seed, avals, cfg.MODEL.NUM_CLASSES)

        def reference_gradient(reference, batch, state):
            reference.batch = jax.device_put(
                (batch["image"], batch["label"]), reference.device)
            return state, reference.first_step(state.params)
    elif run.traffic["driver"] == "lm_diffusion_train_step":
        first = architecture["share_rank"] * architecture["vocab_held"]
        held = [r for r in range(first, first + architecture["vocab_held"])
                if r != architecture["mask_id"]]

        def batch_of(seed):
            return driver.make_batch(base.seed_key(seed), avals, held[0], len(held))

        def reference_gradient(reference, batch, state):
            reference.tokens = batch["image"]
            reference.batch = jax.device_put(batch["image"], reference.device)
            # the moments wait on the host, as in the driver: the walk wants the room
            moments = jax.tree.map(lambda x: x.sharding, state.opt_state)
            aside = jax.device_get(state.opt_state)
            jax.tree.map(lambda x: x.delete(), state.opt_state)
            before = reference.first_step(state.params, driver.step_key(state))
            return state.replace(opt_state=jax.device_put(aside, moments)), tuple(before)
    else:
        raise SystemExit(
            f"readings_first_step: no batch written down for driver "
            f"{run.traffic['driver']!r}: add its two functions here")

    relative = base.relative

    @jax.jit
    def beside(p0, g_ref, p1, m1):
        """Per leaf: the old rules' readings and the elements by their
        distance from the plain step's value."""
        def leaf(p0, g_ref, p1, m1):
            g, step, _v = base.plain_adamw_step(adamw, lr, p0, m1)
            want = (p0 - step).astype(p1.dtype)
            beyond = base.beyond_one_spacing(p1, want) != 0
            return {
                "update_old": relative(p1 - want, step),
                "gradient_old": relative(g - g_ref, g_ref),
                "one_spacing_off": jnp.sum((p1 != want) & ~beyond),
                "beyond": jnp.sum(beyond),
                "step_norm": jnp.sqrt(jnp.sum(jnp.square(step))),
            }
        return jax.tree.map(leaf, p0, g_ref, p1, m1)

    def low(x):
        # not a cast there and back: XLA:TPU drops that round trip
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    @jax.jit
    def faults(p0, m1):
        """Per leaf, ``update`` (and ``second_moment``) as the drivers read
        them, of the plain step with one fault in the program's place."""
        def leaf(p0, m1):
            g, step, v = base.plain_adamw_step(adamw, lr, p0, m1)
            m_low, v_low = low(m1), low(v)
            u_low = m_low / (1 - b1) / (jnp.sqrt(v_low / (1 - b2)) + eps)
            # what the check derives from the moments such a step leaves
            _g, step_low, v_check = base.plain_adamw_step(adamw, lr, p0, m_low)
            return {
                "rate_doubled": relative(
                    base.beyond_one_spacing(p0 - 2 * step, p0 - step), step),
                "no_weight_decay": relative(
                    base.beyond_one_spacing(p0 - (step - lr * wd * p0), p0 - step), step),
                "moments_in_bfloat16": relative(base.beyond_one_spacing(
                    p0 - lr * (u_low + wd * p0), p0 - step_low), step_low),
                "moments_in_bfloat16_second_moment": relative(v_low - v_check, v_check),
            }
        return jax.tree.map(leaf, p0, m1)

    def by_leaf(tree):
        flat, _ = jax.tree_util.tree_flatten_with_path(
            jax.device_get(tree), is_leaf=lambda x: isinstance(x, dict) and (
                "update_old" in x or "rate_doubled" in x))
        return {jax.tree_util.keystr(path): {k: float(v) for k, v in e.items()}
                for path, e in flat}

    def worst(leaves, kind):
        path = max(leaves, key=lambda q: leaves[q][kind])
        return leaves[path][kind], path

    reference = None
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "readings_first_step.jsonl"), "a") as log:
        for i, seed in enumerate(args.seeds):
            t0 = time.perf_counter()
            batch = batch_of(seed)
            state = create_train_state(
                lowered.model, base.seed_key(seed), lowered.mesh, cfg.TRAIN.IM_SIZE,
                layout=lowered.layout)
            layout = jax.tree.map(lambda x: x.sharding, state.params)
            if args.gradient:
                reference = reference or driver.Reference(run, batch)
                state, (before, want) = reference_gradient(reference, batch, state)
            else:
                before, want = jax.device_get(state.params), None
            state, _ = jax.block_until_ready(lowered.train_step(state, batch))
            p0 = jax.device_put(before, layout)
            # without a reference the parameters hold the gradient's place: not read
            g_ref = p0 if want is None else jax.device_put(want, layout)
            del before, want
            mu = base.adamw_moments(state.opt_state).mu
            leaves = base.first_step_errors(adamw, lr, p0, g_ref, state)
            for path, e in by_leaf(beside(p0, g_ref, state.params, mu)).items():
                leaves[path].update(e)
            kinds = ["update", "update_old", "second_moment"] + (
                ["gradient", "gradient_old"] if args.gradient else [])
            line = {"workload": args.workload, "seed": seed,
                    "one_spacing_off": int(sum(e["one_spacing_off"] for e in leaves.values())),
                    "beyond": int(sum(e["beyond"] for e in leaves.values())),
                    "worst": {k: worst(leaves, k) for k in kinds}}
            if not args.gradient:
                for e in leaves.values():
                    del e["gradient"], e["gradient_old"]
            if i < args.faults:
                planted = by_leaf(faults(p0, mu))
                line["faults"] = {k: {"worst": worst(planted, k),
                                      "least": min(e[k] for e in planted.values())}
                                  for k in next(iter(planted.values()))}
            del state, p0, g_ref, mu
            line["seconds"] = time.perf_counter() - t0
            said = {k: f"{v:.3e} {path}" for k, (v, path) in line["worst"].items()}
            print(json.dumps({**{k: v for k, v in line.items() if k != "worst"},
                              "worst": said,
                              "limits": {k: limits[k] for k in
                                         ("update", "second_moment", "gradient")}}),
                  flush=True)
            log.write(json.dumps({**line, "leaves": leaves}) + "\n")
            log.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
