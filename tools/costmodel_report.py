"""Generate the committed cost-model ledger: ``COSTMODEL_r*.json``.

For every shipped arch YAML under ``config/`` (the exact
``merge_from_file`` path train_net uses — a stale key fails right here),
build the real train and eval step programs and record XLA's own
``cost_analysis`` / ``memory_analysis`` through
``telemetry/costmodel.build_ledger``: per-step flops, bytes accessed,
arithmetic intensity and roofline verdict, executable HBM footprint vs
device capacity (headroom %), plus a timed MFU on the current backend
and the analytic-table drift cross-check where the hand table has an
entry. A ``serve`` section records the same ledger for every AOT bucket
shape of the serving forward (``--serve-arch``, default resnet50).

The committed artifact is the regression reference
``tools/bench_history.py`` folds into BENCH_INDEX.json
(``train_step_mfu`` / ``train_step_hbm_headroom_pct`` series — gated by
``run_report --compare BENCH_INDEX.json`` like throughput) and the
per-arch memory budget RUNBOOK's compute-vs-memory-bound recipe reads.

    python tools/costmodel_report.py --out COSTMODEL_r01.json
    python tools/costmodel_report.py --arch resnet50 --no-memory  # quick
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import time

import _path  # noqa: F401  (repo root onto sys.path)

LEDGER_SCHEMA = 1


def _arch_yamls(config_dir: str, subset: set | None):
    import yaml

    out = []
    for path in sorted(glob.glob(os.path.join(config_dir, "*.yaml"))):
        with open(path) as f:
            doc = yaml.safe_load(f) or {}
        arch = (doc.get("MODEL") or {}).get("ARCH")
        if arch is None:
            continue  # a non-cfg YAML species (monitor_rules.yaml)
        if subset is None or arch in subset:
            out.append((arch, path))
    return out


def _analyze(fn, args, *, with_memory: bool, time_steps: int,
             donated_state: bool):
    """Lower once; compile AT MOST once (the same executable serves
    memory_analysis AND the timing loop — no wasted compiles). Returns
    (cost, memory, mean_step_seconds)."""
    from distribuuuu_tpu.telemetry import costmodel

    lowered = fn.lower(*args)
    try:
        cost = costmodel.normalize_cost(lowered.cost_analysis())
    except Exception:
        cost = None
    memory = None
    mean_s = None
    if with_memory or time_steps:
        import jax

        compiled = lowered.compile()
        try:
            memory = costmodel.normalize_memory(compiled.memory_analysis())
        except Exception:
            memory = None
        if time_steps:
            state, batch = args
            out = compiled(state, batch)  # warm (first call may page in)
            if donated_state:
                state = out[0]
            jax.block_until_ready(jax.tree.leaves(out)[0])
            t0 = time.perf_counter()
            for _ in range(time_steps):
                out = compiled(state, batch)
                if donated_state:
                    state = out[0]
            jax.block_until_ready(jax.tree.leaves(out)[0])
            mean_s = (time.perf_counter() - t0) / time_steps
    return cost, memory, mean_s


def _entry(label, phase, cost, memory, *, images, arch, peaks, n_devices,
           mean_step_s):
    from distribuuuu_tpu.telemetry import costmodel

    ledger = costmodel.build_ledger(
        label, phase, cost, memory, images=images, arch=arch, peaks=peaks,
        n_devices=n_devices,
    )
    entry = {k: v for k, v in ledger.items() if v is not None}
    step = ledger["step"]
    if mean_step_s is not None:
        entry["step_seconds"] = round(mean_step_s, 4)
        if step.get("flops") and step.get("peak_flops"):
            entry["mfu"] = round(
                costmodel.mfu_value(
                    step["flops"], mean_step_s, step["peak_flops"]
                ), 4
            )
    # hand-table cross-check, where the table has this arch
    table = costmodel.analytic_step_flops(
        arch, images, train=(phase == "train")
    )
    if table and step.get("flops") and step["source"] == "xla":
        entry["flops_drift_pct"] = round(
            costmodel.drift_pct(step["flops"], table), 2
        )
    return entry


def _abstract_arch(arch, yaml_path, model, optimizer, mesh, train_step,
                   eval_step, *, batch, with_memory, token_arch) -> dict:
    """``build_arch`` without a state or a batch: ``Lowered.abstract_args``'
    shapes, lowered and compiled, never run (``--time-steps 0``)."""
    import jax

    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.parallel.partition import lowering
    from distribuuuu_tpu.telemetry import costmodel

    im = cfg.TRAIN.IM_SIZE
    low = lowering.Lowered(
        mesh=mesh, topology=None, layout=trainer._state_layout(model, mesh, im),
        step_layout=None, train_step=train_step, eval_step=eval_step,
        model=model, optimizer=optimizer, im_size=im,
    )
    state, batch_tree = low.abstract_args(batch, with_mask=True)
    if token_arch:  # abstract_args sizes tokens by the model's init dummy
        shape = (batch, int(cfg.LM.SEQ_LEN))
        batch_tree = {
            k: v if k == "mask" else jax.ShapeDtypeStruct(
                shape, v.dtype, sharding=v.sharding)
            for k, v in batch_tree.items()
        }
    entries = {}
    for phase, fn in (("eval", eval_step), ("train", train_step)):
        cost, memory, _ = _analyze(
            fn, (state, batch_tree), with_memory=with_memory, time_steps=0,
            donated_state=False,
        )
        entries[phase] = _entry(
            f"{phase}_step", phase, cost, memory, images=batch, arch=arch,
            peaks=costmodel.peaks_for(), n_devices=len(jax.devices()),
            mean_step_s=None,
        )
    return {"yaml": os.path.relpath(yaml_path), "im_size": im, "batch": batch,
            "abstract": True, **entries}


def build_arch(arch: str, yaml_path: str, *, batch: int, with_memory: bool,
               time_steps: int) -> dict:
    import jax
    import numpy as np

    import distribuuuu_tpu.config as config
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.parallel import mesh as mesh_lib, sharding as sharding_lib
    from distribuuuu_tpu.parallel.partition import specs as partition_specs
    from distribuuuu_tpu.telemetry import costmodel
    from distribuuuu_tpu.utils.optim import construct_optimizer

    config.reset_cfg()
    cfg.merge_from_file(yaml_path)  # the exact train_net merge path
    im = cfg.TRAIN.IM_SIZE
    # the ledger measures the ARCH on the attached device(s); a YAML's
    # multi-axis MESH stanza (gpt_nano_moe's dp2·tp2·ep2) is the stanza
    # gate's territory and cannot resolve on fewer devices
    for axis, default in (("DATA", -1), ("MODEL", 1), ("SEQ", 1),
                          ("PIPE", 1), ("EXPERT", 1)):
        cfg.MESH[axis] = default
    mesh = mesh_lib.build_mesh()
    model = trainer.build_model_from_cfg()
    layout = trainer._state_layout(model, mesh, im)
    optimizer = construct_optimizer()
    step_layout = layout if cfg.MESH.ZERO else None
    train_step = trainer.make_train_step(
        model, optimizer, topk=trainer.effective_topk(), layout=step_layout
    )
    eval_step = trainer.make_eval_step(model, trainer.effective_topk())
    token_arch = partition_specs.is_token_arch(arch)
    if time_steps == 0:
        # nothing runs, so nothing is materialized: the state and the batch
        # are shapes with their declared shardings. The only way to ledger an
        # arch whose state no host holds (olmoe_1b_7b: 110 GB with AdamW)
        return _abstract_arch(
            arch, yaml_path, model, optimizer, mesh, train_step, eval_step,
            batch=batch, with_memory=with_memory, token_arch=token_arch,
        )
    state = trainer.create_train_state(model, jax.random.key(0), mesh, im,
                                       layout=layout)

    rng = np.random.default_rng(0)
    if token_arch:
        # the LM species eats token batches (ISSUE 12); "images" counts
        # sequences — the lm bench converts to tokens/s with the seq len
        S = int(cfg.LM.SEQ_LEN)
        batch_tree = sharding_lib.shard_batch(mesh, {
            "image": rng.integers(
                0, cfg.MODEL.NUM_CLASSES, (batch, S)
            ).astype(np.int32),
            "label": rng.integers(
                0, cfg.MODEL.NUM_CLASSES, (batch, S)
            ).astype(np.int32),
            "mask": np.ones((batch,), np.float32),
        })
    else:
        batch_tree = sharding_lib.shard_batch(mesh, {
            "image": rng.standard_normal(
                (batch, im, im, 3)
            ).astype(np.float32),
            "label": rng.integers(
                0, cfg.MODEL.NUM_CLASSES, (batch,)
            ).astype(np.int32),
            "mask": np.ones((batch,), np.float32),
        })
    peaks = costmodel.peaks_for()
    n_dev = len(jax.devices())

    # eval first: the train timing loop DONATES the state buffers
    # (donate_argnums=0), so anything else reading them must run before
    cost, memory, mean_s = _analyze(
        eval_step, (state, batch_tree), with_memory=with_memory,
        time_steps=time_steps, donated_state=False,
    )
    evale = _entry("eval_step", "eval", cost, memory, images=batch,
                   arch=arch, peaks=peaks, n_devices=n_dev,
                   mean_step_s=mean_s)
    cost, memory, mean_s = _analyze(
        train_step, (state, batch_tree), with_memory=with_memory,
        time_steps=time_steps, donated_state=True,
    )
    train = _entry("train_step", "train", cost, memory, images=batch,
                   arch=arch, peaks=peaks, n_devices=n_dev,
                   mean_step_s=mean_s)
    return {
        "yaml": os.path.relpath(yaml_path),
        "im_size": im,
        "batch": batch,
        "train": train,
        "eval": evale,
    }


def build_serve(arch_yaml: str, *, with_memory: bool) -> dict:
    """Bucket ledger of the serving forward (engine._forward's math: the
    eval apply over uint8 input with in-graph normalization) for every
    default bucket shape — what Engine emits live as cost.* records."""
    import jax
    import numpy as np

    import distribuuuu_tpu.config as config
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.parallel import mesh as mesh_lib
    from distribuuuu_tpu.serve.engine import default_buckets
    from distribuuuu_tpu.telemetry import costmodel

    config.reset_cfg()
    cfg.merge_from_file(arch_yaml)
    im = cfg.TRAIN.IM_SIZE
    mesh = mesh_lib.build_mesh()
    model = trainer.build_model_from_cfg()
    state = trainer.create_train_state(model, jax.random.key(0), mesh, im)
    variables = {"params": state.params, "batch_stats": state.batch_stats}

    def fwd(variables, images):
        from distribuuuu_tpu.data.transforms import normalize_in_graph

        return model.apply(variables, normalize_in_graph(images), train=False)

    jit_fwd = jax.jit(fwd)
    peaks = costmodel.peaks_for()
    n_dev = len(jax.devices())
    buckets = {}
    for b in default_buckets(cfg.SERVE.MAX_BATCH):
        sds = jax.ShapeDtypeStruct((b, im, im, 3), np.uint8)
        cost, memory, _ = _analyze(
            jit_fwd, (variables, sds), with_memory=with_memory,
            time_steps=0, donated_state=False,
        )
        buckets[str(b)] = _entry(
            f"serve_bucket_{b}", "serve", cost, memory, images=b,
            arch=cfg.MODEL.ARCH, peaks=peaks, n_devices=n_dev,
            mean_step_s=None,
        )
    return {"arch": cfg.MODEL.ARCH, "im_size": im, "buckets": buckets}


def main(argv=None) -> int:
    import jax

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap.add_argument("--config-dir", default=os.path.join(repo, "config"))
    ap.add_argument("--arch", default=None,
                    help="comma-separated subset (default: every arch YAML)")
    ap.add_argument("--batch", type=int, default=8,
                    help="per-step images for the train/eval programs")
    ap.add_argument("--time-steps", type=int, default=2,
                    help="timed steps for the backend MFU (0 = skip timing)")
    ap.add_argument("--no-memory", action="store_true",
                    help="skip memory_analysis (no compiles — fast scan)")
    ap.add_argument("--serve-arch", default="resnet50",
                    help="arch for the serve-bucket ledger ('' = skip)")
    ap.add_argument("--out", default=None,
                    help="destination (default {repo}/COSTMODEL_r01.json)")
    ap.add_argument("--update", action="store_true",
                    help="merge the selected arch entries into an existing "
                         "artifact instead of rewriting it (append a new "
                         "arch without re-measuring the whole zoo; "
                         "unselected entries keep their committed numbers)")
    args = ap.parse_args(argv)

    subset = set(args.arch.split(",")) if args.arch else None
    entries = _arch_yamls(args.config_dir, subset)
    if not entries:
        ap.error(f"no arch YAMLs matched under {args.config_dir!r}")
    with_memory = not args.no_memory

    from distribuuuu_tpu.telemetry import costmodel

    doc = {
        "costmodel": LEDGER_SCHEMA,
        "generated_by": "tools/costmodel_report.py",
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "peaks": costmodel.peaks_for(),
        "batch": args.batch,
        "archs": {},
    }
    serve_yaml = None
    for arch, path in entries:
        t0 = time.perf_counter()
        doc["archs"][arch] = build_arch(
            arch, path, batch=args.batch, with_memory=with_memory,
            time_steps=args.time_steps,
        )
        if arch == args.serve_arch:
            serve_yaml = path
        tr = doc["archs"][arch]["train"]
        flops = tr["step"].get("flops")
        print(
            f"{arch:<18} {'' if flops is None else f'{flops / 1e9:8.2f} GFLOP/step'}"
            f"  bound={((tr.get('roofline') or {}).get('bound'))}"
            f"  mfu={tr.get('mfu')}"
            f"  headroom={(tr.get('memory') or {}).get('headroom_pct')}%"
            f"  ({time.perf_counter() - t0:.1f}s)"
        )
    if args.serve_arch and serve_yaml is not None:
        doc["serve"] = build_serve(serve_yaml, with_memory=with_memory)
        print(f"serve buckets ({args.serve_arch}): "
              + ", ".join(doc["serve"]["buckets"]))
    out = args.out or os.path.join(repo, "COSTMODEL_r01.json")
    if args.update and os.path.exists(out):
        with open(out) as f:
            existing = json.load(f)
        existing["archs"].update(doc["archs"])
        if "serve" in doc:
            existing["serve"] = doc["serve"]
        doc = existing
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"cost-model ledger ({len(doc['archs'])} arch(s)) -> {out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
