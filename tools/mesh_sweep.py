"""Generated multichip sweep: the topology registry drives the dryrun.

Replaces ``__graft_entry__.py:dryrun_multichip``'s hand-enumerated case
matrix (r1-r5: every new parallelism form appended another bespoke
stanza) with a sweep GENERATED from the partition-layer topology
registry (parallel/partition/topology.enumerate_topologies): every valid
(mesh shape × ZeRO stage × representative arch) class on the attached
device count, each executed as one (or an accumulated) train step
through the ONE partition lowering — built from a YAML mesh stanza
alone, exactly the way ``train_net.py --cfg`` would.

Every case the old matrix enumerated appears in the generated set
(``legacy_matrix`` pins this; tests/test_partition.py asserts the
containment), plus the compositions that had no code path before r11:
ZeRO-3 under PP, and a dp×tp×ep 3-axis mesh with ZeRO-1.

Writes ``MULTICHIP_r06.json``: the full generated stanza list, per-case
results for the executed subset, and ``all_ok``.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/mesh_sweep.py [--out MULTICHIP_r06.json] [--full]

``--full`` also executes the extended classes (every generated class, not
just the legacy + acceptance set) — slower, same machinery.
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import _path  # noqa: F401  — repo root onto sys.path for the package import


# ------------------------------------------------------------ generation


def legacy_matrix(n_devices: int) -> list[dict]:
    """The (mesh axes, zero, arch) cases the PRE-r11 dryrun hand-enumerated
    — the floor the generated sweep must contain (tests/test_partition.py
    asserts containment). Op-level primitives (ring attention, raw GPipe,
    raw MoE dispatch) are pinned by ``op_probes``."""
    if n_devices % 8:
        return []
    tp = 2
    dp = n_devices // tp
    pipe = 4 if n_devices % 4 == 0 else 2
    return [
        # dp×tp at ZeRO 0/1/3 (resnet18) + accumulation on the stage-0 case
        {"axes": {"data": dp, "model": tp}, "zero": 0, "arch": "resnet18"},
        {"axes": {"data": dp, "model": tp}, "zero": 1, "arch": "resnet18"},
        {"axes": {"data": dp, "model": tp}, "zero": 3, "arch": "resnet18"},
        # trainer-level PP (+ZeRO-1) on a data×pipe mesh
        {"axes": {"data": n_devices // pipe, "pipe": pipe}, "zero": 0,
         "arch": "vit_tiny"},
        {"axes": {"data": n_devices // pipe, "pipe": pipe}, "zero": 1,
         "arch": "vit_tiny"},
        # PP×EP (experts riding the model axis) on a data×model×pipe mesh
        {"axes": {"data": n_devices // 4, "model": 2, "pipe": 2}, "zero": 0,
         "arch": "vit_tiny_moe"},
        # EP over the model axis (legacy dp×ep layout), partial + dispatch
        {"axes": {"data": dp, "model": tp}, "zero": 0,
         "arch": "vit_tiny_moe"},
    ]


def acceptance_cases(n_devices: int) -> list[dict]:
    """The ISSUE 9 compositions that were refused or pathless before the
    partition layer — both must train from a YAML stanza alone."""
    if n_devices % 8:
        return []
    return [
        # ZeRO-3 under PP (the check_trainer_mesh refusal, removed r11)
        {"axes": {"data": 2, "pipe": 4}, "zero": 3, "arch": "vit_tiny"},
        # 3-axis dp×tp×ep with ZeRO-1 (no expert axis existed before r11)
        {"axes": {"data": 2, "model": 2, "expert": 2}, "zero": 1,
         "arch": "vit_tiny_moe"},
    ]


def _full_axes(axes: dict) -> dict:
    out = {"data": 1, "model": 1, "seq": 1, "pipe": 1, "expert": 1}
    out.update(axes)
    return out


def _case_key(axes: dict, zero: int, arch: str):
    return (tuple(sorted(_full_axes(axes).items())), int(zero), arch)


def generate_cases(n_devices: int) -> list[dict]:
    """Every valid topology class on ``n_devices``, from the registry.

    Enumerates ``enumerate_topologies`` (default arch per feature set)
    PLUS the moe-arch variants where experts ride the model axis (the
    legacy EP layout — still a supported class), dedupes by
    (features, zero, arch) keeping one representative mesh shape per
    class (legacy/acceptance shapes preferred), and marks each case
    ``core`` (executed by the dryrun: the legacy floor, the acceptance
    compositions, and the pure-dp ZeRO ladder) or ``extended``.
    """
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.parallel.partition import topology as topo_lib

    pinned = {
        _case_key(c["axes"], c["zero"], c["arch"])
        for c in legacy_matrix(n_devices) + acceptance_cases(n_devices)
    }

    candidates = []
    for topo, arch in topo_lib.enumerate_topologies(n_devices):
        candidates.append((topo, arch))
        # legacy EP-over-model variant: a populated model axis can carry
        # the experts of a *_moe arch (MoeMlp.moe_axis="model")
        if topo.model > 1 and topo.expert == 1 and arch != "vit_tiny_moe":
            try:
                topo_lib.validate(topo, "vit_tiny_moe", cfg.MODEL.MOE)
            except topo_lib.TopologyError:
                pass
            else:
                candidates.append((topo, "vit_tiny_moe"))

    groups: dict = {}
    for topo, arch in candidates:
        key = (topo.features(), topo.zero, arch)
        groups.setdefault(key, []).append(topo)

    cases = []
    for (feats, zero, arch), topos in groups.items():
        rep = None
        for t in topos:
            if _case_key(t.axes, zero, arch) in pinned:
                rep = t
                break
        if rep is None:
            # deterministic: widest data axis first (the common layout)
            rep = sorted(
                topos, key=lambda t: (-t.axes["data"], t.class_name())
            )[0]
        degenerate_zero = zero > 0 and rep.data == 1  # ZeRO no-ops at dp=1
        core = (
            _case_key(rep.axes, zero, arch) in pinned
            or (feats <= {"dp", "zero1", "zero3"} and not degenerate_zero)
        )
        cases.append({
            "name": f"{rep.class_name()}[{arch}]",
            "class": rep.class_name(),
            "arch": arch,
            "axes": rep.axes,
            "zero": zero,
            "stanza": rep.mesh_stanza(),
            "tier": "core" if core else "extended",
            "degenerate_zero": degenerate_zero,
            "extras": _case_extras(rep, arch, zero),
        })
    cases.sort(key=lambda c: (c["tier"], c["name"]))
    return cases


def _case_extras(topo, arch, zero) -> list[str]:
    """Ride-along variants preserved from the legacy matrix, derived from
    the case class instead of hand-listed."""
    extras = []
    if arch == "resnet18" and zero == 0 and topo.model > 1:
        extras.append("accum")  # gradient accumulation
    if arch.endswith("_moe"):
        extras.append("dispatch")  # switch all_to_all strategy
        if topo.pipe > 1:
            extras.append("aux_check")  # balancing aux reaches the pp loss
    if topo.pipe > 1 and arch == "vit_tiny" and zero == 0:
        extras.append("flash")  # flash attention inside pipeline stages
    return extras


def op_probes(n_devices: int) -> list[dict]:
    """Op-level primitives over single-axis meshes — one probe per
    non-data mesh axis (generated from MESH_AXES, not hand-listed): the
    collectives the trainer-level cases compose are exercised raw."""
    from distribuuuu_tpu.parallel.mesh import MESH_AXES

    probes = []
    for axis in MESH_AXES:
        if axis == "data":
            continue
        if axis == "seq":
            probes.append({"op": "ring_attention", "axis": axis,
                           "size": n_devices})
            probes.append({"op": "ring_flash", "axis": axis,
                           "size": n_devices})
        elif axis == "pipe":
            probes.append({"op": "pp_grad", "axis": axis, "size": n_devices})
        elif axis in ("model", "expert"):
            probes.append({"op": "moe_dispatch", "axis": axis,
                           "size": n_devices})
    return probes


# -------------------------------------------------------------- execution


def _stanza_yaml(case: dict) -> str:
    """The YAML a user would write for this case — the sweep merges it
    verbatim (train-from-a-stanza-alone is the acceptance contract)."""
    import yaml

    mesh = dict(case["stanza"])
    doc = {
        "MODEL": {"ARCH": case["arch"], "NUM_CLASSES": 16},
        "TRAIN": {"IM_SIZE": 64 if case["axes"].get("seq", 1) > 1 else 32},
        "DEVICE": {"COMPUTE_DTYPE": "float32"},
        "MESH": mesh,
    }
    if case["axes"].get("pipe", 1) > 1:
        doc["MESH"]["MICROBATCH"] = 2
    return yaml.safe_dump(doc)


def _names_of(leaf):
    spec = getattr(getattr(leaf, "sharding", None), "spec", ())
    return {
        n for e in spec if e for n in ((e,) if isinstance(e, str) else e)
    }


def run_trainer_case(case: dict, rng) -> dict:
    """One case: merge the generated YAML stanza, validate through the
    registry, lower, train a step (plus the case's extras), verify the
    layout invariants on the LIVE placed state."""
    import jax
    import numpy as np

    import distribuuuu_tpu.config as config
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.parallel import mesh as mesh_lib, sharding as sharding_lib
    from distribuuuu_tpu.parallel.partition import lowering
    from distribuuuu_tpu.utils.optim import construct_optimizer

    t0 = time.perf_counter()
    config.reset_cfg()
    with tempfile.NamedTemporaryFile(
        "w", suffix=".yaml", delete=False
    ) as f:
        f.write(_stanza_yaml(case))
        stanza_path = f.name
    try:
        cfg.merge_from_file(stanza_path)
        topo = trainer.check_trainer_mesh()
        mesh = mesh_lib.mesh_from_cfg(cfg)
        model = trainer.build_model_from_cfg(topo)
        low = lowering.lower(
            model, construct_optimizer(), 5, mesh=mesh, topology=topo,
            im_size=cfg.TRAIN.IM_SIZE,
        )
        state = trainer.create_train_state(
            model, jax.random.key(0), mesh, cfg.TRAIN.IM_SIZE,
            layout=low.layout,
        )
        dp = topo.data
        mb = 2 * (topo.microbatch or 2) if topo.pipe > 1 else 4
        B = max(8, dp * mb)
        im = cfg.TRAIN.IM_SIZE
        host = {
            "image": rng.standard_normal((B, im, im, 3)).astype(np.float32),
            "label": (np.arange(B) % 16).astype(np.int32),
            "mask": np.ones((B,), np.float32),
        }
        state, metrics = low.train_step(state, low.put_batch(host))
        jax.block_until_ready(metrics["loss"])
        loss = float(metrics["loss"])
        checks = {"finite": bool(np.isfinite(loss))}

        # layout invariants on the live state (shard-size accounting, not
        # just specs — the old dryrun's strongest assertion, generalized)
        if topo.zero and dp > 1:
            tree = state.params if topo.zero == 3 else state.opt_state
            deduped = sum(
                1
                for leaf in jax.tree.leaves(tree)
                if hasattr(leaf, "addressable_shards")
                and "data" in _names_of(leaf)
                and leaf.addressable_shards[0].data.size < leaf.size
            )
            checks["zero_deduped"] = deduped > 0
        if topo.expert > 1:
            checks["expert_sharded"] = any(
                "expert" in _names_of(leaf)
                for leaf in jax.tree.leaves(state.params)
            )
        if topo.model > 1 and case["arch"] == "resnet18":
            checks["tp_sharded"] = any(
                "model" in _names_of(leaf)
                for leaf in jax.tree.leaves(state.params)
            )

        # extras preserved from the legacy matrix
        extras_run = []
        if "accum" in case["extras"]:
            accum_low = lowering.lower(
                model, construct_optimizer(), 5, mesh=mesh, topology=topo,
                im_size=im, accum=2,
            )
            _, ametrics = accum_low.train_step(
                trainer.create_train_state(
                    model, jax.random.key(1), mesh, im, layout=low.layout
                ),
                accum_low.put_batch(host),
            )
            jax.block_until_ready(ametrics["loss"])
            checks["accum_finite"] = bool(np.isfinite(float(ametrics["loss"])))
            extras_run.append("accum")
        if "aux_check" in case["extras"]:
            # a large balancing-aux weight must move the pipelined loss
            cfg.MODEL.MOE.AUX_WEIGHT = 10.0
            aux_low = lowering.lower(
                model, construct_optimizer(), 5, mesh=mesh, topology=topo,
                im_size=im,
            )
            _, am = aux_low.train_step(
                trainer.create_train_state(
                    model, jax.random.key(0), mesh, im, layout=low.layout
                ),
                aux_low.put_batch(host),
            )
            jax.block_until_ready(am["loss"])
            checks["aux_reaches_loss"] = float(am["loss"]) > loss
            cfg.MODEL.MOE.AUX_WEIGHT = 0.01
            extras_run.append("aux_check")
        if "dispatch" in case["extras"]:
            cfg.MODEL.MOE.IMPL = "dispatch"
            cfg.MODEL.MOE.CAPACITY_FACTOR = 8.0
            d_model = trainer.build_model_from_cfg(topo)
            d_low = lowering.lower(
                d_model, construct_optimizer(), 5, mesh=mesh, topology=topo,
                im_size=im,
            )
            d_state = trainer.create_train_state(
                d_model, jax.random.key(2), mesh, im, layout=d_low.layout
            )
            d_state, dm = d_low.train_step(d_state, d_low.put_batch(host))
            jax.block_until_ready(dm["loss"])
            checks["dispatch_finite"] = bool(np.isfinite(float(dm["loss"])))
            extras_run.append("dispatch")
        if "flash" in case["extras"]:
            cfg.DEVICE.ATTN_IMPL = "flash"
            f_model = trainer.build_model_from_cfg(topo)
            f_low = lowering.lower(
                f_model, construct_optimizer(), 5, mesh=mesh, topology=topo,
                im_size=im,
            )
            f_state = trainer.create_train_state(
                f_model, jax.random.key(3), mesh, im, layout=f_low.layout
            )
            f_state, fm = f_low.train_step(f_state, f_low.put_batch(host))
            jax.block_until_ready(fm["loss"])
            checks["flash_finite"] = bool(np.isfinite(float(fm["loss"])))
            cfg.DEVICE.ATTN_IMPL = "auto"
            extras_run.append("flash")

        return {
            "name": case["name"], "kind": "trainer", "arch": case["arch"],
            "mesh": {k: v for k, v in case["axes"].items() if v > 1},
            "zero": case["zero"], "loss": round(loss, 4),
            "checks": checks, "extras": extras_run,
            "ok": all(checks.values()),
            "seconds": round(time.perf_counter() - t0, 1),
        }
    except Exception as e:  # noqa: BLE001 — a sweep reports, not aborts
        return {
            "name": case["name"], "kind": "trainer", "arch": case["arch"],
            "mesh": {k: v for k, v in case["axes"].items() if v > 1},
            "zero": case["zero"], "ok": False,
            "error": f"{type(e).__name__}: {e}",
            "seconds": round(time.perf_counter() - t0, 1),
        }
    finally:
        os.unlink(stanza_path)
        config.reset_cfg()


def run_op_probe(probe: dict, rng) -> dict:
    """One op-level primitive over a single-axis mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distribuuuu_tpu.parallel import mesh as mesh_lib

    t0 = time.perf_counter()
    n = probe["size"]
    axis = probe["axis"]
    try:
        mesh = mesh_lib.build_mesh(
            data=1, devices=jax.devices()[:n], **{axis: n}
        )
        if probe["op"] in ("ring_attention", "ring_flash"):
            from distribuuuu_tpu.ops import ring_attention as ra

            q, k, v = (
                np.asarray(
                    rng.standard_normal((1, 2, 8 * n, 16)), np.float32
                )
                for _ in range(3)
            )
            ref = ra.ring_attention(q, k, v, mesh, data_axis=None, causal=True)
            if probe["op"] == "ring_flash":
                out = ra.ring_attention(
                    q, k, v, mesh, data_axis=None, causal=True, impl="flash"
                )
                np.testing.assert_allclose(
                    np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4
                )
            jax.block_until_ready(ref)
        elif probe["op"] == "pp_grad":
            from distribuuuu_tpu.parallel import pp

            feat = 8
            stage_fn = lambda p, x: jnp.tanh(x @ p["w"])  # noqa: E731
            stacked = pp.stack_stage_params(
                [
                    {"w": jnp.asarray(
                        rng.standard_normal((feat, feat)), jnp.float32
                    ) * 0.3}
                    for _ in range(n)
                ]
            )
            papply = pp.pipelined(
                stage_fn, mesh=mesh, num_microbatches=4, axis=axis
            )
            batch = jnp.asarray(rng.standard_normal((8, feat)), jnp.float32)
            grads = jax.jit(
                jax.grad(lambda sp: jnp.mean(papply(sp, batch) ** 2))
            )(stacked)
            jax.block_until_ready(grads)
        elif probe["op"] == "moe_dispatch":
            from distribuuuu_tpu.ops import moe

            params = moe.init_moe_params(jax.random.key(1), 8, 16, n)
            x = jnp.asarray(rng.standard_normal((4 * n, 8)), jnp.float32)
            out = jax.jit(
                lambda p, a: moe.moe_ffn_dispatch(
                    p, a, mesh=mesh, axis=axis, top_k=min(2, n),
                    capacity_factor=4.0,
                )
            )(params, x)
            jax.block_until_ready(out)
        else:
            raise ValueError(f"unknown op probe {probe['op']!r}")
        return {
            "name": f"{probe['op']}@{axis}{n}", "kind": "op", "ok": True,
            "seconds": round(time.perf_counter() - t0, 1),
        }
    except Exception as e:  # noqa: BLE001
        return {
            "name": f"{probe['op']}@{axis}{n}", "kind": "op", "ok": False,
            "error": f"{type(e).__name__}: {e}",
            "seconds": round(time.perf_counter() - t0, 1),
        }


def run_sweep(n_devices: int, out_path: str | None = None,
              full: bool = False, quiet: bool = False) -> dict:
    """Generate + execute the sweep; returns (and optionally writes) the
    MULTICHIP report dict."""
    import numpy as np

    rng = np.random.default_rng(0)
    cases = generate_cases(n_devices)
    probes = op_probes(n_devices)
    to_run = [
        c for c in cases
        if (full or c["tier"] == "core") and not c["degenerate_zero"]
    ]
    results = []
    for probe in probes:
        r = run_op_probe(probe, rng)
        results.append(r)
        if not quiet:
            print(f"  {'ok ' if r['ok'] else 'FAIL'} {r['name']:<40} "
                  f"{r['seconds']:6.1f}s", flush=True)
    for case in to_run:
        r = run_trainer_case(case, rng)
        results.append(r)
        if not quiet:
            detail = f"loss {r.get('loss')}" if r["ok"] else r.get("error", "")
            print(f"  {'ok ' if r['ok'] else 'FAIL'} {r['name']:<40} "
                  f"{r['seconds']:6.1f}s  {detail}", flush=True)
    report = {
        "n_devices": n_devices,
        "generated": [
            {k: c[k] for k in
             ("name", "class", "arch", "axes", "zero", "stanza", "tier")}
            for c in cases
        ],
        "executed": results,
        "n_generated": len(cases),
        "n_executed": len(results),
        "all_ok": all(r["ok"] for r in results),
    }
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        if not quiet:
            print(f"wrote {out_path} ({len(cases)} generated, "
                  f"{len(results)} executed, all_ok={report['all_ok']})")
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="MULTICHIP_r06.json")
    ap.add_argument("--full", action="store_true",
                    help="execute every generated class, not just core")
    ap.add_argument("--list", action="store_true",
                    help="print the generated case list and exit")
    args = ap.parse_args()

    import jax

    n = len(jax.devices())
    if args.list:
        for c in generate_cases(n):
            print(f"  {c['tier']:<8} {c['name']:<40} extras={c['extras']}")
        return
    report = run_sweep(n, out_path=args.out, full=args.full)
    raise SystemExit(0 if report["all_ok"] else 1)


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    main()
