"""Collective micro-benchmark over the live mesh (the nccl-tests analogue).

Sweeps buffer sizes through the collectives the framework actually uses —
psum (gradient/metric allreduce), all_gather, ppermute (ring shifts),
reduce_scatter — over the ``data`` axis of the current device topology, and
reports per-size latency plus algorithm bandwidth the way NCCL's
``all_reduce_perf`` does. XLA compiles each collective exactly as it would
inside a train step, so the numbers reflect the real ICI/DCN path (or the
host-interconnect on a forced CPU mesh).

A second mode (``--zero-ab``, ISSUE 15) A/Bs the ZeRO collective
SCHEDULE instead of raw collective latency: per ZeRO stage (1/3, plus
the PP×ZeRO-3 composition) it lowers the REAL train step through the
partition layer under each scheduling arm — gather-once + overlap
(the default), gather-once with overlap barriers (``ZERO.OVERLAP``
False — the synchronous control), and the legacy per-use schedule
(``ZERO.GATHER_AHEAD=0``) — then records the compiled all-gather census
(the schedule, from analysis.hlo — CPU-provable), measured step wall
time, and max |param diff| vs the default arm after N steps (the
bit-identity half of the A/B). Results land in a ``zero_overlap``
section (``--json-out BENCH_r10.json``) indexed by bench_history as
``zero_overlap_*`` series.

Usage:
    python tools/collective_bench.py [--min-mb 0.001] [--max-mb 64] [--iters 20]
    # simulated topology:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/collective_bench.py --max-mb 4
    # ZeRO schedule A/B:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/collective_bench.py --zero-ab --json-out BENCH_r10.json

For the native (C-API-level) equivalent that talks to the TPU runtime
directly, see native/collective_bench.cc.
"""

from __future__ import annotations

import argparse
import time

import _path  # noqa: F401  — repo root onto sys.path for the package import
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_ops(mesh, n):
    """name → shard_map'd collective taking/returning a sharded buffer."""

    def wrap(fn, out_specs=P("data")):
        return jax.jit(
            jax.shard_map(fn, mesh=mesh, in_specs=P("data"),
                          out_specs=out_specs, check_vma=False)
        )

    # Each op is written shape-preserving so iterations chain (out feeds in),
    # which keeps the timed loop free of host dispatch gaps.

    def ag_slice(x):  # full all_gather cost; keep own shard to preserve shape
        g = jax.lax.all_gather(x, "data", tiled=True)
        i = jax.lax.axis_index("data")
        return jax.lax.dynamic_slice_in_dim(g, i * x.shape[0], x.shape[0])

    def rs_ag(x):  # reduce_scatter + all_gather (the allreduce decomposition)
        s = jax.lax.psum_scatter(x, "data", tiled=True) / n
        return jax.lax.all_gather(s, "data", tiled=True)

    return {
        # allreduce: every chip ends with the sum (the DDP-gradient op)
        "psum": wrap(lambda x: jax.lax.psum(x, "data") / n),
        # allgather: every chip ends with the concatenation
        "all_gather": wrap(ag_slice),
        # ring shift: neighbor exchange (the ring-attention hop)
        "ppermute": wrap(
            lambda x: jax.lax.ppermute(
                x, "data", [(i, (i + 1) % n) for i in range(n)]
            )
        ),
        # reduce_scatter then all_gather (ZeRO-style allreduce split)
        "rs+ag": wrap(rs_ag),
    }


def bench_one(fn, buf, iters: int) -> float:
    out = fn(buf)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(out)  # chain so iterations cannot overlap-collapse
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


# ---------------------------------------------------- ZeRO schedule A/B

# (name, stanza overrides, arch) — every committed-waiver topology plus
# the stage-1 reference
ZERO_AB_CASES = (
    ("dp8_zero1", {"DATA": -1, "ZERO": 1}, "resnet18"),
    ("dp8_zero3", {"DATA": -1, "ZERO": 3}, "resnet18"),
    ("dp2_pp4_zero3", {"DATA": 2, "PIPE": 4, "ZERO": 3}, "vit_tiny"),
)

# arm name -> (ZERO.OVERLAP, ZERO.GATHER_AHEAD)
ZERO_AB_ARMS = {
    "overlap_on": (True, -1),   # gather-once, collectives free to hide
    "overlap_off": (False, -1),  # gather-once, barrier-serialized control
    "per_use": (True, 0),        # the legacy schedule (the r15 baseline)
}


def _zero_ab_case(name: str, stanza: dict, arch: str, steps: int) -> dict:
    """One topology through every scheduling arm: census + step wall +
    params-vs-default-arm divergence."""
    import numpy as np

    import distribuuuu_tpu.config as config
    from distribuuuu_tpu.analysis import hlo
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.parallel import (
        mesh as mesh_lib, sharding as sharding_lib,
    )
    from distribuuuu_tpu.parallel.partition import lowering
    from distribuuuu_tpu.utils.optim import construct_optimizer

    rng = np.random.default_rng(0)
    im = 16
    # ONE host batch for the whole case — every arm trains the same data
    # (a per-arm draw would turn the divergence column into noise)
    host_batch = {
        "image": rng.standard_normal((16, im, im, 3)).astype(np.float32),
        "label": rng.integers(0, 8, (16,)).astype(np.int32),
    }
    out = {"arch": arch, "stanza": stanza, "arms": {}}
    ref_params = None
    for arm, (overlap, ahead) in ZERO_AB_ARMS.items():
        config.reset_cfg()
        cfg.MODEL.ARCH = arch
        cfg.MODEL.NUM_CLASSES = 8
        cfg.DEVICE.COMPUTE_DTYPE = "float32"
        cfg.OPTIM.BASE_LR = 0.01
        for k, v in stanza.items():
            cfg.MESH[k] = v
        cfg.ZERO.OVERLAP = overlap
        cfg.ZERO.GATHER_AHEAD = ahead
        if stanza.get("PIPE", 1) > 1:
            cfg.MESH.MICROBATCH = 4
        topo = trainer.check_trainer_mesh()
        mesh = mesh_lib.mesh_from_cfg(cfg)
        model = trainer.build_model_from_cfg(topo)
        low = lowering.lower(
            model, construct_optimizer(), 2,
            mesh=mesh, topology=topo, im_size=im,
        )
        # the compiled schedule (the census referee, CPU-provable)
        state_sds, batch_sds = low.abstract_args()
        compiled = low.train_step.lower(state_sds, batch_sds).compile()
        census = hlo.collective_census(compiled.as_text(), mesh)
        gathers = sum(
            1 for op in census
            if op["kind"] == "all-gather" and op["axes"] == ("data",)
        )
        total = len(census)
        # measured steps (CPU wall — the schedule is the provable part
        # here, wall-clock overlap needs real async hardware)
        batch = sharding_lib.shard_batch(mesh, host_batch)
        state = low.init_state(jax.random.key(0), im)
        state, _ = low.train_step(state, batch)  # compile+warm
        jax.block_until_ready(state.params)
        state = low.init_state(jax.random.key(0), im)
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = low.train_step(state, batch)
        jax.block_until_ready(state.params)
        wall = (time.perf_counter() - t0) / steps
        # divergence after ONE step from identical init: the same-math
        # column. overlap_off vs on is pinned BIT-identical on the toy
        # tier-1 configs; across full archs a barrier can shift XLA
        # fusion boundaries (ulp-scale FMA-contraction drift — the same
        # class the kernel tier pins at 5e-6); per_use changes the
        # PROGRAM partitioning, so float reduction order legitimately
        # differs. Multi-step trajectories amplify either through BN
        # chaotically, which is why this measures one step.
        state1 = low.init_state(jax.random.key(0), im)
        state1, _ = low.train_step(state1, batch)
        params1 = jax.device_get(state1.params)
        if arm == "overlap_on":
            ref_params = params1
            diff = 0.0
        else:
            diff = max(
                float(np.abs(np.asarray(a, np.float32)
                             - np.asarray(b, np.float32)).max())
                for a, b in zip(
                    jax.tree_util.tree_leaves(ref_params),
                    jax.tree_util.tree_leaves(params1),
                )
            )
        out["arms"][arm] = {
            "data_all_gathers": gathers,
            "total_collectives": total,
            "step_ms": round(wall * 1e3, 2),
            "max_param_diff_vs_overlap_on_1step": diff,
        }
        print(
            f"  {name:<16}{arm:<13} AG@data {gathers:>4}  "
            f"collectives {total:>4}  step {wall * 1e3:8.1f} ms  "
            f"|Δparam@1step| {diff:.2e}"
        )
    config.reset_cfg()
    return out


def zero_ab(steps: int, json_out: str | None) -> None:
    import json

    devices = jax.devices()
    print(
        f"# ZeRO schedule A/B on {len(devices)} × "
        f"{devices[0].device_kind} (platform {devices[0].platform})"
    )
    if len(devices) < 8:
        raise SystemExit(
            f"--zero-ab wants the 8-device mesh the committed census uses "
            f"(have {len(devices)}): run under JAX_PLATFORMS=cpu "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )
    cases = {}
    for name, stanza, arch in ZERO_AB_CASES:
        cases[name] = _zero_ab_case(name, stanza, arch, steps)
    doc = {
        "bench": "zero_overlap_ab",
        "note": (
            "CPU container: the all-gather census and the bit-identity "
            "column are the provable halves of the A/B (the schedule); "
            "step_ms on a time-shared 1-core host does not measure "
            "latency hiding — wall-clock overlap needs TPU hardware "
            "(PERF.md 'Hiding ZeRO collectives')."
        ),
        "zero_overlap": {
            "devices": len(devices),
            "platform": devices[0].platform,
            "steps": steps,
            "cases": cases,
        },
    }
    if json_out:
        with open(json_out, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"# -> {json_out}")
    print("# done")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--min-mb", type=float, default=0.001)
    ap.add_argument("--max-mb", type=float, default=64.0)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--ops", default="", help="comma-separated subset to run")
    ap.add_argument("--zero-ab", action="store_true",
                    help="A/B the ZeRO collective schedule instead "
                         "(gather-once overlap on/off vs per-use)")
    ap.add_argument("--steps", type=int, default=3,
                    help="--zero-ab: measured steps per arm")
    ap.add_argument("--json-out", default=None, metavar="OUT.json",
                    help="--zero-ab: write the A/B matrix here")
    args = ap.parse_args()
    if args.zero_ab:
        zero_ab(args.steps, args.json_out)
        return

    devices = jax.devices()
    n = len(devices)
    mesh = Mesh(np.asarray(devices), ("data",))
    shard = NamedSharding(mesh, P("data"))
    ops = make_ops(mesh, n)
    if args.ops:
        want = set(args.ops.split(","))
        unknown = want - set(ops)
        if unknown:
            ap.error(f"unknown ops {sorted(unknown)}; have {sorted(ops)}")
        ops = {k: v for k, v in ops.items() if k in want}
    print(
        f"# devices: {n} × {devices[0].device_kind}  "
        f"(platform {devices[0].platform})"
    )
    print(f"# {'op':<15}{'size':>12}{'time/iter':>14}{'algbw GB/s':>12}")

    size = args.min_mb * 2**20
    while size <= args.max_mb * 2**20:
        # f32 elements, divisible by n² (reduce_scatter shards the shard)
        el = max(n * n, int(size // 4) // (n * n) * (n * n))
        host = np.ones((el,), np.float32)
        buf = jax.device_put(host, shard)
        for name, fn in ops.items():
            dt = bench_one(fn, buf, args.iters)
            # algorithm bandwidth, nccl-tests convention: full buffer bytes
            # divided by time
            algbw = el * 4 / dt / 1e9
            label = f"{el * 4 / 2**20:.3f}MB"
            print(f"  {name:<15}{label:>12}{dt * 1e6:>12.1f}us{algbw:>12.2f}")
        size *= 8

    print("# done")


if __name__ == "__main__":
    main()
