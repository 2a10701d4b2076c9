"""Load generator for the serving engine: the latency/throughput frontier.

Drives the in-process Engine (no socket — this measures the serving hot
path: batching + dispatch + device) two ways:

* **closed-loop** — C clients submit back-to-back; reports the
  throughput/latency point each concurrency sustains (the "how fast can
  one replica go" curve).
* **open-loop** — Poisson arrivals at an offered rate, the
  traffic-shaped view (2011.03641's point: open-loop latency is what
  users see; closed-loop hides queueing). Requests beyond SERVE.MAX_QUEUE
  are rejected and counted, not retried — offered load means offered.

Both run twice: ``dynamic`` (the configured MAX_BATCH with bucketed
micro-batching) and ``batch1`` (MAX_BATCH=1 — the no-batching strawman a
naive port of test_net would serve). The dynamic/batch1 throughput gap at
equal offered load is the engine's reason to exist.

Offered rates default to calibration: measure batch-1 single-stream
latency L1, then offer ~0.7× and ~2.5× of that capacity (the second point
saturates batch1 while dynamic still has headroom). Writes one JSON
report (default ``BENCH_serve.json``).

Workload-regime note: batching harvests device parallelism a batch-1
forward leaves idle. On CPU a 224² conv net is compute-bound at batch 1
(XLA:CPU parallelizes one conv across all cores), so the default here is
the dispatch-bound tiny shape (resnet18 @16², where the CPU run shows
~2× dynamic/batch1 at saturation — BENCH_serve.json) — the same overhead
regime 2011.03641 measures on TPU at small batch. On a chip, bench the
real serving shape: ``--im-size 224 --num-classes 1000 --dtype bfloat16``.

``--fleet N`` benches the SERVING FLEET (serve/fleet/) instead of the
in-process engine: for every fleet size 1..N it spawns that many real
replica processes behind the router, drives the fleet to saturation
(closed-loop, then open-loop Poisson at 1.3x the measured capacity),
and reports throughput scaling vs replica count, per-replica occupancy
skew, and the fleet-wide steady-state recompile count (must be zero).
The ``fleet`` section is merged into the existing BENCH_serve.json.
Scaling caveat the report records: replica scaling needs CPU cores to
scale ONTO — on an M-core host expect ~min(N, M)x; a single-core
container (this repo's CPU proof environment) pins every replica to the
same core, so the honest expectation there is ~1.0x and the section
carries ``single_core_ceiling: true``.

    JAX_PLATFORMS=cpu python tools/serve_bench.py --duration 5
    JAX_PLATFORMS=cpu python tools/serve_bench.py --fleet 2 --duration 5
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time

import _path  # noqa: F401 — repo root onto sys.path for the package import
import numpy as np


def build_engine(args, max_batch: int):
    """Fresh engine for one mode (random init — latency does not care
    about weight values)."""
    import jax

    import distribuuuu_tpu.config as config
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.parallel import mesh as mesh_lib
    from distribuuuu_tpu.serve import Engine

    config.reset_cfg()
    cfg.MODEL.ARCH = args.arch
    cfg.MODEL.NUM_CLASSES = args.num_classes
    if args.arch.startswith("resnet"):
        cfg.MODEL.BN_GROUP = 8  # tiny-batch ghost BN: any divisor works
    cfg.TRAIN.IM_SIZE = args.im_size
    cfg.DEVICE.COMPUTE_DTYPE = args.dtype
    mesh = mesh_lib.build_mesh(data=1, model=1, seq=1, pipe=1,
                               devices=[jax.devices()[0]])
    model = trainer.build_model_from_cfg()
    state = trainer.create_train_state(
        model, jax.random.key(0), mesh, args.im_size
    )
    engine = Engine(
        model,
        {"params": state.params, "batch_stats": state.batch_stats},
        args.im_size,
        max_batch=max_batch,
        max_wait_ms=args.max_wait_ms,
        max_queue=args.max_queue,
        input_dtype=np.uint8,
    )
    return engine.start()


def make_requests(n: int, im_size: int, seed: int = 0) -> list[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, 256, (im_size, im_size, 3), dtype=np.uint8)
        for _ in range(n)
    ]


def _await_all(futs) -> int:
    done = 0
    for f in futs:
        f.result()
        done += 1
    return done


def closed_loop(engine, images, clients: int, duration_s: float) -> dict:
    """C threads, each submit→wait→repeat for the window."""
    from distribuuuu_tpu.serve import ServeMetrics

    engine.metrics = ServeMetrics()
    stop = time.perf_counter() + duration_s
    counts = [0] * clients

    def client(ci: int):
        i = ci
        while time.perf_counter() < stop:
            engine.submit(images[i % len(images)]).result()
            counts[ci] += 1
            i += clients

    threads = [
        threading.Thread(target=client, args=(ci,)) for ci in range(clients)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    snap = engine.metrics.snapshot()
    return {
        "clients": clients,
        "completed": sum(counts),
        "throughput_rps": round(sum(counts) / elapsed, 2),
        "p50_ms": snap["p50_ms"],
        "p99_ms": snap["p99_ms"],
        "batch_occupancy": snap["batch_occupancy"],
    }


def open_loop(engine, images, offered_rps: float, duration_s: float,
              seed: int = 0) -> dict:
    """Poisson arrivals at ``offered_rps``; rejections counted, not
    retried (offered load is offered load)."""
    from distribuuuu_tpu.serve import QueueFullError, ServeMetrics

    engine.metrics = ServeMetrics()
    rng = np.random.default_rng(seed)
    futs = []
    rejected = 0
    t0 = time.perf_counter()
    next_t = t0
    i = 0
    while True:
        next_t += rng.exponential(1.0 / offered_rps)
        if next_t - t0 > duration_s:
            break
        delay = next_t - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        try:
            futs.append(engine.submit(images[i % len(images)]))
        except QueueFullError:
            rejected += 1
        i += 1
    completed = _await_all(futs)
    elapsed = time.perf_counter() - t0
    snap = engine.metrics.snapshot()
    return {
        "offered_rps": round(offered_rps, 1),
        "offered": i,
        "completed": completed,
        "rejected": rejected,
        "achieved_rps": round(completed / elapsed, 2),
        "p50_ms": snap["p50_ms"],
        "p99_ms": snap["p99_ms"],
        "batch_occupancy": snap["batch_occupancy"],
    }


def calibrate_batch1_latency(engine, images, n: int = 30) -> float:
    """Median single-stream request latency (seconds), warmed."""
    for img in images[:5]:
        engine.submit(img).result()
    lats = []
    for k in range(n):
        t0 = time.perf_counter()
        engine.submit(images[k % len(images)]).result()
        lats.append(time.perf_counter() - t0)
    return float(np.median(lats))


# -- fleet mode --------------------------------------------------------------

def _fleet_cfg_yaml(args, work: str) -> str:
    """Dump the bench workload as a replica config (float32 pre-transformed
    input path: DATA.DEVICE_NORMALIZE off keeps the replica's per-request
    host work at 'np.load' — the load-gen measures the fleet, not PIL)."""
    import distribuuuu_tpu.config as config
    from distribuuuu_tpu.config import cfg

    config.reset_cfg()
    cfg.MODEL.ARCH = args.arch
    cfg.MODEL.NUM_CLASSES = args.num_classes
    if args.arch.startswith("resnet"):
        cfg.MODEL.BN_GROUP = 8
    cfg.TRAIN.IM_SIZE = args.im_size
    cfg.TEST.IM_SIZE = args.im_size
    cfg.DEVICE.COMPUTE_DTYPE = args.dtype
    cfg.DEVICE.PLATFORM = "cpu" if os.environ.get(
        "JAX_PLATFORMS", ""
    ).startswith("cpu") else "auto"
    cfg.DATA.DEVICE_NORMALIZE = False
    cfg.SERVE.MAX_BATCH = args.max_batch
    cfg.SERVE.MAX_WAIT_MS = args.max_wait_ms
    cfg.SERVE.MAX_QUEUE = args.max_queue
    cfg.SERVE.FLEET.AUTOSCALE = False  # fixed size per measured point
    cfg.SERVE.FLEET.MAX_REPLICAS = max(args.fleet, 2)
    cfg.SERVE.FLEET.HEALTH_PERIOD_S = 1.0
    cfg.OUT_DIR = work
    path = os.path.join(work, "fleet_bench_cfg.yaml")
    with open(path, "w") as f:
        f.write(cfg.dump())
    return path


def _float_payloads(n: int, im_size: int, seed: int = 0) -> list[bytes]:
    """Pre-transformed float32 request payloads (the protocol's direct
    engine-input path)."""
    import io

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        buf = io.BytesIO()
        np.save(buf, rng.standard_normal(
            (im_size, im_size, 3)).astype(np.float32))
        out.append(buf.getvalue())
    return out


def _fleet_closed_loop(router, payloads, clients: int, duration_s: float):
    """C threads submit back-to-back through the router (its in-process
    dispatch — the same path the socket accept loop calls); busy
    rejections back off and retry, so completions measure capacity."""
    stop = time.perf_counter() + duration_s
    counts = [0] * clients
    rejected = [0] * clients

    def client(ci: int):
        i = ci
        while time.perf_counter() < stop:
            resp = router.dispatch(payloads[i % len(payloads)])
            if resp.startswith(b'{"error"'):
                rejected[ci] += 1
                time.sleep(0.005)
                continue
            counts[ci] += 1
            i += clients

    threads = [
        threading.Thread(target=client, args=(ci,)) for ci in range(clients)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    elapsed = time.perf_counter() - t0
    return sum(counts) / elapsed, sum(rejected)


def _fleet_open_loop(router, payloads, offered_rps: float, duration_s: float,
                     workers: int = 64, seed: int = 0):
    """Poisson arrivals at ``offered_rps`` pushed through a worker pool;
    fleet-wide queue_full rejections are counted, not retried (offered
    load means offered — the backpressure passthrough is the result)."""
    import queue

    rng = np.random.default_rng(seed)
    q: queue.Queue = queue.Queue()
    done = {"ok": 0, "rejected": 0}
    lock = threading.Lock()

    def worker():
        while True:
            payload = q.get()
            if payload is None:
                return
            resp = router.dispatch(payload)
            with lock:
                if resp.startswith(b'{"error"'):
                    done["rejected"] += 1
                else:
                    done["ok"] += 1

    pool = [threading.Thread(target=worker, daemon=True)
            for _ in range(workers)]
    for t in pool:
        t.start()
    t0 = time.perf_counter()
    next_t, offered = t0, 0
    while True:
        next_t += rng.exponential(1.0 / offered_rps)
        if next_t - t0 > duration_s:
            break
        delay = next_t - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        q.put(payloads[offered % len(payloads)])
        offered += 1
    for _ in pool:
        q.put(None)
    for t in pool:
        t.join()
    elapsed = time.perf_counter() - t0
    return {
        "offered_rps": round(offered_rps, 1),
        "offered": offered,
        "completed": done["ok"],
        "rejected": done["rejected"],
        "achieved_rps": round(done["ok"] / elapsed, 2),
    }


def run_fleet_bench(args) -> dict:
    """Saturation throughput vs replica count through the real fleet:
    router + N replica processes per point, per-replica occupancy skew,
    zero-steady-state-recompile assertion from each replica's
    ``jit.compiles`` baseline."""
    import tempfile

    from distribuuuu_tpu.serve.fleet import FleetService
    from distribuuuu_tpu.serve.fleet.pool import probe_stats

    work = tempfile.mkdtemp(prefix="fleet_bench_")
    cfg_path = _fleet_cfg_yaml(args, work)
    from distribuuuu_tpu.config import cfg

    payloads = _float_payloads(32, args.im_size)
    points = []
    for n in range(1, args.fleet + 1):
        t0 = time.perf_counter()
        svc = FleetService(cfg, n, cfg_path=cfg_path, out_dir=work)
        svc.start(wait=True)
        try:
            routable = svc.router.n_routable()
            if routable != n:
                raise RuntimeError(
                    f"fleet of {n}: only {routable} replicas warmed — see "
                    f"{work}/fleet/replica*.log"
                )
            baselines = {
                r.id: int(r.stats.get("jit_compiles", 0))
                for r in svc.router.replicas()
            }
            print(f"# fleet of {n}: warm in {time.perf_counter() - t0:.1f}s",
                  flush=True)
            # 2x-oversubscribed concurrency per replica: saturation means
            # a standing queue at every replica, so the batcher always
            # flushes on full. A fixed client count would halve
            # per-replica occupancy at every doubling; exactly MAX_BATCH
            # per replica leaves occupancy hostage to dispatch scatter
            # (partial batches waiting out MAX_WAIT_MS) — both misread
            # amortization loss as a scaling limit.
            clients = 2 * args.max_batch * n
            _fleet_closed_loop(  # warm the sockets + EWMAs
                svc.router, payloads, clients, min(1.0, args.duration / 4)
            )
            sat_rps, sat_rej = _fleet_closed_loop(
                svc.router, payloads, clients, args.duration
            )
            open_pt = _fleet_open_loop(
                svc.router, payloads, 1.3 * sat_rps, args.duration
            )
            # one health pass refreshes stats; then read the recompile count
            svc.pool.health_check()
            recompiles = sum(
                int(r.stats.get("jit_compiles", 0)) - baselines[r.id]
                for r in svc.router.replicas() if r.id in baselines
            )
            snap = svc.router.stats()
            per_rep = [p["requests"] for p in snap["per_replica"]]
            skew = (max(per_rep) / max(min(per_rep), 1)) if per_rep else 0.0
            point = {
                "replicas": n,
                "clients": clients,
                "saturation_rps": round(sat_rps, 2),
                "closed_loop_rejected": sat_rej,
                "open_loop": open_pt,
                "p50_ms": snap["p50_ms"],
                "p99_ms": snap["p99_ms"],
                "per_replica_requests": per_rep,
                "occupancy_skew": round(skew, 3),
                "rerouted": snap["rerouted"],
                "steady_state_recompiles": recompiles,
            }
            points.append(point)
            print(
                f"  fleet {n}: saturation {sat_rps:8.1f} rps  "
                f"p50 {snap['p50_ms']:7.1f} ms  p99 {snap['p99_ms']:7.1f} ms  "
                f"skew {skew:.2f}  recompiles {recompiles}",
                flush=True,
            )
        finally:
            svc.shutdown()

    by_n = {p["replicas"]: p["saturation_rps"] for p in points}
    cores = os.cpu_count() or 1
    fleet = {
        "metric": "fleet_saturation_scaling_vs_replica_count",
        "arch": args.arch,
        "im_size": args.im_size,
        "max_batch": args.max_batch,
        # NOTE on the batching window at fleet scale: when replicas
        # outnumber cores, scheduler latency delays closed-loop client
        # resubmits past a tight MAX_WAIT_MS and partial batches destroy
        # amortization (measured: 5 ms -> occupancy 0.90, 30 ms -> 1.0 on
        # the 1-core proof box). Bench with a window >= a batch service
        # time for honest saturation numbers.
        "max_wait_ms": args.max_wait_ms,
        "duration_s": args.duration,
        "cpu_count": cores,
        "sizes": sorted(by_n),
        "points": points,
        "steady_state_recompiles": sum(
            p["steady_state_recompiles"] for p in points
        ),
    }
    if 1 in by_n and 2 in by_n:
        fleet["fleet2_over_fleet1"] = round(by_n[2] / max(by_n[1], 1e-9), 3)
        # replica scaling needs cores to scale onto: on one core every
        # replica time-shares the same CPU, so ~1.0x is the physical
        # ceiling (the ≥1.7x CPU proof requires a ≥2-core host)
        fleet["single_core_ceiling"] = cores < 2
        fleet["scaling_target_met"] = (
            fleet["fleet2_over_fleet1"] >= 1.7 if cores >= 2 else None
        )
        print(
            f"# fleet-of-2 / fleet-of-1 saturation: "
            f"{by_n[2]:.1f}/{by_n[1]:.1f} = {fleet['fleet2_over_fleet1']:.2f}x"
            f" ({cores} core(s))",
            flush=True,
        )
    return fleet


def merge_fleet_section(out_path: str, fleet: dict) -> None:
    """Write the ``fleet`` section into BENCH_serve.json, preserving the
    single-replica frontier results already there."""
    results = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            results = json.load(f)
    results["fleet"] = fleet
    with open(out_path, "w") as f:
        json.dump(results, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="resnet18")
    ap.add_argument("--im-size", type=int, default=16)
    ap.add_argument("--num-classes", type=int, default=10)
    ap.add_argument("--dtype", default="float32",
                    help="DEVICE.COMPUTE_DTYPE for the served model")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-wait-ms", type=float, default=5.0)
    ap.add_argument("--max-queue", type=int, default=64)
    ap.add_argument("--duration", type=float, default=5.0,
                    help="seconds per load point")
    ap.add_argument("--loads", default="",
                    help="comma-separated offered req/s (default: "
                         "calibrated 0.7× and 2.5× batch-1 capacity)")
    ap.add_argument("--clients", default="1,8",
                    help="closed-loop concurrency levels")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="bench the serving fleet at sizes 1..N (real "
                         "replica processes behind the router) instead of "
                         "the in-process engine; merges a 'fleet' section "
                         "into --out")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()

    if args.fleet:
        fleet = run_fleet_bench(args)
        merge_fleet_section(args.out, fleet)
        print(json.dumps({k: v for k, v in fleet.items() if k != "points"}))
        print(f"# fleet section merged into {args.out}", flush=True)
        return

    import jax

    from distribuuuu_tpu.config import cfg

    from distribuuuu_tpu.asyncplane import compile_cache

    compile_cache.setup_from_cfg(cfg)  # on the chip: warm across processes
    images = make_requests(64, args.im_size)
    results = {
        "metric": "serve_latency_throughput_frontier",
        "arch": args.arch,
        "im_size": args.im_size,
        "num_classes": args.num_classes,
        "compute_dtype": args.dtype,
        "device_kind": jax.devices()[0].device_kind,
        "max_batch": args.max_batch,
        "max_wait_ms": args.max_wait_ms,
        "max_queue": args.max_queue,
        "duration_s": args.duration,
        "open_loop": [],
        "closed_loop": [],
    }

    engines = {}
    for mode, mb in (("dynamic", args.max_batch), ("batch1", 1)):
        t0 = time.perf_counter()
        engines[mode] = build_engine(args, mb)
        print(f"# {mode}: buckets {engines[mode].buckets} compiled in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
    results["buckets"] = engines["dynamic"].buckets

    l1 = calibrate_batch1_latency(engines["batch1"], images)
    cap1 = 1.0 / l1
    results["batch1_single_stream_ms"] = round(l1 * 1e3, 3)
    print(f"# batch-1 single-stream latency {l1 * 1e3:.2f} ms "
          f"(~{cap1:.0f} req/s capacity)", flush=True)
    loads = (
        [float(x) for x in args.loads.split(",") if x]
        if args.loads
        else [round(0.7 * cap1, 1), round(2.5 * cap1, 1)]
    )

    for load in loads:
        for mode in ("dynamic", "batch1"):
            r = open_loop(engines[mode], images, load, args.duration)
            r["mode"] = mode
            results["open_loop"].append(r)
            print(f"  open  {mode:<8} offered {load:8.1f} rps -> "
                  f"{r['achieved_rps']:8.1f} rps  p50 {r['p50_ms']:7.1f} ms  "
                  f"p99 {r['p99_ms']:7.1f} ms  rejected {r['rejected']}",
                  flush=True)
    for clients in [int(c) for c in args.clients.split(",") if c]:
        for mode in ("dynamic", "batch1"):
            r = closed_loop(engines[mode], images, clients, args.duration)
            r["mode"] = mode
            results["closed_loop"].append(r)
            print(f"  closed {mode:<8} {clients:3d} clients -> "
                  f"{r['throughput_rps']:8.1f} rps  p50 {r['p50_ms']:7.1f} ms  "
                  f"p99 {r['p99_ms']:7.1f} ms", flush=True)

    for engine in engines.values():
        engine.drain()

    # the headline: dynamic vs batch1 at the highest offered load
    top = max(loads)
    by = {
        (r["mode"], r["offered_rps"]): r["achieved_rps"]
        for r in results["open_loop"]
    }
    if ("dynamic", round(top, 1)) in by and ("batch1", round(top, 1)) in by:
        d, b = by[("dynamic", round(top, 1))], by[("batch1", round(top, 1))]
        results["dynamic_vs_batch1_at_top_load"] = round(d / b, 3) if b else None
        print(f"# dynamic/batch1 throughput at {top:.0f} rps offered: "
              f"{d:.1f}/{b:.1f} = {d / max(b, 1e-9):.2f}x", flush=True)

    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps({k: v for k, v in results.items()
                      if k not in ("open_loop", "closed_loop")}))
    print(f"# full report -> {args.out}", flush=True)


if __name__ == "__main__":
    main()
