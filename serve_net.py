"""Serve a classification model online (the sibling of train_net.py /
test_net.py; no reference analogue — the reference stops at offline eval).

Loads any zoo arch from an orbax checkpoint or torch pickle
(``MODEL.WEIGHTS``) or the pretrained URL zoo (``MODEL.PRETRAINED``),
applies the val transform pipeline to incoming images, and serves
predictions through the dynamic micro-batching engine
(distribuuuu_tpu/serve/) over a length-prefixed socket. SIGTERM drains
gracefully: stop accepting, finish every in-flight request, exit.

``--fleet N`` runs an N-replica serving FLEET instead of one engine
(distribuuuu_tpu/serve/fleet/): this process becomes the router on
``SERVE.HOST:PORT`` (least-loaded dispatch, idempotent retry, verbatim
backpressure passthrough) and spawns N replicas — each a plain
``serve_net.py`` on an ephemeral port — warm-up gated, health-checked,
and autoscaled against the ``SERVE.FLEET`` policy. SIGTERM drains the
whole fleet: stop accepting, drain every replica, exit.

Usage:
    # socket service (SERVE.* config node controls batching/port):
    python serve_net.py --cfg config/resnet50.yaml MODEL.WEIGHTS path/to/ckpt

    # an autoscaling 2..4-replica fleet behind one router port
    # (--fleet before the KEY VALUE overrides — those are greedy):
    python serve_net.py --cfg config/resnet50.yaml --fleet 2 \\
        MODEL.WEIGHTS path/to/ckpt SERVE.FLEET.MAX_REPLICAS 4

    # one-shot batch mode (tests/CI): val-transformed .npy in, logits out
    python serve_net.py --cfg config/resnet50.yaml \\
        --batch-input imgs.npy --batch-output logits.npy
"""

import argparse
import os
import sys

import distribuuuu_tpu.config as config
from distribuuuu_tpu.config import cfg


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="Serve a classification model."
    )
    parser.add_argument(
        "--cfg", dest="cfg_file", required=True, type=str,
        help="Config file location",
    )
    parser.add_argument(
        "--fleet", type=int, default=0, metavar="N",
        help="run an N-replica fleet (router + pool + autoscaler) instead "
             "of a single engine; 0 = single-replica mode",
    )
    parser.add_argument(
        "--batch-input", default=None,
        help="one-shot batch mode: .npy of val-transformed images "
             "('-' = stdin) instead of the socket server",
    )
    parser.add_argument(
        "--batch-output", default="-",
        help="batch-mode logits .npy destination ('-' = stdout)",
    )
    parser.add_argument(
        "opts", help="See distribuuuu_tpu/config.py for all options",
        default=None, nargs=argparse.REMAINDER,
    )
    args = parser.parse_args(sys.argv[1:] if argv is None else argv)
    config.merge_from_file(args.cfg_file)
    cfg.merge_from_list(args.opts)
    cfg.freeze()
    from distribuuuu_tpu import models  # an import; no backend is touched

    refusal = models.traits(cfg.MODEL.ARCH).serve_refusal
    if refusal:
        raise SystemExit(f"serve_net: {cfg.MODEL.ARCH!r} {refusal}")

    if args.fleet:
        return run_fleet(args.fleet)

    from distribuuuu_tpu import telemetry, trainer
    from distribuuuu_tpu.serve import admission, engine_from_cfg, protocol
    from distribuuuu_tpu.utils.jsonlog import setup_metrics_log
    from distribuuuu_tpu.utils.logger import get_logger, setup_logger

    setup_logger()
    logger = get_logger()
    # per-rank telemetry (TELEMETRY node): a standalone replica is rank 0;
    # a fleet replica gets its rank from the pool (DTPU_REPLICA_RANK), so
    # N replicas sharing OUT_DIR write N distinct telemetry sinks — bucket
    # AOT compiles land as kind="compile" records per replica
    telemetry.setup_from_cfg(
        cfg, rank=int(os.environ.get("DTPU_REPLICA_RANK", "0"))
    )
    # persistent compilation cache (asyncplane/compile_cache.py): a
    # restarted or replacement replica deserializes its AOT bucket
    # executables from disk instead of paying the warm-up compile storm
    # again. Placed after platform selection — it reads the backend.
    from distribuuuu_tpu.asyncplane import compile_cache
    from distribuuuu_tpu.parallel import mesh as mesh_lib

    mesh_lib.apply_platform(cfg.DEVICE.PLATFORM)
    compile_cache.setup_from_cfg(cfg)
    if cfg.MODEL.ARCH.startswith("gpt"):
        # the LM generation plane (lm/service.py): KV-cache continuous
        # batching behind the SAME socket/stats/fleet protocol; generate
        # requests arrive as streaming ctrl frames
        from distribuuuu_tpu.lm import service as lm_service

        if args.batch_input is not None:
            raise SystemExit(
                "--batch-input is the image engine's one-shot mode; "
                "drive a gpt_* replica with generate ctrl frames "
                "(lm/service.generate_request) instead"
            )
        engine = lm_service.engine_from_cfg()
        logger.info(
            "generating with %s: %d tile executables compiled "
            "(decode tiles %s), %d slots, prompt<=%d, max_new=%d, %s",
            cfg.MODEL.ARCH, engine.n_compiles,
            sorted(engine._decode_exec), engine.n_slots,
            engine.prompt_len, engine.max_new, _device_line(engine),
        )
    else:
        engine = engine_from_cfg()
        logger.info(
            "serving %s: buckets %s compiled (%d shapes), max_wait %.1f ms, "
            "queue bound %d, %s",
            cfg.MODEL.ARCH, engine.buckets, engine.n_compiles,
            cfg.SERVE.MAX_WAIT_MS, cfg.SERVE.MAX_QUEUE, _device_line(engine),
        )
    engine.start()

    if args.batch_input is not None:
        n = protocol.run_batch(engine, args.batch_input, args.batch_output)
        engine.drain()
        logger.info("batch mode: served %d requests", n)
        return

    setup_metrics_log(cfg.OUT_DIR)  # serve metrics land in metrics.jsonl
    admission.install_drain()  # SIGTERM → graceful drain (preempt pattern)
    listener = protocol.open_listener(cfg.SERVE.HOST, cfg.SERVE.PORT)
    host, port = listener.getsockname()[:2]
    logger.info("listening on %s:%d (SIGTERM drains gracefully)", host, port)
    try:
        protocol.serve_forever(
            engine, listener, should_stop=admission.drain_requested,
            topk=trainer.effective_topk(),
        )
    except KeyboardInterrupt:
        listener.close()
        engine.drain()
    logger.info("drained; exiting")


def _device_line(engine) -> str:
    """The backend and the device(s) this engine's weights live on — so a
    replica that fell back to the CPU, or two replicas on one chip, show
    in the start-up line."""
    from distribuuuu_tpu.parallel import mesh as mesh_lib
    from distribuuuu_tpu.serve import protocol

    return (
        f"{mesh_lib.describe_devices()}, engine on "
        f"{protocol.engine_device(engine)}"
    )


def run_fleet(n: int):
    """The ``--fleet N`` entrypoint: this process is the router; replicas
    are child ``serve_net.py`` processes spawned from a dump of the merged
    config (so every CLI override reaches them), each with its own
    telemetry rank. SIGTERM drains the fleet end to end."""
    from distribuuuu_tpu import telemetry
    from distribuuuu_tpu.serve import admission, protocol
    from distribuuuu_tpu.serve.fleet import FleetService
    from distribuuuu_tpu.utils.jsonlog import setup_metrics_log
    from distribuuuu_tpu.utils.logger import get_logger, setup_logger

    # the router never initializes a jax backend: a process that has
    # touched jax holds every chip it can see, and the chips are the
    # replicas'. (setup_logger would ask jax for the process index.)
    setup_logger(rank=0)
    logger = get_logger()
    telemetry.setup_from_cfg(cfg, rank=0)  # replicas take ranks 1..N
    setup_metrics_log(cfg.OUT_DIR)
    fleet_dir = os.path.join(cfg.OUT_DIR, "fleet")
    os.makedirs(fleet_dir, exist_ok=True)
    cfg_path = os.path.join(fleet_dir, "replica_cfg.yaml")
    with open(cfg_path, "w") as f:
        f.write(cfg.dump())

    svc = FleetService(cfg, n, cfg_path=cfg_path)
    logger.info(
        "fleet: spawning %d replica(s) of %s (budget %d..%d, autoscale %s)",
        n, cfg.MODEL.ARCH, cfg.SERVE.FLEET.MIN_REPLICAS,
        cfg.SERVE.FLEET.MAX_REPLICAS, cfg.SERVE.FLEET.AUTOSCALE,
    )
    svc.start(wait=True)
    routable = svc.router.n_routable()
    if not routable:
        svc.shutdown()
        raise RuntimeError(
            "fleet: no replica survived warm-up — see "
            f"{fleet_dir}/replica*.log"
        )
    admission.install_drain()  # SIGTERM → drain the whole fleet
    listener = protocol.open_listener(cfg.SERVE.HOST, cfg.SERVE.PORT)
    host, port = listener.getsockname()[:2]
    logger.info(
        "fleet: router listening on %s:%d over %d routable replica(s) "
        "(SIGTERM drains gracefully)", host, port, routable,
    )
    try:
        svc.serve(listener, should_stop=admission.drain_requested)
    except KeyboardInterrupt:
        listener.close()
    svc.shutdown()
    logger.info("fleet drained; exiting")


if __name__ == "__main__":
    main()
