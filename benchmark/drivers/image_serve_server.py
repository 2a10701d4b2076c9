"""The server child of driver ``image_serve``: what ``serve_net.py`` runs.

Builds the engine from the program's config (``engine_from_cfg``), listens on
a loopback port (``open_listener``) and serves with ``serve_forever`` — the
socket, the framing, the JPEG decode and the val transform are the program's.
This process holds the chip; the parent generates load and stays off jax.

The parent drives it over a pipe, one JSON object a line each way:
``ready`` (port, device facts) -> ``open`` (window opens: compile counter
marked) -> ``trace_start`` / ``trace_stop`` -> ``close`` (compiles since ``open``, memory
peak) -> ``reference`` (plain float32 logits for sampled payloads, remade here
from the seed) -> ``exit`` (drain and leave).
"""

import os
import sys

# fd 1 is the control pipe; everything else this process prints goes to fd 2
CONTROL = os.fdopen(os.dup(1), "w")
os.dup2(2, 1)
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)

import json  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

# The program surface this server stands on (PERF.md lists it).
import distribuuuu_tpu.config as program_config  # noqa: E402
from distribuuuu_tpu.asyncplane.compile_cache import setup_from_cfg  # noqa: E402
from distribuuuu_tpu.config import cfg  # noqa: E402
from distribuuuu_tpu.parallel.mesh import build_mesh  # noqa: E402
from distribuuuu_tpu.serve.engine import engine_from_cfg  # noqa: E402
from distribuuuu_tpu.serve.protocol import open_listener, serve_forever  # noqa: E402
from distribuuuu_tpu.trainer import (  # noqa: E402
    build_model_from_cfg,
    create_train_state,
)

from benchmark.harness import cli, payloads, profiler  # noqa: E402
from benchmark.harness.compiles import CompileCounter, delta  # noqa: E402
from benchmark.harness.discovery import Catalog  # noqa: E402


def say(**message) -> None:
    CONTROL.write(json.dumps(message) + "\n")
    CONTROL.flush()


def configure(run) -> dict:
    program, serve = run.section("program"), run.section("serve")
    program_config.reset_cfg()
    program_config.merge_from_file(os.path.join(run.root, program["cfg_file"]))
    overrides = {
        **program["overrides"],
        **serve["overrides"],
        "DEVICE.COMPUTE_DTYPE": serve["dtype"],
        "SERVE.HOST": "127.0.0.1",
        "SERVE.PORT": 0,
        "RNG_SEED": run.seed,
    }
    cfg.merge_from_list([str(x) for kv in overrides.items() for x in kv])
    return serve


def reference_logits(run, device, payload_ids: list) -> dict:
    """Plain float32 inference logits for the sampled payloads, on weights
    made as ``engine_from_cfg`` makes them (same initialiser, same seed) and
    on images decoded by the benchmark's own copy of the val transform."""
    reference = run.catalog.reference(run.cell.config["reference"])
    architecture = run.section("architecture")
    spec = run.traffic["payloads"]
    made = payloads.jpeg_payloads(run.seed, **spec)
    images = np.stack([
        payloads.decode_for_serving(made[i], cfg.TEST.IM_SIZE, cfg.TRAIN.IM_SIZE)
        for i in payload_ids
    ])
    state = create_train_state(
        build_model_from_cfg(), jax.random.key(cfg.RNG_SEED or 0),
        build_mesh(data=1, devices=[device]), cfg.TRAIN.IM_SIZE,
    )
    logits = jax.jit(
        lambda p, s, x: reference.logits(
            p, s, x, architecture=architecture, train=False
        )
    )(state.params, state.batch_stats, images)
    return {int(i): [float(v) for v in row]
            for i, row in zip(payload_ids, np.asarray(logits))}


def main() -> int:
    argv = sys.argv[1:]
    catalog = Catalog()
    run = cli.Run(
        catalog, catalog.cell(cli.parse(argv).workload), argv, time.perf_counter()
    )
    configure(run)
    counter = CompileCounter().install()
    device = jax.devices()[cfg.SERVE.DEVICE]
    setup_from_cfg(cfg)
    engine = engine_from_cfg().start()
    listener = open_listener(cfg.SERVE.HOST, cfg.SERVE.PORT)
    stop = threading.Event()
    server = threading.Thread(
        target=serve_forever, args=(engine, listener, stop.is_set),
        kwargs={"topk": min(5, cfg.MODEL.NUM_CLASSES)}, name="bench-serve",
    )
    server.start()
    say(event="ready", port=listener.getsockname()[1],
        platform=device.platform, kind=device.device_kind, count=1,
        buckets=engine.buckets, setup_compiles=counter.snapshot())

    at_open = tracing = window_span = None
    for line in sys.stdin:
        command = json.loads(line)
        op = command["op"]
        if op == "open":
            at_open = counter.snapshot()
            say(event="opened")
        elif op == "trace_start":
            tracing = profiler.capture(run.trace_dir)
            captured = tracing.__enter__()
            window_span = profiler.span("window")
            window_span.__enter__()
            say(event="tracing")
        elif op == "trace_stop":
            window_span.__exit__(None, None, None)
            tracing.__exit__(None, None, None)
            say(event="traced", path=captured["path"])
        elif op == "close":
            # before the reference runs: its float32 forward is not the
            # served path's memory
            memory = device.memory_stats() or {}
            say(event="closed",
                compiles_in_window=delta(counter.snapshot(), at_open)["lookups"],
                # buffers held and programs' temporaries are counted apart
                memory_peak_bytes=int(memory.get("peak_bytes_in_use", 0))
                + int(memory.get("peak_bytes_reserved", 0)),
                memory_limit_bytes=int(memory.get("bytes_limit", 0)))
        elif op == "reference":
            say(event="reference",
                logits=reference_logits(run, device, command["payload_ids"]))
        elif op == "exit":
            break
    stop.set()
    server.join(timeout=60)
    return 0 if not server.is_alive() else 1


if __name__ == "__main__":
    sys.exit(main())
