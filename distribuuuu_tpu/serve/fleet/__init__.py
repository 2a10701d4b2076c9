"""Serving fleet (ISSUE 6): a shared-nothing replica pool behind a
router process — the subsystem that takes PR 1's single-replica engine to
"millions of users" scale by composing three prior tentpoles:

* **serving** (PR 1) — each replica IS the existing serve_net engine
  (dynamic micro-batching over AOT bucket shapes) in its own process;
* **resilience** (PR 3) — draining restarts chain through the SIGTERM
  drain protocol, so deploys and scale-downs lose zero requests;
* **telemetry** (PR 5) — the least-loaded policy and the autoscaler read
  the Registry instruments serve/metrics.py already reports through.

    router.py     least-loaded dispatch, idempotent retry, verbatim
                  backpressure passthrough, fleet-wide latency telemetry
    pool.py       replica lifecycle: spawn, warm-up-gated routability,
                  health probes, draining restarts, target maintenance;
                  FleetService composes router+pool+autoscaler
    autoscale.py  p99-target/queue-watermark policy loop with hysteresis

Entry points: ``serve_net.py --fleet N`` (the operator CLI),
``tools/serve_bench.py --fleet N`` (saturation scaling bench), and
``tools/resilience_drill.py`` drill 10 (SIGKILL-a-replica-under-load).
"""

from distribuuuu_tpu.serve.fleet.autoscale import (  # noqa: F401
    AutoscalePolicy,
    Autoscaler,
    Observation,
)
from distribuuuu_tpu.serve.fleet.pool import (  # noqa: F401
    ChipSlots,
    FleetService,
    PoolManager,
    check_fleet_fits,
    free_port,
    local_chips,
    one_chip_env,
    probe_stats,
    spawn_serve_net,
    warmed_up,
)
from distribuuuu_tpu.serve.fleet.router import (  # noqa: F401
    LoadSnapshot,
    Replica,
    Router,
    load_score,
    pick_replica,
)
