"""Persistent compilation cache wiring (the ``COMPILE_CACHE`` node).

A restart — crash recovery, preemption resume, elastic resume at the
same topology, a rolling serve-replica deploy, the next call of the chip
tool — pays the full compile storm again: every step program, every
serve bucket, every reshard helper. JAX ships an on-disk executable
cache keyed on (program, flags, backend, cache path); this module puts
it where ``JAX_COMPILATION_CACHE_DIR`` says or else at one fixed
directory in the checkout, and makes its effect OBSERVABLE:

* ``jit.cache_hits`` / ``jit.cache_misses`` registry counters and one
  ``kind="compile.cache"`` telemetry record per lookup
  (telemetry/runtime.py listens on jax's monitoring bus);
* a compile served from the cache is counted as a HIT, **not** as a
  ``jit.compiles`` compile — deserializing an executable is not a
  compilation, and the recompile-storm alert / run_report recompile
  count must not fire on a deliberately warm restart.

``tools/asyncplane_bench.py`` runs the cold/warm restart pair and
records the proof into BENCH_r06.json (warm-restart ``jit.compiles`` at
or near zero for previously-compiled step programs).
"""

from __future__ import annotations

import os

from distribuuuu_tpu.utils.logger import get_logger

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"

# Where the cache lives when nobody placed it: one fixed directory at the
# root of the checkout (git-ignored). The directory is part of the cache
# key, so it must not move between runs — never OUT_DIR, a tempfile name,
# a pid or a timestamp.
CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".compile_cache",
)


def validate_cfg(cc) -> None:
    """Refuse nonsense knob values before they reach jax.config (the
    cache failing open at runtime would just silently not cache)."""
    if float(cc.MIN_COMPILE_TIME_S) < 0:
        raise ValueError(
            f"COMPILE_CACHE.MIN_COMPILE_TIME_S={cc.MIN_COMPILE_TIME_S}: "
            "must be >= 0 (0 caches every compile)"
        )
    if int(cc.MAX_SIZE_MB) < 0:
        raise ValueError(
            f"COMPILE_CACHE.MAX_SIZE_MB={cc.MAX_SIZE_MB}: must be >= 0 "
            "(0 = unbounded)"
        )


def setup_from_cfg(cfg) -> str | None:
    """Place the persistent compilation cache for this process; returns
    the active cache dir, or None when the cache is off. The ONE function
    every entry point that compiles calls (train_net, test_net,
    serve_net, bench.py, chip_smoke.py), after platform selection — it
    reads ``jax.default_backend()``.

    * ``JAX_COMPILATION_CACHE_DIR`` set: the cache was placed from
      outside. jax already read the variable into its config; nothing
      here clears or replaces ``jax_compilation_cache_dir``, whatever
      the ``COMPILE_CACHE`` node says.
    * not set: ``COMPILE_CACHE.DIR`` if given, else :data:`CHECKOUT_DIR`.
      On the TPU backend the cache is on without a knob (a cold chip
      call compiles every step program and every serve tile); on the
      CPU it stays opt-in through ``COMPILE_CACHE.ENABLED``, and a
      disabled run clears a directory an earlier run in the same
      process set (jax config is process-global).
    """
    import jax

    cc = cfg.COMPILE_CACHE
    validate_cfg(cc)
    cache_dir = os.environ.get(ENV_DIR)
    if not cache_dir:
        if not (cc.ENABLED or jax.default_backend() == "tpu"):
            if jax.config.jax_compilation_cache_dir:
                jax.config.update("jax_compilation_cache_dir", None)
            return None
        cache_dir = os.path.abspath(cc.DIR or CHECKOUT_DIR)
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # jax's own default (1s) skips everything test/CPU-sized; the node
    # default (0) persists every compile — restarts are what we optimize
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(cc.MIN_COMPILE_TIME_S),
    )
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if int(cc.MAX_SIZE_MB) > 0:
        jax.config.update(
            "jax_compilation_cache_max_size", int(cc.MAX_SIZE_MB) * 2**20
        )
    # hit/miss observability rides the same monitoring bus as the
    # compile listener; installing here covers serve/test entrypoints too
    from distribuuuu_tpu.telemetry import runtime as telemetry_runtime

    telemetry_runtime.install_compile_listener()
    get_logger().info(
        "persistent compilation cache: %s (%s; min_compile_time %.3fs%s)",
        cache_dir,
        f"from {ENV_DIR}" if os.environ.get(ENV_DIR) else "set here",
        float(cc.MIN_COMPILE_TIME_S),
        f", max {int(cc.MAX_SIZE_MB)} MB" if int(cc.MAX_SIZE_MB) else "",
    )
    return cache_dir
