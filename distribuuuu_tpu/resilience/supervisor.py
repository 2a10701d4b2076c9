"""In-run failure supervision: non-finite loss policy + stall watchdog.

Two failure classes the epoch loop previously could not survive:

* **Non-finite loss.** A NaN/Inf loss (bad sample, LR spike, hardware bit
  flip) silently poisons every subsequent step — the run keeps burning
  chips while training garbage. ``TRAIN.NONFINITE`` picks the policy:

    "raise"     fail fast at the next metric flush (the default — honest
                failure beats silent corruption);
    "skip"      the update is discarded IN-GRAPH (``guard_nonfinite``
                selects the pre-step state when the loss is non-finite,
                advancing only the step cursor) and the host logs/counts
                the skipped step — right for rare bad batches;
    "rollback"  the trainer reloads the last intact checkpoint and
                re-runs from there (``TRAIN.MAX_ROLLBACKS`` attempts) —
                right for transient corruption; a deterministic NaN will
                re-trip and surface after the budget is spent.

  The guard itself is compiled into the step (a scalar ``isfinite`` plus
  a select — no host sync, no dispatch stall); detection happens at the
  PRINT_FREQ metric flush the loop already performs, so the async
  dispatch pipeline keeps its depth.

* **Stalled steps.** A wedged collective, a dead remote host, or a hung
  storage layer leaves the loop blocked with no log line ever appearing.
  The ``Heartbeat`` watchdog (``TRAIN.STALL_TIMEOUT`` seconds, 0 = off)
  runs a daemon thread that flags — log line + ``kind="stall"`` metrics
  record — whenever no ``beat()`` lands inside the window. Flag, not
  kill: the operator (or the fleet scheduler's external watchdog) owns
  the restart decision; the log line is what makes the hang diagnosable.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager

import jax
import jax.numpy as jnp

from distribuuuu_tpu.utils.jsonlog import metrics_log
from distribuuuu_tpu.utils.logger import get_logger

NONFINITE_POLICIES = ("raise", "skip", "rollback")


class NonFiniteLossError(RuntimeError):
    """Loss went NaN/Inf and the policy was not 'skip' (or the rollback
    budget ran out). Carries the position for the rollback handler."""

    def __init__(self, epoch: int, batch: int, value: float):
        super().__init__(
            f"non-finite loss ({value}) at epoch {epoch + 1}, batch ~{batch}. "
            "Policy TRAIN.NONFINITE: 'raise' (this), 'skip' (discard the "
            "step in-graph), 'rollback' (reload the last intact checkpoint); "
            "see docs/RUNBOOK.md 'Recovering a wedged run'."
        )
        self.epoch = epoch
        self.batch = batch
        self.value = value


def validate_policy(policy: str) -> str:
    if policy not in NONFINITE_POLICIES:
        raise ValueError(
            f"TRAIN.NONFINITE={policy!r}: must be one of {NONFINITE_POLICIES}"
        )
    return policy


def guard_nonfinite(old_state, new_state, metrics: dict, policy: str):
    """The in-graph half of the non-finite policy (call inside the jitted
    step body, AFTER the optimizer update).

    Always annotates ``metrics["nonfinite"]`` (1.0 when the loss is not
    finite) so the host loop can detect without an extra fetch. Under
    "skip" it additionally selects the PRE-step state leaf-by-leaf — the
    poisoned params/stats/optimizer update is discarded wholesale — while
    the step cursor still advances (so per-step RNG folding moves on and
    a deterministic bad batch is not re-drawn forever).
    """
    bad = jnp.logical_not(jnp.isfinite(metrics["loss"]))
    metrics = dict(metrics)
    metrics["nonfinite"] = bad.astype(jnp.float32)
    if policy != "skip":
        return new_state, metrics

    def _sel(n, o):
        if n is o:  # untouched leaves (e.g. the base PRNG key)
            return n
        try:
            if jnp.issubdtype(n.dtype, jax.dtypes.prng_key):
                return n  # the step never rewrites the base key
        except (AttributeError, TypeError):
            pass
        return jnp.where(bad, o, n)

    reverted = jax.tree.map(_sel, new_state, old_state)
    if hasattr(reverted, "replace") and hasattr(new_state, "step"):
        reverted = reverted.replace(step=new_state.step)
    return reverted, metrics


class NonFiniteMonitor:
    """Host-side half: consumes the fetched ``nonfinite`` flags at flush
    time and applies the policy — count+log for "skip", raise for
    "raise"/"rollback" (the trainer's epoch loop catches the latter)."""

    def __init__(self, policy: str, epoch: int, logger=None):
        self.policy = validate_policy(policy)
        self.epoch = epoch
        self.logger = logger or get_logger()
        self.skipped = 0

    def observe(self, loss: float, nonfinite: float, batch: int) -> bool:
        """True ⇒ this step was skipped in-graph (exclude it from meters)."""
        if not nonfinite:
            return False
        if self.policy == "skip":
            self.skipped += 1
            self.logger.warning(
                "non-finite loss at epoch %d batch ~%d — update skipped "
                "in-graph (TRAIN.NONFINITE=skip; %d skipped so far)",
                self.epoch + 1, batch, self.skipped,
            )
            metrics_log(
                "nonfinite", epoch=self.epoch + 1, batch=batch,
                skipped=self.skipped, policy="skip",
            )
            return True
        metrics_log(
            "nonfinite", epoch=self.epoch + 1, batch=batch,
            policy=self.policy,
        )
        raise NonFiniteLossError(self.epoch, batch, loss)


@contextmanager
def watch_blocking(label: str, timeout: float, logger=None, on_flag=None):
    """Stall coverage for blocking host-side operations OUTSIDE the
    epoch loop, where no ``Heartbeat`` thread is running: the async
    checkpoint committer's join barrier, the cross-host commit barrier
    wait, a preemption drain, a restore, the dispatch sequencer's
    token/fence waits. Same signal contract as the heartbeat — a warning
    line and a ``kind="stall"`` record — when the wrapped block exceeds
    ``timeout`` seconds (the operator's first clue that storage, not
    training, is what hung).
    ``timeout <= 0`` disables (zero overhead: no thread is started).
    Flag, not kill — the block keeps waiting; the restart decision stays
    external.

    ``on_flag(age_s)`` replaces the default emission: callers with their
    own record kind (the sequencer's ``dispatch.wedge``) reuse the
    watcher mechanics but speak their own schema — kinds stay literal at
    their emit sites for the static schema check."""
    timeout = float(timeout)
    if timeout <= 0:
        yield
        return
    logger = logger or get_logger()
    done = threading.Event()
    t0 = time.monotonic()

    def _watch():
        while not done.wait(min(timeout / 4.0, 1.0)):
            age = time.monotonic() - t0
            if age > timeout:
                if on_flag is not None:
                    on_flag(age)
                    return  # one flag per excursion
                logger.warning(
                    "blocked in %s for %.1fs (threshold %.1fs) — hung "
                    "storage or a wedged background commit; see "
                    "docs/RUNBOOK.md 'Async checkpointing and warm "
                    "restarts'", label, age, timeout,
                )
                metrics_log(
                    "stall", age_s=round(age, 3), last=label, count=1
                )
                return  # one flag per excursion; the join itself persists

    watcher = threading.Thread(
        target=_watch, daemon=True, name="dtpu-block-watch"
    )
    watcher.start()
    try:
        yield
    finally:
        done.set()
        watcher.join(timeout=2.0)


class Heartbeat:
    """Stall watchdog: flags when no ``beat()`` arrives within ``timeout``
    seconds. ``timeout <= 0`` disables (no thread is started); ``beat``/
    ``stop`` are then no-ops, so call sites need no gating."""

    def __init__(self, timeout: float, logger=None):
        self.timeout = float(timeout)
        self.logger = logger or get_logger()
        self.stall_count = 0
        self._last = time.monotonic()
        self._label = "start"
        self._flagged_at = 0.0  # last beat time we already flagged for
        self._stop = threading.Event()
        self._thread = None
        if self.timeout > 0:
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="dtpu-heartbeat"
            )
            self._thread.start()

    def beat(self, label: str = "") -> None:
        self._last = time.monotonic()
        if label:
            self._label = label

    def _run(self) -> None:
        poll = max(min(self.timeout / 4.0, 1.0), 0.01)
        while not self._stop.wait(poll):
            last = self._last
            age = time.monotonic() - last
            if age > self.timeout and last != self._flagged_at:
                self._flagged_at = last
                self.stall_count += 1
                self.logger.warning(
                    "heartbeat: no step progress for %.1fs (last: %s; "
                    "TRAIN.STALL_TIMEOUT=%.1fs) — a wedged collective, dead "
                    "peer host, or hung storage; see docs/RUNBOOK.md "
                    "'Recovering a wedged run'",
                    age, self._label, self.timeout,
                )
                metrics_log(
                    "stall", age_s=round(age, 3), last=self._label,
                    count=self.stall_count,
                )

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
