"""Token-ordered dispatch sequencer: overlapped execution made safe on
multi-device topologies (ISSUE 11 tentpole, lifts PR 10's gate).

The pinned deadlock this removes: two host threads (the trainer's epoch
loop and the concurrent-eval worker) each dispatch SPMD programs onto
the same multi-device mesh. The backend establishes per-device execution
order asynchronously — NOT at the dispatch call — so the two programs'
per-device orders can invert: device 0 runs eval's collective while
device 7 runs train's, each collective waits forever for its missing
participants at the XLA rendezvous, and the whole backend wedges
(reproduced deterministically; `collective_ops_utils` "stuck at
rendezvous"). PR 10 shipped around it by gating concurrent eval to
single-device processes.

What the probe matrix established on the CPU backend (and the design
follows from it — see tests/test_asyncplane.py's regression test):

* two *different* collective programs concurrently in flight can
  cross-wait — even when every dispatch call happens on ONE thread, so
  a plain dispatch mutex is NOT sufficient;
* a chain is safe: when the previous program's outputs are *ready*
  before the next program is dispatched, no inversion is possible.

The sequencer therefore combines both disciplines:

* **token ring** — every step dispatch (trainer, concurrent-eval
  worker, snapshot) first acquires a dispatch token; tokens are granted
  in one global FIFO order (a ticket counter), so dispatches are
  serialized and attributable;
* **completion fence on stream switch** — when the token passes between
  *streams* (train → eval, eval → train, …), the incoming dispatch
  first blocks until the previous stream's last dispatched outputs are
  ready. The in-flight set therefore only ever contains programs of ONE
  stream; within a stream, programs chain by construction (train steps
  thread the donated state) or are fenced per dispatch (the eval
  stream), so every device observes one agreed program sequence — the
  deadlock precondition is structurally removed, not raced against.

A wedged dispatcher (a thread that acquired the token and never
completes its dispatch — hung storage under a fence, a stuck compile)
surfaces through the same stall contract as everything else: the
acquire/fence waits are wired through ``supervisor.watch_blocking`` and
flag a ``kind="dispatch.wedge"`` record (+ a log line) instead of
hanging silently; the monitor's
``dispatch-wedge`` rule (config/monitor_rules.yaml) alerts on it.
``FAULTS.WEDGE_DISPATCH`` injects exactly this failure for the
``dispatch_wedge_recovery`` drill.

Stats (tokens issued per stream, max/total token-wait, fence waits) are
emitted as ``kind="dispatch.token"`` records at epoch boundaries and
surfaced by ``tools/run_report.py``; ``tools/asyncplane_bench.py``
measures the overhead (BENCH_r07.json: token acquire latency and
trainer-blocked time with concurrent eval ON at 8 devices).

``ASYNC.SEQUENCER=False`` is the escape hatch: the trainer then
restores the PR 10 degrade-to-sync gates with a logged warning.

On MULTI-HOST runs the local FIFO is not enough — two hosts' FIFOs can
grant the same global slot to different streams and re-create the
inversion between hosts. ``install_ring`` attaches a
``ring.CrossHostRing`` (ISSUE 18): the leader (process 0) publishes its
grant order through an atomically-replaced watermark file, followers
grant slots only in that published order (``_acquire_agreed``), and a
follower blocked past ``ASYNC.RING_DEADLINE_S`` flags ``dispatch.wedge``
and marks the ring wedged so the trainer degrades THAT epoch's eval to
sync instead of hanging. Ring aggregates ride out as
``kind="dispatch.ring"`` records next to the token stats.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager

from distribuuuu_tpu.utils.logger import get_logger

# the dispatch streams the trainer wires (free-form — stats are keyed
# per stream, the fence triggers on any stream CHANGE)
TRAIN_STREAM = "train"
EVAL_STREAM = "eval"
SNAPSHOT_STREAM = "snapshot"


class DispatchSequencer:
    """One global token ring + completion fence over dispatch streams."""

    def __init__(self, wedge_timeout: float = 0.0, logger=None):
        self.wedge_timeout = float(wedge_timeout)
        self.logger = logger or get_logger()
        self._cond = threading.Condition()
        self._next_ticket = 0   # next token number to hand out
        self._serving = 0       # token currently allowed to dispatch
        self._holder: str | None = None  # stream holding the token
        self._last_stream: str | None = None  # stream of the last dispatch
        self._fence = None      # last dispatched outputs of _last_stream
        self._wedges = 0
        self._ring = None       # CrossHostRing when multi-host (ISSUE 18)
        self._slot = 0          # next global slot (follower agreed-order)
        self._ring_wedged = False  # sticky until the trainer re-arms
        self.stats = {
            "tokens": 0,
            "streams": {},          # stream -> tokens granted
            "switches": 0,          # stream changes (fence candidates)
            "total_wait_s": 0.0,    # token acquire wait, summed
            "max_wait_s": 0.0,
            "fence_waits": 0,       # fences that actually blocked
            "fence_wait_s": 0.0,
            "max_fence_wait_s": 0.0,
        }

    # ------------------------------------------------------------ wedge
    def _flag_wedge(self, phase: str, age: float) -> None:
        """The stall-contract flag for a wedged dispatcher: log line +
        ``kind="dispatch.wedge"`` record (the monitor's
        dispatch-wedge rule input). One flag per excursion — the wait
        itself persists (flag, not kill)."""
        from distribuuuu_tpu.utils.jsonlog import metrics_log

        holder = self._holder or "?"
        self._wedges += 1
        self.logger.warning(
            "dispatch token wedged: %s blocked %.1fs in %s (threshold "
            "%.1fs) — the %r stream holds the token and its dispatch "
            "never completed; see docs/RUNBOOK.md 'Async on a pod: the "
            "dispatch sequencer'",
            phase, age, holder, self.wedge_timeout, holder,
        )
        metrics_log(
            "dispatch.wedge", age_s=round(age, 3), holder=holder,
            phase=phase, count=self._wedges,
        )

    @contextmanager
    def _watched(self, phase: str):
        """Wrap a blocking wait in the supervisor's blocking watchdog
        (one watcher thread, spawned only when a wait actually happens
        and a timeout is configured)."""
        from distribuuuu_tpu.resilience import supervisor

        with supervisor.watch_blocking(
            f"dispatch sequencer ({phase})", self.wedge_timeout,
            logger=self.logger,
            on_flag=lambda age: self._flag_wedge(phase, age),
        ):
            yield

    # ---------------------------------------------------------- the ring
    def attach_ring(self, ring) -> None:
        """Wire a ``ring.CrossHostRing``: the leader publishes every local
        grant, followers switch to agreed-order acquire. Called once by
        ``install_ring`` before the second dispatch stream starts."""
        self._ring = ring

    def acquire(self, stream: str) -> int:
        """Block until this thread holds the dispatch token; returns the
        token number (tokens are granted in one global FIFO order)."""
        ring = self._ring
        if ring is not None and not ring.leader:
            return self._acquire_agreed(stream)
        with self._cond:
            ticket = self._next_ticket
            self._next_ticket += 1
            contended = self._serving != ticket
        t0 = time.perf_counter()
        if contended:
            with self._watched(f"token acquire, stream {stream!r}"):
                with self._cond:
                    while self._serving != ticket:
                        self._cond.wait(0.1)
        wait = time.perf_counter() - t0
        st = self.stats
        st["tokens"] += 1
        st["streams"][stream] = st["streams"].get(stream, 0) + 1
        st["total_wait_s"] += wait
        st["max_wait_s"] = max(st["max_wait_s"], wait)
        self._holder = stream
        if ring is not None:  # leader: publish the grant for followers
            from distribuuuu_tpu.utils import faults

            faults.maybe_wedge_ring(ticket)  # injection no-op
            ring.publish(ticket, stream)
        return ticket

    def _acquire_agreed(self, stream: str) -> int:
        """Follower acquire on a multi-host ring: grant local slot N to
        ``stream`` only when the leader's published order says slot N
        belongs to it — a follower may LAG the leader by a poll interval
        but can never outrun its decisions, which is what keeps every
        host's per-device enqueue order identical. Blocked past the ring
        deadline: flag ``dispatch.wedge`` + mark the ring wedged (the
        trainer degrades that epoch's eval to sync). Blocked past
        ``detach_after_s`` with no leader progress: detach (local FIFO,
        error-logged) — degradation over a silent hang, always."""
        ring = self._ring
        t0 = time.perf_counter()
        flagged = False
        with self._watched(f"ring slot wait, stream {stream!r}"):
            with self._cond:
                while True:
                    if self._holder is None:
                        if ring.detached:
                            break
                        agreed = ring.agreed_stream(self._slot)
                        if agreed == stream:
                            break
                    waited = time.perf_counter() - t0
                    if not flagged and waited > ring.deadline_s:
                        flagged = True
                        ring.wedged = True
                        self._ring_wedged = True
                        ring.stats["deadline_misses"] += 1
                        self._flag_wedge(
                            f"ring slot {self._slot} ({stream!r})", waited
                        )
                    if waited > ring.detach_after_s:
                        ring.detach(waited)
                        continue  # re-check: grant on _holder alone now
                    self._cond.wait(0.05)
                slot = self._slot
                self._slot += 1
                self._holder = stream
        wait = time.perf_counter() - t0
        st = self.stats
        st["tokens"] += 1
        st["streams"][stream] = st["streams"].get(stream, 0) + 1
        st["total_wait_s"] += wait
        st["max_wait_s"] = max(st["max_wait_s"], wait)
        rst = ring.stats
        rst["slots"] += 1
        rst["total_wait_s"] += wait
        rst["max_wait_s"] = max(rst["max_wait_s"], wait)
        return slot

    def _fence_previous(self, stream: str) -> None:
        """The stream-switch fence: before dispatching into a different
        stream than the previous token's, block until that stream's last
        dispatched outputs are ready — the in-flight set never mixes two
        programs, so per-device order inversions cannot happen."""
        if self._last_stream in (None, stream) or self._fence is None:
            return
        import jax

        self.stats["switches"] += 1
        t0 = time.perf_counter()
        with self._watched(
            f"fence on {self._last_stream!r} before {stream!r}"
        ):
            jax.block_until_ready(self._fence)
        wait = time.perf_counter() - t0
        st = self.stats
        st["fence_waits"] += 1
        st["fence_wait_s"] += wait
        st["max_fence_wait_s"] = max(st["max_fence_wait_s"], wait)
        self._fence = None

    def release(self, ticket: int) -> None:
        with self._cond:
            self._serving = ticket + 1
            self._holder = None
            self._cond.notify_all()

    def dispatch(self, stream: str, fn, *args, fence: bool = False, **kw):
        """Dispatch ``fn(*args, **kw)`` under the token: acquire in
        global order, fence the previous stream if it differs, call, and
        record the outputs as this stream's fence. ``fence=True``
        additionally blocks until THIS dispatch's outputs are ready
        before releasing — the discipline for streams whose programs do
        not chain through data dependencies (the eval stream)."""
        ticket = self.acquire(stream)
        try:
            self._fence_previous(stream)
            from distribuuuu_tpu.utils import faults

            faults.maybe_wedge_dispatch(ticket)  # injection no-op
            out = fn(*args, **kw)
            if fence:
                import jax

                with self._watched(f"post-dispatch fence, {stream!r}"):
                    jax.block_until_ready(out)
                self._fence = None
            else:
                self._fence = out
            self._last_stream = stream
            return out
        finally:
            self.release(ticket)

    def snapshot_stats(self) -> dict:
        """Stats payload (rounded, json-able) for ``dispatch.token``."""
        st = self.stats
        return {
            "tokens": st["tokens"],
            "streams": dict(st["streams"]),
            "switches": st["switches"],
            "total_wait_s": round(st["total_wait_s"], 6),
            "max_wait_s": round(st["max_wait_s"], 6),
            "fence_waits": st["fence_waits"],
            "fence_wait_s": round(st["fence_wait_s"], 6),
            "max_fence_wait_s": round(st["max_fence_wait_s"], 6),
            "wedges": self._wedges,
        }


# ------------------------------------------------------- module-level API
_active: DispatchSequencer | None = None


def install(wedge_timeout: float = 0.0, logger=None) -> DispatchSequencer:
    """Activate the sequencer for this process (the trainer calls this
    when a second dispatch stream is about to start on a multi-device
    process). Idempotent: re-install keeps the existing ring (stats roll
    on) but adopts the new timeout."""
    global _active
    if _active is None:
        _active = DispatchSequencer(wedge_timeout, logger=logger)
    else:
        _active.wedge_timeout = float(wedge_timeout)
    return _active


def install_ring(root: str, rank: int, world: int, deadline_s: float, *,
                 detach_after_s: float = 600.0, logger=None):
    """Attach the cross-host dispatch ring to the installed sequencer
    (the trainer calls this on multi-host runs right after ``install``).
    The leader fresh-clears ``root`` and raises the OPEN sentinel;
    followers block (bounded by ``detach_after_s``, the barrier-timeout
    contract) until it appears — stale order from a previous attempt can
    never leak in. Idempotent once attached."""
    from distribuuuu_tpu.asyncplane import ring as ring_mod

    seq = _active
    if seq is None:
        raise RuntimeError(
            "install_ring requires an installed sequencer — call "
            "sequencer.install() first"
        )
    if seq._ring is not None:
        return seq._ring
    r = ring_mod.CrossHostRing(
        root, rank, world, deadline_s,
        detach_after_s=detach_after_s, logger=logger or seq.logger,
    )
    r.open(timeout=detach_after_s)
    seq.attach_ring(r)
    return r


def ring_installed() -> bool:
    return _active is not None and _active._ring is not None


def ring_wedged() -> bool:
    """True when a follower missed its ring deadline since the last
    re-arm — the trainer's epoch-boundary signal to run THAT epoch's
    eval synchronously instead of launching the concurrent worker."""
    return _active is not None and _active._ring_wedged


def clear_ring_wedge() -> None:
    """Re-arm after the degraded epoch (the wedge record already
    flagged; a persistent wedge just flags again next epoch)."""
    if _active is not None:
        _active._ring_wedged = False


def installed() -> bool:
    return _active is not None


def get() -> DispatchSequencer | None:
    return _active


def shutdown() -> None:
    """Deactivate (end of train_model / tests). Subsequent dispatches
    take the zero-overhead pass-through path again."""
    global _active
    _active = None


def dispatch(stream: str, fn, *args, fence: bool = False, **kw):
    """The one call site the trainer uses: token-ordered dispatch when
    the sequencer is installed, plain pass-through (one attribute read)
    otherwise — single-stream runs pay nothing."""
    seq = _active
    if seq is None:
        return fn(*args, **kw)
    return seq.dispatch(stream, fn, *args, fence=fence, **kw)


def emit_stats(**extra) -> None:
    """One ``kind="dispatch.token"`` record with the ring's running
    aggregates (the trainer emits at epoch boundaries; run_report reads
    the last record per rank)."""
    seq = _active
    if seq is None:
        return
    from distribuuuu_tpu.telemetry import spans as telemetry_spans

    if not telemetry_spans.enabled():
        return
    telemetry_spans.emit_event(
        "dispatch.token", **seq.snapshot_stats(), **extra
    )
    ring = seq._ring
    if ring is not None:
        rs = ring.snapshot_stats()
        telemetry_spans.emit_event(
            "dispatch.ring", host=rs["host"], hosts=rs["hosts"],
            role=rs["role"], slots=rs["slots"], switches=rs["switches"],
            total_wait_s=rs["total_wait_s"], max_wait_s=rs["max_wait_s"],
            deadline_misses=rs["deadline_misses"], wedged=rs["wedged"],
            detached=rs["detached"], **extra,
        )
