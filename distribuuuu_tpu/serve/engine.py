"""Dynamic micro-batching inference engine (the serving hot path).

Turns the trainer's eval forward into an online service. Design, in the
order requests experience it:

1. **Admission** (``admission.AdmissionController``): ``submit`` rejects
   beyond ``SERVE.MAX_QUEUE`` pending requests with a retry-after hint —
   bounded queues keep overload from becoming unbounded latency.
2. **Dynamic micro-batching**: a batcher thread assembles up to
   ``SERVE.MAX_BATCH`` requests, or flushes ``SERVE.MAX_WAIT_MS`` after
   the oldest waiting request arrived — the batching-delay/occupancy
   trade the Gemma-on-TPU serving study (PAPERS.md, 2605.25645) puts at
   the center of TPU serving economics.
3. **Bucketed shapes, compiled exactly once**: a batch of n pads (zero
   rows) to the smallest bucket ≥ n; every bucket shape is AOT-compiled
   at startup via ``jax.jit`` lowering (``.lower(...).compile()``), so
   steady-state serving NEVER hits the jit cache or recompiles — the
   dispatch-pipelining regime the TPU concurrency study (2011.03641)
   shows bounds small-batch latency. ``n_compiles``/``COMPILE_EVENTS``
   are the compilation-count hook tests assert on.
4. **Double-buffered dispatch**: XLA dispatch is async — the batcher
   hands the in-flight device computation to a completion thread through
   a depth-2 queue and immediately assembles batch k+1 while the device
   executes batch k. The depth bound is the backpressure that stops the
   host from racing arbitrarily far ahead of the device.
5. **Per-request futures**: the completion thread blocks on the device
   result, slices off the padding rows, and demuxes row i to request i's
   ``Future`` — padded logits never leave the engine.

The forward is the eval step's: ``model.apply(..., train=False)`` on
val-transformed input, with the trainer's dtype-gated in-graph
normalization (uint8 input ⇒ ``(x/255 − mean)/std`` on device — the
``DATA.DEVICE_NORMALIZE`` pipeline; float input arrives pre-normalized).
Served logits are numerically identical to ``test_model``'s
(tests/test_serve.py proves it, padding included).

Throughput beyond one chip: serving is latency-optimal at one single-chip
replica per chip (no cross-chip collective on the critical path) — run
one engine per local device (``SERVE.DEVICE``) behind any request-level
balancer, rather than sharding a tiny batch over the mesh.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future
from queue import Queue

import jax
import numpy as np

from distribuuuu_tpu.config import cfg
from distribuuuu_tpu.serve.admission import AdmissionController, QueueFullError
from distribuuuu_tpu.serve.metrics import ServeMetrics
from distribuuuu_tpu.telemetry import registry as telemetry_registry
from distribuuuu_tpu.telemetry import spans

# Compilation-count hook: every AOT bucket compile appends its batch size.
# Steady-state serving must not grow this list (tests/test_serve.py).
COMPILE_EVENTS: list[int] = []


def default_buckets(max_batch: int) -> list[int]:
    """Powers of two up to ``max_batch``, plus ``max_batch`` itself —
    ≤ 2× padding waste at any occupancy with O(log) compiled shapes."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be ≥ 1, got {max_batch}")
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return out


class _Request:
    __slots__ = ("image", "future", "t_enq")

    def __init__(self, image: np.ndarray, t_enq: float):
        self.image = image
        self.future: Future = Future()
        self.t_enq = t_enq


class Engine:
    """Request-level serving engine over one device.

    ``variables`` is the eval-state dict ``{"params", "batch_stats"}``
    (what ``test_model`` feeds its eval step). Parameters default from
    ``cfg.SERVE``; pass explicit values for library/test use. ``submit``
    before ``start`` is allowed — requests queue until the threads run.
    """

    def __init__(
        self,
        model,
        variables: dict,
        im_size: int,
        *,
        max_batch: int | None = None,
        max_wait_ms: float | None = None,
        bucket_sizes: list[int] | None = None,
        max_queue: int | None = None,
        input_dtype=np.uint8,
        metrics: ServeMetrics | None = None,
        emit_interval_s: float = 10.0,
        quantize: str | None = None,
    ):
        self.model = model
        self._variables = variables
        self.im_size = int(im_size)
        self.max_batch = int(max_batch if max_batch is not None else cfg.SERVE.MAX_BATCH)
        wait = max_wait_ms if max_wait_ms is not None else cfg.SERVE.MAX_WAIT_MS
        self._max_wait_s = float(wait) / 1e3
        buckets = bucket_sizes or list(cfg.SERVE.BUCKET_SIZES) or default_buckets(
            self.max_batch
        )
        self.buckets = sorted(set(int(b) for b in buckets))
        if self.buckets[0] < 1 or self.buckets[-1] != self.max_batch:
            raise ValueError(
                f"SERVE.BUCKET_SIZES {self.buckets} must lie in [1, MAX_BATCH] "
                f"and include MAX_BATCH={self.max_batch} (a batch of n pads "
                "to the smallest bucket ≥ n; larger buckets would be dead "
                "compiled shapes)"
            )
        self.input_dtype = np.dtype(input_dtype)
        self.metrics = metrics or ServeMetrics()
        self._emit_interval_s = emit_interval_s
        self._admission = AdmissionController(
            max_queue if max_queue is not None else cfg.SERVE.MAX_QUEUE
        )

        # -- weight-only quantized variant (serve/quantize.py) ------------
        # "" = full precision; "bf16"/"int8" repack the weights BEFORE the
        # AOT compiles below, so every bucket executable bakes in the
        # variant — int8 weights dequantize in-graph per forward, trading
        # a cheap elementwise op for halved/quartered HBM weight traffic.
        mode = quantize if quantize is not None else str(cfg.SERVE.QUANTIZE)
        self.quantize_mode = mode
        self.quantize_meta = None
        if mode:
            from distribuuuu_tpu.serve import quantize as quantize_lib

            self._variables, self.quantize_meta = (
                quantize_lib.quantize_variables(variables, mode)
            )
            spans.emit_event(
                "serve.quantized",
                arch=cfg.MODEL.ARCH,
                mode=mode,
                bytes_before=self.quantize_meta["bytes_before"],
                bytes_after=self.quantize_meta["bytes_after"],
                leaves=self.quantize_meta["leaves"],
            )

        # -- AOT compile every bucket shape, exactly once, at startup -----
        self.n_compiles = 0
        self._compiled = {}
        jit_fwd = jax.jit(self._forward)
        for b in self.buckets:
            sds = jax.ShapeDtypeStruct(
                (b, self.im_size, self.im_size, 3), self.input_dtype
            )
            self._compiled[b] = jit_fwd.lower(self._variables, sds).compile()
            self.n_compiles += 1
            COMPILE_EVENTS.append(b)
        # AOT startup compiles in the shared registry (telemetry/): a
        # run_report over a serve run separates these expected compiles
        # from steady-state recompile storms (which bump jit.compiles
        # via the monitoring listener without bumping this)
        telemetry_registry.get_registry().counter(
            "serve.aot_compiles"
        ).inc(self.n_compiles)
        # cost-model ledger per bucket (telemetry/costmodel.py): flops /
        # bytes / HBM footprint of each serving shape, read straight off
        # the executables compiled above — no extra compile. The serve
        # half of run_report's MFU/headroom section.
        if cfg.TELEMETRY.COSTMODEL:
            from distribuuuu_tpu.telemetry import costmodel

            for b in self.buckets:
                label = (
                    f"serve_bucket_{b}_{mode}" if mode
                    else f"serve_bucket_{b}"
                )
                costmodel.capture_compiled(
                    self._compiled[b], label=label,
                    phase="serve", images=b, arch=cfg.MODEL.ARCH,
                )

        self._cond = threading.Condition()
        self._pending: deque[_Request] = deque()
        # depth-2 in-flight queue = the double buffer: batch k executing on
        # device, batch k+1 dispatched, batcher assembling k+2 blocks here
        self._inflight: Queue = Queue(maxsize=2)
        self._draining = False
        self._started = False
        self._batcher_t = threading.Thread(
            target=self._batcher, name="serve-batcher", daemon=True
        )
        self._completer_t = threading.Thread(
            target=self._completer, name="serve-completer", daemon=True
        )

    # -- model forward (traced once per bucket at startup) -----------------
    def _forward(self, variables, images):
        if self.quantize_mode == "int8":
            # in-graph dequant: int8 weights + per-channel scales expand to
            # f32 inside the traced forward — XLA fuses the expansion into
            # the consuming matmul/conv, so HBM reads stay int8-sized
            from distribuuuu_tpu.serve import quantize as quantize_lib

            variables = quantize_lib.dequantize_in_graph(variables)
        if images.dtype == np.uint8:
            # the DATA.DEVICE_NORMALIZE eval pipeline: host ships raw uint8,
            # normalization runs in-graph (identical formula/order to the
            # host path — data/transforms.py)
            from distribuuuu_tpu.data.transforms import normalize_in_graph

            images = normalize_in_graph(images)
        # one replica, one chip: the kernels with no shard_map of their
        # own may engage (ops/pallas/__init__.py)
        from distribuuuu_tpu.ops import pallas as kernel_tier

        with kernel_tier.single_device_program():
            return self.model.apply(variables, images, train=False)

    # -- client surface ----------------------------------------------------
    def start(self) -> "Engine":
        self._batcher_t.start()
        self._completer_t.start()
        self._started = True
        return self

    def __enter__(self) -> "Engine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.drain()

    def submit(self, image: np.ndarray) -> Future:
        """Enqueue one request; returns a Future resolving to its logits
        row. Raises ``QueueFullError`` (backpressure) or
        ``EngineClosedError`` (draining) instead of queueing unboundedly."""
        image = np.asarray(image)
        want = (self.im_size, self.im_size, 3)
        if image.shape != want or image.dtype != self.input_dtype:
            raise ValueError(
                f"request image must be {want} {self.input_dtype.name} "
                f"(the engine's compiled input), got {image.shape} "
                f"{image.dtype.name}"
            )
        with self._cond:
            try:
                self._admission.admit(
                    len(self._pending), self._retry_after_ms()
                )
            except QueueFullError:
                self.metrics.record_rejection()  # stats()["rejected"]
                raise
            req = _Request(image, time.perf_counter())
            self._pending.append(req)
            self._cond.notify()
        return req.future

    def drain(self, timeout: float | None = 60.0) -> None:
        """Graceful shutdown: stop accepting, finish every queued and
        in-flight request, stop the threads. Idempotent."""
        with self._cond:
            self._draining = True
            self._admission.close()
            self._cond.notify_all()
        if self._started:
            self._batcher_t.join(timeout)
            self._completer_t.join(timeout)
        else:
            # never started: nothing will ever serve the queue — fail
            # pending futures rather than hanging their owners
            from distribuuuu_tpu.serve.admission import EngineClosedError

            with self._cond:
                while self._pending:
                    req = self._pending.popleft()
                    req.future.set_exception(
                        EngineClosedError("engine drained before start()")
                    )

    def stats(self) -> dict:
        with self._cond:
            depth = len(self._pending)
        out = self.metrics.snapshot()
        out.update(
            queue_depth=depth,
            n_compiles=self.n_compiles,
            buckets=list(self.buckets),
            max_batch=self.max_batch,
            quantize=self.quantize_mode,
        )
        return out

    def _retry_after_ms(self) -> float:
        """Queue depth × recent service time per slot, floored at the
        batching window — a client honoring it lands when capacity frees."""
        per_slot = self.metrics.mean_batch_ms() / self.max_batch
        with_depth = self._admission.max_queue * per_slot / 2
        return max(self._max_wait_s * 1e3, with_depth)

    # -- batcher thread ----------------------------------------------------
    def _collect(self) -> list[_Request] | None:
        """Block until a flush condition: MAX_BATCH waiting, or MAX_WAIT_MS
        since the oldest request arrived, or draining. None = drained dry."""
        with self._cond:
            while not self._pending and not self._draining:
                self._cond.wait(timeout=0.1)
            if not self._pending:
                return None  # draining and nothing left
            deadline = self._pending[0].t_enq + self._max_wait_s
            while len(self._pending) < self.max_batch and not self._draining:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                self._cond.wait(timeout=remaining)
            take = min(len(self._pending), self.max_batch)
            return [self._pending.popleft() for _ in range(take)]

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        raise AssertionError(f"no bucket for batch {n}")  # unreachable

    def _batcher(self) -> None:
        while True:
            reqs = self._collect()
            if reqs is None:
                break
            bucket = self._bucket_for(len(reqs))
            batch = np.zeros(
                (bucket, self.im_size, self.im_size, 3), self.input_dtype
            )
            for i, r in enumerate(reqs):
                batch[i] = r.image
            try:
                # async dispatch: returns immediately; the device executes
                # while we loop back and assemble the next batch
                out = self._compiled[bucket](self._variables, batch)
            except Exception as e:  # noqa: BLE001 — fail THIS batch only
                for r in reqs:
                    r.future.set_exception(e)
                continue
            self._inflight.put((out, reqs, bucket, time.perf_counter()))
        self._inflight.put(None)  # completer shutdown sentinel

    # -- completion thread -------------------------------------------------
    def _completer(self) -> None:
        last_emit = time.perf_counter()
        while True:
            item = self._inflight.get()
            if item is None:
                break
            out, reqs, bucket, t_disp = item
            logits = np.asarray(out)  # blocks until the device finishes
            t_done = time.perf_counter()
            lats = []
            for i, r in enumerate(reqs):
                r.future.set_result(np.array(logits[i]))
                lats.append(t_done - r.t_enq)
            self.metrics.record_batch(len(reqs), bucket, t_done - t_disp, lats)
            if t_done - last_emit >= self._emit_interval_s:
                self.metrics.emit()  # no-op without a jsonlog sink
                last_emit = t_done


def engine_from_cfg() -> Engine:
    """Build a serving Engine from the global cfg: the configured arch on a
    single-device mesh (``SERVE.DEVICE``), weights from ``MODEL.WEIGHTS``
    (orbax dir or torch pickle) or the pretrained URL zoo
    (``MODEL.PRETRAINED``), input dtype per ``DATA.DEVICE_NORMALIZE``.

    Single-process by construction — serving does not call
    ``setup_distributed``; multi-chip hosts run one engine per chip.
    """
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.parallel import mesh as mesh_lib

    mesh_lib.apply_backend_flags(
        cfg.DEVICE.DETERMINISTIC or cfg.CUDNN.DETERMINISTIC
    )
    mesh_lib.apply_platform(cfg.DEVICE.PLATFORM)
    devices = jax.local_devices()
    idx = cfg.SERVE.DEVICE
    if not 0 <= idx < len(devices):
        raise ValueError(
            f"SERVE.DEVICE={idx} out of range: {len(devices)} local devices"
        )
    mesh = mesh_lib.build_mesh(data=1, model=1, seq=1, pipe=1,
                               devices=[devices[idx]])
    model = trainer.build_model_from_cfg()
    state = trainer.create_train_state(
        model, jax.random.key(cfg.RNG_SEED or 0), mesh, cfg.TRAIN.IM_SIZE
    )
    if cfg.MODEL.WEIGHTS:
        state = trainer._with_restored_weights(state, cfg.MODEL.WEIGHTS, model)
    elif cfg.MODEL.PRETRAINED:
        from distribuuuu_tpu.utils import url_zoo

        state = trainer._with_restored_weights(
            state, url_zoo.fetch(cfg.MODEL.ARCH), model
        )
    variables = {"params": state.params, "batch_stats": state.batch_stats}
    return Engine(
        model,
        variables,
        cfg.TRAIN.IM_SIZE,
        input_dtype=np.uint8 if cfg.DATA.DEVICE_NORMALIZE else np.float32,
        quantize=str(cfg.SERVE.QUANTIZE),
    )
