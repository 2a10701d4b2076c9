"""Serving subsystem (distribuuuu_tpu/serve/): bucketed-shape padding
correctness, flush-on-timeout vs flush-on-full, backpressure at MAX_QUEUE,
graceful drain, steady-state zero-recompilation, and end-to-end
serve-vs-``test_model``-logits equality on a tiny arch (fast tier, CPU).
"""

from __future__ import annotations

import io
import json
import os
import signal
import socket
import threading
import time

import jax
import numpy as np
import pytest

import distribuuuu_tpu.config as config
from distribuuuu_tpu import trainer
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu.parallel import mesh as mesh_lib
from distribuuuu_tpu.serve import (
    AdmissionController,
    Engine,
    EngineClosedError,
    QueueFullError,
    ServeMetrics,
    default_buckets,
)
from distribuuuu_tpu.serve import engine as engine_lib
from distribuuuu_tpu.serve import protocol

IM = 16
NC = 10
MAX_WAIT_MS = 250.0  # the shared engine's batching window


def _tiny_cfg():
    config.reset_cfg()
    cfg.MODEL.ARCH = "resnet18"
    cfg.MODEL.NUM_CLASSES = NC
    cfg.MODEL.BN_GROUP = 8
    cfg.DEVICE.COMPUTE_DTYPE = "float32"
    cfg.TRAIN.IM_SIZE = IM
    cfg.TEST.IM_SIZE = IM


@pytest.fixture(scope="module")
def served():
    """One tiny model + eval variables for every engine in this module."""
    _tiny_cfg()
    mesh = mesh_lib.build_mesh(data=1, model=1, seq=1, pipe=1,
                               devices=[jax.devices()[0]])
    model = trainer.build_model_from_cfg()
    state = trainer.create_train_state(model, jax.random.key(0), mesh, IM)
    variables = {"params": state.params, "batch_stats": state.batch_stats}
    return model, variables


@pytest.fixture(scope="module")
def engine(served):
    """The shared float32 engine (buckets 1/2/4). Tests that drain or need
    special geometry build their own."""
    model, variables = served
    eng = Engine(
        model, variables, IM,
        max_batch=4, max_wait_ms=MAX_WAIT_MS, max_queue=32,
        input_dtype=np.float32,
    )
    eng.start()
    yield eng
    eng.drain()


def _float_images(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, IM, IM, 3)).astype(np.float32)


def test_default_buckets():
    assert default_buckets(8) == [1, 2, 4, 8]
    assert default_buckets(6) == [1, 2, 4, 6]
    assert default_buckets(1) == [1]
    with pytest.raises(ValueError):
        default_buckets(0)


def test_bucket_validation(served):
    model, variables = served
    with pytest.raises(ValueError, match="BUCKET_SIZES"):
        Engine(model, variables, IM, max_batch=4, bucket_sizes=[1, 2],
               input_dtype=np.float32)  # missing MAX_BATCH bucket


def test_admission_controller_unit():
    adm = AdmissionController(max_queue=2)
    adm.admit(0, 5.0)
    adm.admit(1, 5.0)
    with pytest.raises(QueueFullError) as ei:
        adm.admit(2, 7.5)
    assert ei.value.retry_after_ms == 7.5
    assert ei.value.max_queue == 2
    adm.close()
    with pytest.raises(EngineClosedError):
        adm.admit(0, 5.0)


def test_submit_validates_shape_and_dtype(engine):
    with pytest.raises(ValueError, match="compiled input"):
        engine.submit(np.zeros((IM, IM, 3), np.uint8))  # wrong dtype
    with pytest.raises(ValueError, match="compiled input"):
        engine.submit(np.zeros((IM + 1, IM, 3), np.float32))  # wrong shape


def test_padded_logits_masked_and_match_eval(served, engine):
    """A 3-request flush pads to bucket 4: responses must be bitwise
    independent of the padding rows and numerically identical to the eval
    forward ``test_model`` runs on the same inputs."""
    model, variables = served
    images = _float_images(3, seed=1)

    futs = [engine.submit(img) for img in images]
    got = np.stack([f.result() for f in futs])

    # (a) identity with the eval-step forward at the natural (unpadded)
    # batch shape — the exact apply() validate()/test_model() computes
    ref = np.asarray(
        jax.jit(lambda v, x: model.apply(v, x, train=False))(variables, images)
    )
    np.testing.assert_allclose(got, ref, rtol=1e-6, atol=1e-6)

    # (b) padding rows cannot contaminate real rows: run the engine's own
    # bucket-4 executable with zero padding vs garbage padding — the first
    # three rows must be BITWISE equal
    pad_zero = np.zeros((4, IM, IM, 3), np.float32)
    pad_zero[:3] = images
    pad_garbage = pad_zero.copy()
    pad_garbage[3] = 1e6
    out_zero = np.asarray(engine._compiled[4](variables, pad_zero))
    out_garbage = np.asarray(engine._compiled[4](variables, pad_garbage))
    assert (out_zero[:3] == out_garbage[:3]).all()
    # and the engine's demuxed responses are those same rows
    assert (got == out_zero[:3]).all()


def _queue_wait_ms(engine, images):
    """Serve ``images`` as one window and return ``(snapshot, wait)``: the
    oldest request's submit-to-dispatch wait by the engine's own metrics
    (its latency, the window's largest, less the batch's service time)."""
    engine.metrics = ServeMetrics()
    for f in [engine.submit(img) for img in images]:
        f.result()
    snap = engine.metrics.snapshot()
    return snap, snap["p99_ms"] - snap["mean_batch_ms"]


def test_flush_on_full_vs_flush_on_timeout(engine):
    """What tells the two flushes apart is how long the batch queued, not
    how long the forward pass took: a timer flush cannot leave before
    MAX_WAIT_MS, a full batch does not wait for the timer."""
    _queue_wait_ms(engine, _float_images(4, seed=1))  # the program's first run

    # full: MAX_BATCH requests flush at once, before the window closes
    snap, full_wait = _queue_wait_ms(engine, _float_images(4, seed=2))
    assert snap["batches"] == 1 and snap["batch_occupancy"] == 1.0
    assert full_wait < MAX_WAIT_MS, f"flush-on-full queued {full_wait:.1f} ms"

    # timeout: a partial batch waits out MAX_WAIT_MS then flushes padded
    snap, partial_wait = _queue_wait_ms(engine, _float_images(3, seed=3))
    assert snap["batches"] == 1
    assert snap["batch_occupancy"] == pytest.approx(3 / 4)
    assert partial_wait >= MAX_WAIT_MS - 0.01, (  # the snapshot rounds to µs
        f"partial batch flushed after {partial_wait:.1f} ms — "
        f"before the {MAX_WAIT_MS:.0f} ms window"
    )
    assert full_wait < partial_wait


def test_backpressure_rejects_at_max_queue(served):
    """With the batcher not yet running, the queue fills to MAX_QUEUE and
    the next submit is rejected with a retry-after hint; starting the
    engine then serves everything that was admitted."""
    model, variables = served
    eng = Engine(
        model, variables, IM, max_batch=1, max_wait_ms=1.0, max_queue=4,
        input_dtype=np.float32,
    )
    images = _float_images(5, seed=4)
    futs = [eng.submit(img) for img in images[:4]]
    with pytest.raises(QueueFullError) as ei:
        eng.submit(images[4])
    assert ei.value.retry_after_ms > 0
    assert ei.value.depth == 4
    eng.start()
    for f in futs:
        assert f.result().shape == (NC,)
    eng.drain()


def test_rejections_and_raw_occupancy_are_counted(served):
    """``stats()["rejected"]`` counts every ``QueueFullError`` of ``submit``
    (it read 0 whatever happened: ``record_rejection`` had no caller), and
    the occupancy ratio's raw counters are in the snapshot, so a reader can
    difference them over a window of its own."""
    model, variables = served
    eng = Engine(
        model, variables, IM, max_batch=2, max_wait_ms=1.0, max_queue=3,
        input_dtype=np.float32,
    )
    images = _float_images(5, seed=6)
    futs = [eng.submit(img) for img in images[:3]]
    for img in images[3:]:
        with pytest.raises(QueueFullError):
            eng.submit(img)
    assert eng.stats()["rejected"] == 2 and eng.stats()["requests"] == 0
    eng.start()
    for f in futs:
        f.result()
    eng.drain()
    stats = eng.stats()
    assert stats["rejected"] == 2 and stats["requests"] == 3
    assert stats["occ_filled"] == 3
    assert stats["occ_slots"] >= stats["occ_filled"]
    assert stats["batch_occupancy"] == pytest.approx(
        stats["occ_filled"] / stats["occ_slots"], abs=1e-4)


def test_graceful_drain_completes_inflight(served):
    model, variables = served
    eng = Engine(
        model, variables, IM, max_batch=2, max_wait_ms=500.0, max_queue=32,
        input_dtype=np.float32,
    )
    eng.start()
    futs = [eng.submit(img) for img in _float_images(5, seed=5)]
    eng.drain()  # must flush the partial tail immediately, not after 500 ms
    for f in futs:
        assert f.result().shape == (NC,)
    with pytest.raises(EngineClosedError):
        eng.submit(_float_images(1, seed=6)[0])
    assert eng.metrics.snapshot()["requests"] == 5


def test_drain_before_start_fails_pending(served):
    model, variables = served
    eng = Engine(model, variables, IM, max_batch=1, max_wait_ms=1.0,
                 input_dtype=np.float32)
    fut = eng.submit(_float_images(1, seed=7)[0])
    eng.drain()
    with pytest.raises(EngineClosedError):
        fut.result(timeout=1)


def test_sigterm_drain_flag():
    """The serve loop's SIGTERM handling follows the preempt pattern:
    handler sets a flag, the accept loop polls it."""
    from distribuuuu_tpu.serve import drain_requested, install_drain, reset_drain

    reset_drain()
    assert not drain_requested()
    install_drain(signals=(signal.SIGUSR1,))
    os.kill(os.getpid(), signal.SIGUSR1)
    deadline = time.time() + 2
    while not drain_requested() and time.time() < deadline:
        time.sleep(0.01)
    assert drain_requested()
    reset_drain()


def test_steady_state_never_recompiles(engine):
    """Startup compiles exactly the configured buckets (the
    compilation-count hook); mixed-size steady-state traffic adds zero."""
    assert engine.n_compiles == len(engine.buckets) == 3
    events_before = len(engine_lib.COMPILE_EVENTS)
    for n in (1, 4, 3, 2, 4, 1, 3):
        futs = [engine.submit(img) for img in _float_images(n, seed=10 + n)]
        for f in futs:
            f.result()
    assert engine.n_compiles == 3
    assert len(engine_lib.COMPILE_EVENTS) == events_before
    assert set(engine._compiled) == {1, 2, 4}


def test_run_batch_roundtrip(served, engine, tmp_path):
    """Batch mode: npy in → logits npy out, equal to the direct eval
    forward; N above MAX_QUEUE exercises the retry/backoff path."""
    model, variables = served
    images = _float_images(6, seed=8)
    src, dst = tmp_path / "in.npy", tmp_path / "out.npy"
    np.save(src, images)
    n = protocol.run_batch(engine, str(src), str(dst))
    assert n == 6
    out = np.load(dst)
    ref = np.asarray(
        jax.jit(lambda v, x: model.apply(v, x, train=False))(variables, images)
    )
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-6)


def test_socket_roundtrip(served):
    """Length-prefixed socket frontend end-to-end: uint8 npy request in,
    JSON logits out, numerically matching the eval forward (uint8 inputs
    take the in-graph normalize path — DATA.DEVICE_NORMALIZE serving)."""
    _tiny_cfg()  # protocol.make_transform reads cfg (IM_SIZEs, normalize)
    model, variables = served
    eng = Engine(
        model, variables, IM, max_batch=2, max_wait_ms=5.0, max_queue=16,
        input_dtype=np.uint8,
    )
    eng.start()
    listener = protocol.open_listener("127.0.0.1", 0)
    port = listener.getsockname()[1]
    stop = threading.Event()
    server = threading.Thread(
        target=protocol.serve_forever,
        args=(eng, listener, stop.is_set),
        kwargs=dict(topk=3, poll_s=0.05),
        daemon=True,
    )
    server.start()
    try:
        img = np.random.default_rng(9).integers(
            0, 256, (IM, IM, 3), dtype=np.uint8
        )
        buf = io.BytesIO()
        np.save(buf, img)
        with socket.create_connection(("127.0.0.1", port), timeout=10) as conn:
            protocol.send_frame(conn, buf.getvalue())
            resp = json.loads(protocol.recv_frame(conn))
            # malformed payload → per-request error, connection survives
            protocol.send_frame(conn, b"not an image")
            err = json.loads(protocol.recv_frame(conn))
    finally:
        stop.set()
        server.join(timeout=10)
    assert "error" not in resp, resp
    assert len(resp["logits"]) == NC
    assert resp["topk"][0] == resp["pred"]
    from distribuuuu_tpu.data.transforms import normalize_in_graph

    ref = np.asarray(
        jax.jit(
            lambda v, x: model.apply(v, normalize_in_graph(x), train=False)
        )(variables, img[None])
    )[0]
    np.testing.assert_allclose(resp["logits"], ref, rtol=1e-5, atol=1e-5)
    assert resp["pred"] == int(np.argmax(ref))
    assert "error" in err
    # serve_forever drained the engine on stop
    with pytest.raises(EngineClosedError):
        eng.submit(img)
