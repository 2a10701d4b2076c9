"""Length-prefixed socket frontend + one-shot batch mode for serve_net.

Wire format: every frame is a 4-byte big-endian payload length followed by
the payload. Request payloads, auto-detected:

* ``.npy`` bytes (numpy magic ``\\x93NUMPY``) holding an (H, W, 3) uint8
  image — decoded without a PIL round-trip;
* a ``(TRAIN.IM_SIZE, TRAIN.IM_SIZE, 3)`` float32 ``.npy`` — treated as
  ALREADY val-transformed (the engine's float input path) and submitted
  as-is;
* anything else — an encoded image file (JPEG/PNG/…, PIL-decodable).

Raw images get the SAME val transform pipeline evaluation uses (shorter
side to ``TEST.IM_SIZE``, center-crop ``TRAIN.IM_SIZE``, normalization
placement per ``DATA.DEVICE_NORMALIZE`` — data/transforms.py), so a
served prediction is bit-for-bit the offline ``test_net.py`` prediction
for the same file.

Response payload: JSON — ``{"pred", "topk", "logits"}`` on success;
``{"error": ..., "retry_after_ms"?}`` on rejection/failure (backpressure
maps to ``"queue_full"`` + retry hint, drain to ``"draining"``).

Batch mode (``run_batch``) bypasses the socket: a ``.npy`` of N
val-transformed images in (file or stdin), an ``(N, num_classes)`` float32
logits ``.npy`` out (file or stdout) — the CI-testable path.
"""

from __future__ import annotations

import io
import json
import os
import socket
import struct
import sys
import threading
import time

import numpy as np

from distribuuuu_tpu.config import cfg
from distribuuuu_tpu.serve.admission import EngineClosedError, QueueFullError
from distribuuuu_tpu.telemetry import tracectx

_NPY_MAGIC = b"\x93NUMPY"
MAX_FRAME = 64 << 20  # refuse absurd frames before allocating for them

# Control frames: a payload starting with this magic is a JSON control
# request, not an image. The fleet layer (serve/fleet/) uses op="stats" as
# the replica health/load endpoint — the pool's warm-up gate and health
# probes, and the router's queue-depth/occupancy reads, all ride the same
# length-prefixed connection clients use. The leading NUL byte cannot
# occur in any image or .npy payload, so detection is unambiguous.
CTRL_MAGIC = b"\x00DTPUCTL1"

# Model-id envelope (serve/campaign, multi-model fleets): magic, a 1-byte
# model-id length, the utf-8 model id, then the ORIGINAL request payload
# unchanged. Shares the NUL lead byte with control frames (unambiguous vs
# image payloads) but differs from CTRL_MAGIC at byte 5, so parse_ctrl
# rejects it and bare payloads keep their existing single-model meaning.
# The router strips the envelope before forwarding — replicas serve the
# same bytes they always did.
MODEL_MAGIC = b"\x00DTPUMDL1"

# Request-trace envelope (ISSUE 20): binary data payloads of TRACED
# requests ride ``tracectx.TRACE_MAGIC + u16 len + ctx JSON + payload``,
# OUTERMOST (a traced multi-model request is TRACE(MODEL(payload))).
# Same NUL-lead disambiguation as the other two magics; untraced
# payloads are byte-identical to the pre-tracing wire format. Traced
# ``op="generate"`` ctrl frames instead embed ``"trace": {...}`` in the
# ctrl JSON — peers that predate tracing ignore the extra key.


def ctrl_request(op: str, **fields) -> bytes:
    """Encode a control request payload (send it with ``send_frame``)."""
    return CTRL_MAGIC + json.dumps({"op": op, **fields}).encode()


def parse_ctrl(payload: bytes) -> dict | None:
    """The decoded control request, or None for a data (image) payload."""
    if not payload.startswith(CTRL_MAGIC):
        return None
    return json.loads(payload[len(CTRL_MAGIC):])


def model_envelope(model: str, payload: bytes) -> bytes:
    """Wrap a request payload with the model id it must route to."""
    mid = model.encode("utf-8")
    if not 0 < len(mid) < 256:
        raise ValueError(f"model id must be 1..255 utf-8 bytes, got {model!r}")
    return MODEL_MAGIC + bytes([len(mid)]) + mid + payload


def split_model_envelope(payload: bytes) -> tuple[str | None, bytes]:
    """(model_id, inner_payload) for an enveloped payload; (None, payload)
    for a bare one — single-model clients never change."""
    if not payload.startswith(MODEL_MAGIC):
        return None, payload
    n = payload[len(MODEL_MAGIC)]
    start = len(MODEL_MAGIC) + 1
    mid = payload[start:start + n]
    if len(mid) != n:
        raise ValueError("truncated model envelope")
    return mid.decode("utf-8"), payload[start + n:]


def engine_device(engine) -> str:
    """Which device(s) hold the engine's weights, as jax names them, plus
    the chip the fleet pool handed this process (``TPU_VISIBLE_CHIPS``:
    every one-chip replica sees its own chip as local device 0)."""
    import jax

    devs = sorted(
        jax.tree.leaves(engine._variables)[0].devices(), key=lambda d: d.id
    )
    chip = os.environ.get("TPU_VISIBLE_CHIPS")
    return ",".join(str(d) for d in devs) + (
        f" (chip {chip})" if chip is not None else ""
    )


def replica_stats(engine) -> dict:
    """The replica-side stats snapshot a ``ctrl_request("stats")`` returns:
    the engine's metrics/queue view plus the process-global ``jit.compiles``
    counter (telemetry/runtime.py's compile listener) — how the fleet
    asserts zero steady-state recompiles across every replica — and the
    device the replica runs on."""
    from distribuuuu_tpu.telemetry import registry as telemetry_registry

    reg = telemetry_registry.get_registry()
    out = engine.stats()
    out.update(
        pid=os.getpid(),
        device=engine_device(engine),
        accepting=engine._admission.is_open,
        jit_compiles=int(reg.counter("jit.compiles").value),
        aot_compiles=int(reg.counter("serve.aot_compiles").value),
    )
    return out


# -- framing ----------------------------------------------------------------

def send_frame(sock: socket.socket, payload: bytes) -> None:
    sock.sendall(struct.pack(">I", len(payload)) + payload)


def _recv_exact(sock: socket.socket, n: int) -> bytes | None:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None  # peer closed
        buf += chunk
    return buf


def recv_frame(sock: socket.socket) -> bytes | None:
    """One frame's payload, or None on clean EOF."""
    hdr = _recv_exact(sock, 4)
    if hdr is None:
        return None
    (n,) = struct.unpack(">I", hdr)
    if n > MAX_FRAME:
        raise ValueError(f"frame of {n} bytes exceeds MAX_FRAME={MAX_FRAME}")
    return _recv_exact(sock, n)


# -- request decoding -------------------------------------------------------

def make_transform():
    """The val pipeline as a payload→engine-input function, captured from
    the global cfg (same geometry/normalization the val loader uses)."""
    from PIL import Image

    from distribuuuu_tpu.data.transforms import val_transform

    resize, crop = cfg.TEST.IM_SIZE, cfg.TRAIN.IM_SIZE
    normalize = not cfg.DATA.DEVICE_NORMALIZE

    def transform(payload: bytes) -> np.ndarray:
        if payload[: len(_NPY_MAGIC)] == _NPY_MAGIC:
            arr = np.load(io.BytesIO(payload), allow_pickle=False)
            if (
                arr.dtype == np.float32
                and arr.shape == (crop, crop, 3)
            ):
                return arr  # pre-transformed: the engine's float input path
            if arr.dtype != np.uint8 or arr.ndim != 3 or arr.shape[-1] != 3:
                raise ValueError(
                    f"npy request must be (H, W, 3) uint8 raw or "
                    f"({crop}, {crop}, 3) float32 pre-transformed, got "
                    f"{arr.shape} {arr.dtype}"
                )
            img = Image.fromarray(arr)
        else:
            img = Image.open(io.BytesIO(payload)).convert("RGB")
        return val_transform(img, resize, crop, normalize=normalize)

    return transform


# -- socket server ----------------------------------------------------------

def open_listener(host: str, port: int) -> socket.socket:
    """Bound+listening socket (port 0 ⇒ ephemeral; read
    ``sock.getsockname()[1]`` for the real port)."""
    sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    sock.bind((host, port))
    sock.listen(128)
    return sock


def _handle_conn(engine, conn: socket.socket, transform, topk: int) -> None:
    with conn:
        while True:
            try:
                payload = recv_frame(conn)
            except (OSError, ValueError):
                return
            if payload is None:
                return
            trace = None
            if payload.startswith(tracectx.TRACE_MAGIC):
                # traced binary payload: strip the context so the inner
                # bytes the engine sees are exactly the untraced bytes; a
                # torn envelope gets a clean refusal, never a half-parse
                try:
                    trace, payload = tracectx.split_payload(payload)
                except ValueError:
                    try:
                        send_frame(conn, json.dumps(
                            {"error": "bad_trace_envelope"}
                        ).encode())
                    except OSError:
                        return
                    continue
            if payload.startswith(MODEL_MAGIC):
                # a fleet router already routed this here; a direct client
                # may also send enveloped requests — either way the replica
                # serves the inner payload (it IS the model)
                try:
                    _model, payload = split_model_envelope(payload)
                except (ValueError, IndexError):
                    try:
                        send_frame(conn, json.dumps(
                            {"error": "bad_model_envelope"}
                        ).encode())
                    except OSError:
                        return
                    continue
            ctrl = parse_ctrl(payload) if payload.startswith(CTRL_MAGIC[:1]) else None
            if ctrl is not None:
                if ctrl.get("op") == "stats":
                    resp = replica_stats(engine)
                elif ctrl.get("op") == "generate":
                    # the LM generation plane's STREAMING ctrl frame
                    # (lm/service.py): one token frame per decode step on
                    # this same connection, a done frame last — the fleet
                    # router relays the whole sequence
                    if not hasattr(engine, "submit") or not hasattr(
                        engine, "prompt_len"
                    ):
                        resp = {
                            "error": "not_a_generation_replica",
                            "detail": "this replica serves an image arch; "
                                      "generate needs a gpt_* MODEL.ARCH",
                        }
                    else:
                        from distribuuuu_tpu.lm import service as lm_service

                        try:
                            lm_service.handle_generate(
                                engine, ctrl,
                                lambda p: send_frame(conn, p),
                            )
                        except OSError:
                            return
                        continue
                else:
                    resp = {"error": f"unknown control op {ctrl.get('op')!r}"}
                try:
                    send_frame(conn, json.dumps(resp).encode())
                except OSError:
                    return
                continue
            t_req = time.perf_counter()
            try:
                fut = engine.submit(transform(payload))
                logits = fut.result()
                order = np.argsort(logits)[::-1][: max(1, topk)]
                resp = {
                    "pred": int(order[0]),
                    "topk": [int(i) for i in order],
                    "logits": [float(v) for v in logits],
                }
            except QueueFullError as e:
                resp = {
                    "error": "queue_full",
                    "retry_after_ms": round(e.retry_after_ms, 1),
                }
            except EngineClosedError:
                resp = {"error": "draining"}
            except Exception as e:  # noqa: BLE001 — per-request fault isolation
                resp = {"error": f"{type(e).__name__}: {e}"}
            tracectx.emit_trace_span(
                trace, "replica.handle", t_req,
                time.perf_counter() - t_req,
                ok=("error" not in resp),
            )
            try:
                send_frame(conn, json.dumps(resp).encode())
            except OSError:
                return


def serve_forever(
    engine,
    listener: socket.socket,
    should_stop,
    topk: int = 5,
    poll_s: float = 0.25,
) -> None:
    """Accept loop: one handler thread per connection, requests multiplexed
    through the shared engine. Polls ``should_stop()`` (the SIGTERM drain
    flag, admission.drain_requested) between accepts; on stop it closes the
    listener, drains the engine (every accepted request completes), and
    joins the handlers — the graceful-exit half of preemption handling."""
    transform = make_transform()
    listener.settimeout(poll_s)
    handlers: list[threading.Thread] = []
    try:
        while not should_stop():
            try:
                conn, _addr = listener.accept()
            except socket.timeout:
                continue
            t = threading.Thread(
                target=_handle_conn,
                args=(engine, conn, transform, topk),
                daemon=True,
            )
            t.start()
            handlers.append(t)
    finally:
        listener.close()
        engine.drain()
        for t in handlers:
            t.join(timeout=5.0)


# -- batch mode -------------------------------------------------------------

def run_batch(engine, in_path: str, out_path: str) -> int:
    """One-shot batch mode: ``.npy`` images in, ``.npy`` logits out
    ('-' = stdin/stdout). Input must be (N, IM, IM, 3) in the engine's
    input dtype (val-transformed). Submits through the normal admission/
    batching path — backpressure is honored by waiting out the retry
    hint, so N may exceed SERVE.MAX_QUEUE. Returns N."""
    src = sys.stdin.buffer if in_path == "-" else in_path
    images = np.load(src, allow_pickle=False)
    if images.ndim != 4:
        raise ValueError(f"batch input must be (N, H, W, 3), got {images.shape}")
    futs = []
    for row in images:
        while True:
            try:
                futs.append(engine.submit(row))
                break
            except QueueFullError as e:  # back off as a client would
                time.sleep(e.retry_after_ms / 1e3)
    logits = np.stack([f.result() for f in futs]).astype(np.float32)
    if out_path == "-":
        np.save(sys.stdout.buffer, logits)
        sys.stdout.buffer.flush()
    else:
        np.save(out_path, logits)
    return len(images)
