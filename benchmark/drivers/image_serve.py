"""Driver ``image_serve``: open-loop JPEG requests over the loopback socket.

The parent (this file) is the load generator and stays off jax; the server
child (``image_serve_server.py``) runs what ``serve_net.py`` runs and holds
the chip. Requests are seeded JPEG files sent as the protocol's plain image
payload over a pool of persistent connections, due at the instants of a
seeded Poisson process of a FIXED rate (the traffic file's; found once by a
sweep, never searched for here). A request's latency runs from the instant it
was due to its complete response at the client; how late after its due time
it was written to the socket is reported beside it.

``attempted`` = requests due in the window; ``failed`` = refused, errored or
unanswered at the drain deadline. ``correct``: no compilation in the window,
and for a seeded sample of requests the served logits agree with the plain
float32 reference on the same weights and the same decoded image, compared
at the reference's top-5 classes and relative to the spread of its logits
(scores, not class order: random weights tie).
"""

from __future__ import annotations

import collections
import json
import os
import queue
import socket
import subprocess
import sys
import threading
import time

import numpy as np

# The program surface this driver stands on (PERF.md lists it): the wire
# format only. Importing it initializes no jax backend.
from distribuuuu_tpu.serve.protocol import ctrl_request, recv_frame, send_frame

from benchmark.harness import payloads, schedule, stats
from benchmark.harness.clock import Window, now
from benchmark.harness.observation import Observation

SERVER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "image_serve_server.py")


class Server:
    """The server child and the control pipe to it."""

    def __init__(self, argv: list):
        self.process = subprocess.Popen(
            [sys.executable, SERVER, *argv], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True,
        )

    def hear(self, event: str) -> dict:
        line = self.process.stdout.readline()
        if not line:
            raise RuntimeError(
                f"the server child ended (exit {self.process.wait()}) before "
                f"{event!r}; its own output is above"
            )
        message = json.loads(line)
        if message["event"] != event:
            raise RuntimeError(f"expected {event!r} from the server, got {message}")
        return message

    def ask(self, op: str, event: str, **fields) -> dict:
        self.process.stdin.write(json.dumps({"op": op, **fields}) + "\n")
        self.process.stdin.flush()
        return self.hear(event)

    def stop(self) -> int:
        """Ask the child to leave, wait for it, and end it if it will not."""
        try:
            if self.process.poll() is None:
                self.process.stdin.write(json.dumps({"op": "exit"}) + "\n")
                self.process.stdin.flush()
            return self.process.wait(timeout=90)
        except (OSError, subprocess.TimeoutExpired):
            self.process.kill()
            return self.process.wait()


class Pool:
    """Persistent connections, one worker thread each. A due request goes to
    whichever worker is idle; the pool is large enough that none waits (the
    lateness metric would show it)."""

    def __init__(self, port: int, size: int, keep_logits: set):
        self.jobs = queue.Queue()
        self.done = []  # (index, due, sent, finished, outcome, logits|None)
        self._keep = keep_logits
        self._lock = threading.Lock()
        self._socks = [
            socket.create_connection(("127.0.0.1", port)) for _ in range(size)
        ]
        for s in self._socks:
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._threads = [
            threading.Thread(target=self._work, args=(s,), daemon=True)
            for s in self._socks
        ]
        for t in self._threads:
            t.start()

    def _work(self, sock) -> None:
        while True:
            job = self.jobs.get()
            if job is None:
                return
            index, due, payload = job
            sent = finished = None
            logits = None
            try:
                send_frame(sock, payload)
                sent = now()
                raw = recv_frame(sock)
                finished = now()
                reply = json.loads(raw) if raw else {"error": "closed"}
                outcome = reply.get("error", "ok")
                if outcome == "ok" and index in self._keep:
                    logits = reply["logits"]
            except (OSError, ValueError) as e:
                outcome = f"{type(e).__name__}"
            with self._lock:
                self.done.append((index, due, sent, finished, outcome, logits))

    def close(self, timeout: float) -> None:
        for _ in self._threads:
            self.jobs.put(None)
        for t in self._threads:
            t.join(timeout)
        for s in self._socks:
            s.close()


def engine_stats(port: int) -> dict:
    """The program's own counters over the wire (``stats`` control op)."""
    with socket.create_connection(("127.0.0.1", port)) as sock:
        send_frame(sock, ctrl_request("stats"))
        return json.loads(recv_frame(sock))


def offer(pool: Pool, due_s, order, made, t0: float) -> None:
    """The open loop: hand each request to the pool at its due instant."""
    for index, due in enumerate(due_s):
        wait = t0 + due - now()
        if wait > 0:
            time.sleep(wait)
        pool.jobs.put((index, t0 + due, made[order[index]]))


def agreement(sample: dict, reference: dict, order, tolerance: float):
    """Worst served-vs-reference difference over the sample, at the
    reference's top-5 classes, relative to the spread of its logits."""
    worst = 0.0
    for index, served in sample.items():
        want = np.asarray(reference[str(order[index])], np.float64)
        got = np.asarray(served, np.float64)
        top = np.argsort(want)[-5:]
        scale = max(float(want.std()), 1e-6)
        worst = max(worst, float(np.abs(got[top] - want[top]).max()) / scale)
    return worst, worst <= tolerance


def run(run) -> Observation:
    traffic = run.traffic
    server = Server(run.argv)  # boots while the payloads are made
    try:
        made = payloads.jpeg_payloads(run.seed, **traffic["payloads"])
        sizes = [len(p) for p in made]
        run.say(
            f"payloads: {len(made)} JPEG files, {min(sizes)}-{max(sizes)} "
            f"bytes, median {int(stats.median(sizes))}"
        )
        ready = server.hear("ready")
        run.admit_device(ready["platform"], ready["kind"], ready["count"])
        run.say(f"server ready: buckets {ready['buckets']}, set-up compiles "
                f"{ready['setup_compiles']}")
        port = ready["port"]

        due_s = schedule.poisson_due_times(run.seed, traffic["rate_per_s"], run.seconds)
        order = schedule.payload_order(run.seed, len(due_s), len(made))
        rng = np.random.default_rng([run.seed, 3])
        sampled = set(
            rng.choice(len(due_s), min(traffic["reference_sample"], len(due_s)),
                       replace=False).tolist()
        )
        pool = Pool(port, traffic["connections"], sampled)

        # warm the path end to end (sockets, decode, every thread), closed loop
        for i in range(traffic["warmup_requests"]):
            pool.jobs.put((-1, now(), made[i % len(made)]))
        while len(pool.done) < traffic["warmup_requests"]:
            time.sleep(0.01)
        del pool.done[:]

        before = engine_stats(port)
        server.ask("open", "opened")
        run.open_window()
        window = Window(run.seconds)
        t0 = window.open()
        tracer = None
        if run.trace:
            def trace_part():
                time.sleep(traffic["trace_after_s"])
                server.ask("trace_start", "tracing")
                time.sleep(traffic["trace_seconds"])
                return server.ask("trace_stop", "traced")

            traced = {}
            tracer = threading.Thread(
                target=lambda: traced.update(trace_part()), daemon=True
            )
            tracer.start()
        offer(pool, due_s, order, made, t0)
        # the window is the schedule's: it ends run.seconds after it opened
        wait = t0 + run.seconds - now()
        if wait > 0:
            time.sleep(wait)
        window.close()
        # drain: what is still in flight may finish, up to the deadline
        deadline = now() + traffic["drain_s"]
        while len(pool.done) < len(due_s) and now() < deadline:
            time.sleep(0.005)
        after = engine_stats(port)
        if tracer is not None:
            tracer.join(timeout=60)
        closed = server.ask("close", "closed")
        done = list(pool.done)
        pool.close(timeout=2.0)

        sample = {d[0]: d[5] for d in done if d[5] is not None}
        reference = server.ask(
            "reference", "reference",
            payload_ids=sorted({int(order[i]) for i in sample}),
        )["logits"] if sample else {}
    finally:
        code = server.stop()
    if code:
        raise RuntimeError(f"the server child exited with {code}")

    limit_s = traffic["latency_limit_ms"] / 1e3
    ok = [d for d in done if d[4] == "ok"]
    latency = [schedule.latency_s(d[1], d[3]) for d in ok]
    late = [schedule.late_s(d[1], d[2]) for d in done if d[2] is not None]
    good = sum(1 for x in latency if x <= limit_s)
    outcomes = dict(collections.Counter(d[4] for d in done))
    outcomes["unanswered"] = len(due_s) - len(done)
    tolerance = run.section("serve")["reference_tolerance"]
    worst, agrees = (
        agreement(sample, reference, order, tolerance) if sample
        else (float("nan"), False)
    )
    run.say(
        f"window: {len(due_s)} requests due in {run.seconds:.1f} s at "
        f"{traffic['rate_per_s']}/s; outcomes {outcomes}; within "
        f"{traffic['latency_limit_ms']} ms: {good}"
    )
    end_to_end = {}
    if latency:
        p50, p99 = stats.percentile(latency, 0.5), stats.percentile(latency, 0.99)
        # a backlog that grows shows as a second half slower than the first
        by_due = [x for _, x in sorted(
            (d[1], schedule.latency_s(d[1], d[3])) for d in ok
        )]
        halves = [by_due[: len(by_due) // 2], by_due[len(by_due) // 2:]]
        run.say(
            f"latency from due time over {len(latency)} samples: p50 "
            f"{p50 * 1e3:.3f} ms, p99 {p99 * 1e3:.3f} ms with "
            f"{stats.samples_beyond(latency, 0.99)} samples beyond it; p50 of "
            f"the first half {stats.median(halves[0]) * 1e3:.3f} ms, of the "
            f"second {stats.median(halves[1]) * 1e3:.3f} ms; engine queue at "
            f"close {after.get('queue_depth')}; generator lateness p99 "
            f"{stats.percentile(late, 0.99) * 1e3:.3f} ms"
        )
        end_to_end = {
            "serve_goodput_per_s_per_chip": good / window.elapsed,
            "serve_latency_ms_p50": p50 * 1e3,
            "serve_latency_ms_p99": p99 * 1e3,
        }
    run.say(
        f"reference: worst served-vs-float32 difference over {len(sample)} "
        f"sampled requests {worst:.5f} of the logits' spread (tolerance "
        f"{tolerance}): "
        f"{'agrees' if agrees else 'DISAGREES'}"
    )
    counters = {
        "compiles_in_window": closed["compiles_in_window"],
        "stats_before": before,
        "stats_after": after,
        "late_s": late,
        "client_latency_p50_ms": end_to_end.get("serve_latency_ms_p50"),
    }
    return Observation(
        correct=bool(agrees),
        attempted=len(due_s),
        failed=len(due_s) - len(ok),
        end_to_end=end_to_end,
        counters=counters,
        device={
            "platform": ready["platform"], "kind": ready["kind"],
            "count": ready["count"],
            "memory_peak_bytes": closed["memory_peak_bytes"],
            "memory_limit_bytes": closed["memory_limit_bytes"],
        },
        trace_path=traced.get("path") if run.trace else None,
    )
