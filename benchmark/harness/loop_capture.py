"""Capture and reduction for a driver that runs the program's own loop.

A host-fed driver (``drivers/train_loop.py``) calls ``trainer.train_epoch``,
whose spans are the program's (``dtpu.<layer>.<name>``, on the profiler's
clock since PR 24), on several threads: the loop's, and the loader's workers.
Two things differ from ``harness/profiler.py`` and ``program_spans.load_spans``,
neither of which this file edits:

* :func:`capture` starts the profiler with the Python tracer OFF and the host
  tracer on: under the default Python tracer every call of the loop becomes
  an event. That is necessary and, for a loop that ships ``uint8`` NHWC
  batches, not sufficient (next paragraph but one).
* :func:`load_capture` tells threads apart. A host line of this
  installation's trace is named after the process (``python3``) whatever
  thread it holds, so ``program_spans.load_spans`` puts the workers' spans on
  the loop's "thread" and self time would count them as the loop's children.
  Here a line's ordinal is part of its thread's name.

What the tracer still costs such a loop (PERF.md section 6, PR 35): PJRT
lays a ``uint8`` NHWC batch out for the device tile by tile on the host, and
each of a batch's ~400,000 tiles is a host event of the same level as the
program's annotations. A capture of ``resnet50.trainloop_hostfed`` runs at
240 ms a step against the window's 48 and leaves the device 80 % idle
whatever the options, so every span-sourced value of THAT traffic
(``idle_by_cause``, the spans' totals) and its ``device.idle_frac`` describe
the capture, not the loop; the window's numbers come from the registry
counters, which need no capture. The same batch shipped as ``[B, H*W*C]``
and reshaped on the device leaves 8 host events a transfer and runs under a
capture as without one (1.70 against 1.7-2.1 ms a transfer, PR 35's probe).

:func:`reduce_loop` is what the driver puts into its observation's counters
for the ``trainer.*`` / ``loader.*`` readers, so that those stay pure
functions of ``Observed``: the spans' totals over the traced window and the
window's device-idle time by cause. Pure Python over plain dicts, tested on
synthetic events and on ``fixtures/trainloop_spans.xplane.pb.gz``.
"""

from __future__ import annotations

import contextlib
import glob
import os
import shutil

from benchmark.harness import program_spans, trace

TRAINER = program_spans.PROGRAM_PREFIX + "trainer."
LOADER = program_spans.PROGRAM_PREFIX + "loader."
EPOCH, WAIT, H2D, STEP, FETCH = (
    TRAINER + name for name in ("epoch", "wait", "h2d", "step", "metrics_fetch")
)
DECODE, ASSEMBLE = LOADER + "decode", LOADER + "assemble"
# device-idle time of the traced window, by what the loop's thread was doing;
# a moment under two of them counts for the first
CAUSES = ("fence", "wait", "h2d", "step", "unattributed")


@contextlib.contextmanager
def capture(trace_dir: str):
    """``profiler.capture`` with the Python tracer off: yields a dict whose
    ``"path"`` is the ``.xplane.pb`` once the block has ended."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2  # TraceAnnotations: bench.* and dtpu.*
    out = {"path": None}
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under {trace_dir}")
    out["path"] = max(found, key=os.path.getmtime)


def load_capture(path: str, op_names: dict | None = None,
                 prefix: str = program_spans.PROGRAM_PREFIX) -> tuple[list, list]:
    """``(events, spans)`` of one capture in ONE pass over the file: what
    ``trace.load_events`` returns (for ``trace.Reduction``), and the host
    events whose names start with ``prefix`` as ``program_spans.load_spans``
    returns them, but with one thread name a host line, ``<line
    name>#<ordinal of the line in its plane>``. One pass matters here: a
    capture of a host-fed loop holds ~400,000 host events a batch (PJRT
    traces every tile it transposes for the transfer), so the file is read
    once."""
    from jax.profiler import ProfileData

    with trace._open(path) as f:
        data = ProfileData.from_serialized_xspace(f.read())
    op_names = op_names or {}
    events, spans = [], []
    for plane in data.planes:
        on_device = plane.name.startswith(trace.DEVICE_PLANE_PREFIX)
        for ordinal, line in enumerate(plane.lines):
            if on_device and line.name not in (trace.OPS_LINE, trace.ASYNC_LINE):
                continue
            for ev in line.events:
                name = ev.name
                if on_device:
                    name, opcode = trace.parse_instruction(name)
                elif name.startswith(trace.SPAN_PREFIX):
                    opcode = ""
                elif name.startswith(prefix):
                    spans.append({
                        "name": name, "thread": f"{line.name}#{ordinal}",
                        "start_ns": float(ev.start_ns),
                        "dur_ns": float(ev.duration_ns),
                    })
                    continue
                else:
                    continue
                events.append({
                    "plane": plane.name if on_device else "host",
                    "line": line.name, "name": name, "opcode": opcode,
                    "op_name": op_names.get(name, ""),
                    "start_ns": float(ev.start_ns),
                    "dur_ns": float(ev.duration_ns),
                })
    return events, spans


def load_spans(path: str, prefix: str = program_spans.PROGRAM_PREFIX) -> list[dict]:
    """The program's spans of a capture alone (:func:`load_capture`)."""
    return load_capture(path, prefix=prefix)[1]


# ------------------------------------------------------------ pure arithmetic
def minus(merged_a, merged_b) -> list:
    """``merged_a`` less ``merged_b``, both merged interval lists."""
    out, j = [], 0
    for s, e in merged_a:
        while j < len(merged_b) and merged_b[j][1] <= s:
            j += 1
        k = j
        while k < len(merged_b) and merged_b[k][0] < e:
            if merged_b[k][0] > s:
                out.append([s, merged_b[k][0]])
            s = max(s, merged_b[k][1])
            k += 1
        if s < e:
            out.append([s, e])
    return out


def window_of(reduction) -> tuple[float, float] | None:
    """The traced window as ``trace.Reduction`` finds it: the longest
    ``bench.window`` span, else first operation start to last operation end;
    None where the trace holds neither."""
    marks = [s for s in reduction.spans if s["name"] == trace.WINDOW_SPAN]
    if marks:
        s = max(marks, key=lambda m: m["dur_ns"])
        return s["start_ns"], s["start_ns"] + s["dur_ns"]
    return program_spans.device_window(reduction) if reduction.devices else None


def _intervals(spans, *names):
    """The merged intervals of the spans so named."""
    return trace.interval_union(
        (s["start_ns"], s["start_ns"] + s["dur_ns"])
        for s in spans if s["name"] in names
    )[1]


def fence_intervals(spans, hi: float) -> list:
    """From the start of each ``metrics_fetch`` (the loop's only fence: the
    device drains what was dispatched ahead) to the start of the next
    ``step``, whose dispatch is what refills it; to ``hi`` after the last."""
    steps = sorted(s["start_ns"] for s in spans if s["name"] == STEP)
    out = []
    for f in (s for s in spans if s["name"] == FETCH):
        end = f["start_ns"] + f["dur_ns"]
        out.append((f["start_ns"], next((t for t in steps if t >= end), hi)))
    return trace.interval_union(out)[1]


def idle_by_cause(spans, reduction) -> dict | None:
    """Seconds of the traced window in which no operation ran on the first
    device, by :data:`CAUSES`, with ``"window"`` and ``"idle"`` (their sum).
    None where the trace holds no device operation."""
    if not reduction.devices:
        return None
    lo, hi = window_of(reduction)
    rest = sorted(
        [s, e] for s, e in program_spans.device_gaps(reduction, lo, hi)
    )
    out = {"window": (hi - lo) / 1e9,
           "idle": sum(e - s for s, e in rest) / 1e9}
    for cause, intervals in (
        ("fence", fence_intervals(spans, hi)), ("wait", _intervals(spans, WAIT)),
        ("h2d", _intervals(spans, H2D)), ("step", _intervals(spans, STEP)),
    ):
        out[cause] = trace.intersection(rest, intervals) / 1e9
        rest = minus(rest, intervals)
    out["unattributed"] = sum(e - s for s, e in rest) / 1e9
    return out


def workers_busy_share_of_wait(spans) -> float | None:
    """The share of the loop's ``wait`` time during which at least one
    worker was inside ``decode`` or ``assemble``: near 1 the loader is too
    slow, near 0 the ring is too shallow (or the epoch has just turned)."""
    waits = _intervals(spans, WAIT)
    total = sum(e - s for s, e in waits)
    if not total:
        return None
    return trace.intersection(waits, _intervals(spans, DECODE, ASSEMBLE)) / total


def reduce_loop(spans: list, reduction) -> dict:
    """What a host-fed driver adds to its counters after the traced epoch:
    ``program_spans`` = ``ProgramSpans.totals()`` over the traced window,
    ``idle_s`` = :func:`idle_by_cause`."""
    return {
        "program_spans": program_spans.ProgramSpans(spans).totals(
            *(window_of(reduction) or ())
        ),
        "idle_s": idle_by_cause(spans, reduction),
    }


def span_ms_per_step(counters: dict, name: str, key: str = "total_s"):
    """``key`` of span ``name`` in ms a traced step; None where the span,
    the reduction or the step count is absent."""
    row = (counters.get("program_spans") or {}).get(name)
    steps = counters.get("trace_steps")
    if not (row and steps):
        return None
    return row[key] * 1e3 / steps


def idle_frac(counters: dict, cause: str):
    """Device-idle seconds under ``cause`` over the traced window; None
    where nothing was traced."""
    idle = counters.get("idle_s")
    if not (idle and idle.get("window")):
        return None
    return idle[cause] / idle["window"]


def per_step_ms(counters: dict, seconds_key: str):
    """A registry counter's seconds over the window, in ms a step of the
    window (``trainer.steps``); None where the program counts neither."""
    seconds, steps = counters.get(seconds_key), counters.get("trainer.steps")
    if seconds is None or not steps:
        return None
    return seconds * 1e3 / steps
