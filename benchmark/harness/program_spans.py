"""The program's own spans in a profiler trace: ``dtpu.*`` host annotations.

``distribuuuu_tpu.telemetry.spans`` writes every program span as a
``jax.profiler.TraceAnnotation`` named ``dtpu.<layer>.<name>``, so a capture
of a run through ``trainer.train_model`` or ``serve_net.py`` holds them on the
device's clock, beside ``/device:TPU:<n>``'s operations. ``trace.load_events``
keeps only the benchmark's own ``bench.*`` annotations; this module is the
loader for the program's, and the two reductions a host-fed cell needs:

* :meth:`ProgramSpans.totals`: per span name the count, the total seconds and
  the self seconds (total less the spans nested inside it on the same
  thread), over a window;
* :meth:`ProgramSpans.idle_gaps`: the device-idle gaps of a
  ``trace.Reduction``, each attributed to the program span that covers most
  of it (what the host was doing while the device waited).

Like ``trace.py``: one function touches the ``.xplane.pb``, the rest is pure
Python over plain dicts, tested on synthetic events and pinned on a recorded
trace (``fixtures/``).
"""

from __future__ import annotations

import collections

from benchmark.harness import trace

PROGRAM_PREFIX = "dtpu."
NO_SPAN = "host: no span of the program"


def load_spans(path: str, prefix: str = PROGRAM_PREFIX) -> list[dict]:
    """Host events whose names start with ``prefix``, from an ``.xplane.pb``
    (or ``.xplane.pb.gz``), as ``{"name", "thread", "start_ns", "dur_ns"}``
    on the clock the device planes share."""
    from jax.profiler import ProfileData

    with trace._open(path) as f:
        data = ProfileData.from_serialized_xspace(f.read())
    spans = []
    for plane in data.planes:
        if plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    spans.append({
                        "name": ev.name,
                        "thread": line.name,
                        "start_ns": float(ev.start_ns),
                        "dur_ns": float(ev.duration_ns),
                    })
    return spans


def _end(span: dict) -> float:
    return span["start_ns"] + span["dur_ns"]


class ProgramSpans:
    """The reductions over the program's spans of one capture."""

    def __init__(self, spans: list[dict]):
        self.spans = sorted(spans, key=lambda s: (s["start_ns"], -s["dur_ns"]))

    @classmethod
    def from_file(cls, path: str):
        return cls(load_spans(path))

    def names(self) -> list[str]:
        return sorted({s["name"] for s in self.spans})

    def totals(self, lo: float | None = None, hi: float | None = None) -> dict:
        """``{name: {"count", "total_s", "self_s"}}`` of the spans that start
        in ``[lo, hi)`` (every span when the window is left out). Self time
        is a span's duration less its direct children's: the spans nested
        inside it on the same thread."""
        child_ns = collections.Counter()  # id(span) -> its direct children's time
        by_thread = collections.defaultdict(list)
        for s in self.spans:
            by_thread[s["thread"]].append(s)
        for spans in by_thread.values():
            stack = []  # open spans, outermost first; self.spans is sorted
            for s in spans:
                while stack and _end(stack[-1]) <= s["start_ns"]:
                    stack.pop()
                if stack:
                    child_ns[id(stack[-1])] += s["dur_ns"]
                stack.append(s)
        out = collections.defaultdict(
            lambda: {"count": 0, "total_s": 0.0, "self_s": 0.0}
        )
        for s in self.spans:
            if (lo is not None and s["start_ns"] < lo) or (
                hi is not None and s["start_ns"] >= hi
            ):
                continue
            row = out[s["name"]]
            row["count"] += 1
            row["total_s"] += s["dur_ns"] / 1e9
            row["self_s"] += (s["dur_ns"] - child_ns[id(s)]) / 1e9
        return dict(out)

    def covering(self, start: float, end: float) -> str:
        """The span that covers most of ``[start, end)``; of two that cover
        it equally (one nested in the other), the inner one."""
        best, best_key = NO_SPAN, (0.0, 0.0)
        for s in self.spans:
            cover = min(end, _end(s)) - max(start, s["start_ns"])
            if cover > 0 and (cover, -s["dur_ns"]) > best_key:
                best, best_key = s["name"], (cover, -s["dur_ns"])
        return best

    def idle_gaps(self, reduction, n: int, lo: float | None = None,
                  hi: float | None = None) -> list:
        """The ``n`` longest device-idle gaps of ``reduction``'s first device
        (the one whose dispatch the host's spans describe) as
        ``[program span, seconds]``, longest first. The window is ``[lo,
        hi)``, by default first operation start to last operation end."""
        return [
            [self.covering(s, e), (e - s) / 1e9]
            for s, e in device_gaps(reduction, lo, hi)[:n]
        ]


def device_window(reduction) -> tuple[float, float]:
    """First operation start to last operation end on the first device."""
    ops = reduction.ran(reduction.devices[0])
    return (min(e["start_ns"] for e in ops),
            max(e["start_ns"] + e["dur_ns"] for e in ops))


def device_gaps(reduction, lo: float | None = None,
                hi: float | None = None) -> list:
    """Maximal intervals of ``[lo, hi)`` with no operation on the first
    device of a ``trace.Reduction``, longest first."""
    if not reduction.devices:
        return []
    first, last = device_window(reduction)
    lo = first if lo is None else lo
    hi = last if hi is None else hi
    ops = reduction.ran(reduction.devices[0])
    _, merged = trace.interval_union(
        (max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi))
        for e in ops if e["start_ns"] < hi and e["start_ns"] + e["dur_ns"] > lo
    )
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    gaps = [
        (edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
        if edges[i + 1] > edges[i]
    ]
    return sorted(gaps, key=lambda g: g[0] - g[1])
