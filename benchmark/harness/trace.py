"""From a profiler trace to metrics: xplane -> events -> reductions.

Two layers. :func:`load_events` is the only code that touches the
``.xplane.pb`` (through ``jax.profiler.ProfileData``, nothing but jax): it
flattens the device planes' operation lines and the benchmark's own host spans
into plain dicts ``{"plane", "line", "name", "opcode", "op_name", "start_ns",
"dur_ns"}`` on one clock. Everything else is pure Python over those dicts
(:class:`Reduction`), tested on synthetic events and pinned on a recorded
trace of this installation (``fixtures/``).

What a trace of this installation (jax 0.9.0, libtpu 0.0.34, v5e) holds: a
plane ``/device:TPU:<n>`` per chip with the lines ``XLA Ops`` (one event per
HLO instruction run; the event's name is the instruction's whole HLO text and
it carries NO name-scope metadata), ``Async XLA Ops`` (the lifetime of each
asynchronous pair, start to done), ``XLA Modules`` and ``Steps`` (envelopes);
host threads under ``/host:CPU``. So the instruction's name and opcode are
parsed from the HLO text, and its ``op_name`` (the ``jax.named_scope`` path)
comes from the compiled program's own HLO text, which the driver saves next
to the trace (:func:`op_names_from_hlo`).

Definitions, per device plane and then averaged over the devices used:

* busy: the union of the intervals in which an operation ran. Module
  envelopes and step markers span their operations and are left out; so are
  asynchronous ``-start``/``-done`` pairs' lifetimes, which overlap compute.
* window: the host span ``bench.window`` where the capture has one, else
  first operation start to last operation end over all devices.
* idle share: 1 - busy / window. An idle gap is a maximal interval of the
  window with no operation on the device; it is attributed to the benchmark's
  host span that covers most of it (what the host was doing meanwhile).
* scope time: the sum of operation durations whose ``op_name`` path holds the
  named scope (``optimizer_update``), autodiff decorations unwrapped. A
  ``while``, ``conditional`` or ``call`` is an event AND so is every operation
  of its body: the sums take the body's operations and leave the enclosing
  event out, so a scanned loop is counted once; busy time is a union and
  takes both (the loop's own control between two body operations is time the
  device was busy).
* collective time: operations whose HLO name is a collective; its exposed
  share is the part of their union during which no other operation ran on
  that device.
"""

from __future__ import annotations

import collections
import gzip
import json
import re

from benchmark.harness.profiler import SPAN_PREFIX

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
WINDOW_SPAN = SPAN_PREFIX + "window"
ENCLOSING = ("while", "conditional", "call")  # each spans its body's operations
COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all",
)
_HLO_OP_NAME = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*metadata=\{[^}]*op_name=\"([^\"]*)\""
)


# ------------------------------------------------------------------- adapter
def parse_instruction(text: str) -> tuple[str, str]:
    """(name, opcode) of an HLO instruction from its text
    ``%name = type opcode(operands), attributes``; a bare name such as
    ``all-reduce.3`` (other backends, synthetic events) gives its stem."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        name = text.lstrip("%")
        return name, name.rsplit(".", 1)[0]
    rest = rest.lstrip()
    if rest.startswith("("):  # a tuple type: skip to its closing parenthesis
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    return head.strip().lstrip("%"), rest.lstrip().partition("(")[0].strip()


def op_names_from_hlo(hlo_text: str) -> dict:
    """instruction name -> ``op_name`` from a compiled program's HLO text."""
    names = {}
    for line in hlo_text.splitlines():
        m = _HLO_OP_NAME.match(line)
        if m:
            names[m.group(1)] = m.group(2)
    return names


def _open(path: str):
    return gzip.open(path, "rb") if path.endswith(".gz") else open(path, "rb")


def load_op_names(path: str | None) -> dict:
    if not path:
        return {}
    with _open(path) as f:
        return json.loads(f.read())


def load_events(path: str, op_names: dict | None = None) -> list[dict]:
    """Operation events of every TPU device plane and the benchmark's host
    spans, from an ``.xplane.pb`` (or ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData

    with _open(path) as f:
        data = ProfileData.from_serialized_xspace(f.read())
    op_names = op_names or {}
    events = []
    for plane in data.planes:
        on_device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            if on_device and line.name not in (OPS_LINE, ASYNC_LINE):
                continue
            for ev in line.events:
                if on_device:
                    name, opcode = parse_instruction(ev.name)
                elif ev.name.startswith(SPAN_PREFIX):
                    name, opcode = ev.name, ""
                else:
                    continue
                events.append({
                    "plane": plane.name if on_device else "host",
                    "line": line.name,
                    "name": name,
                    "opcode": opcode,
                    "op_name": op_names.get(name, ""),
                    "start_ns": float(ev.start_ns),
                    "dur_ns": float(ev.duration_ns),
                })
    return events


# ------------------------------------------------------------ pure arithmetic
def interval_union(intervals):
    """(total measure, merged list) of a set of (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def intersection(merged_a, merged_b) -> float:
    """Measure of the intersection of two merged interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(merged_a) and j < len(merged_b):
        s = max(merged_a[i][0], merged_b[j][0])
        e = min(merged_a[i][1], merged_b[j][1])
        if s < e:
            total += e - s
        if merged_a[i][1] <= merged_b[j][1]:
            i += 1
        else:
            j += 1
    return total


def scope_path(op_name: str) -> list[str]:
    """The components of an ``op_name`` path with autodiff's decorations
    unwrapped: the forward under ``value_and_grad`` shows as ``jvp(fwd)``,
    its backward as ``transpose(jvp(fwd))``."""
    return [
        part.replace("transpose(", "").replace("jvp(", "")
        .replace("vjp(", "").rstrip(")")
        for part in op_name.split("/")
    ]


def in_scope(op_name: str, scope: str) -> bool:
    return scope in scope_path(op_name)


def _stem(opcode: str) -> str:
    for suffix in ("-start", "-done"):
        if opcode.endswith(suffix):
            return opcode[: -len(suffix)]
    return opcode


def is_collective(event: dict) -> bool:
    """A collective, or either half of an asynchronous one."""
    return _stem(event["opcode"]) in COLLECTIVES


class Reduction:
    """The reductions every trace-sourced metric reads."""

    def __init__(self, events: list[dict]):
        self.ops = collections.defaultdict(list)  # plane -> operations run
        # plane -> while / conditional / call events: in the busy union, in no sum
        self.enclosing = collections.defaultdict(list)
        # plane -> asynchronous pairs, start to done: a transfer in flight
        # overlaps compute, so a lifetime is not time the device was busy
        self.lifetimes = collections.defaultdict(list)
        self.spans = []  # the benchmark's host spans
        for ev in events:
            if ev["plane"] == "host":
                self.spans.append(ev)
            elif ev["line"] == ASYNC_LINE:
                self.lifetimes[ev["plane"]].append(ev)
            elif ev["dur_ns"] > 0:
                kind = self.enclosing if ev["opcode"] in ENCLOSING else self.ops
                kind[ev["plane"]].append(ev)
        self.devices = sorted({*self.ops, *self.enclosing})
        self._window = self._find_window()

    @classmethod
    def from_file(cls, path: str, op_names_path: str | None = None):
        return cls(load_events(path, load_op_names(op_names_path)))

    # -- window ---------------------------------------------------------------
    def _find_window(self):
        marks = [s for s in self.spans if s["name"] == WINDOW_SPAN]
        if marks:
            s = max(marks, key=lambda m: m["dur_ns"])
            return s["start_ns"], s["start_ns"] + s["dur_ns"]
        ran = [e for d in self.devices for e in self.ran(d)]
        if not ran:
            return 0.0, 0.0
        return (min(e["start_ns"] for e in ran),
                max(e["start_ns"] + e["dur_ns"] for e in ran))

    def ran(self, d) -> list:
        """Every event in which device ``d`` was busy: its operations and
        the events that enclose some of them."""
        return self.ops[d] + self.enclosing[d]

    def _clipped(self, events):
        """(start, end) of each event, clipped to the window."""
        lo, hi = self._window
        out = []
        for e in events:
            s, t = max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi)
            if s < t:
                out.append((s, t))
        return out

    def _mean_over_devices(self, per_device) -> float:
        values = [per_device(d) for d in self.devices]
        return sum(values) / len(values) if values else 0.0

    def window_s(self) -> float:
        return (self._window[1] - self._window[0]) / 1e9

    # -- busy and idle --------------------------------------------------------
    def busy_s(self) -> float:
        return self._mean_over_devices(
            lambda d: interval_union(self._clipped(self.ran(d)))[0]
        ) / 1e9

    def idle_frac(self) -> float | None:
        if not self.devices or self.window_s() <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s()

    def idle_gaps(self, n: int) -> list:
        """The ``n`` longest device-idle gaps as ``[what the host was doing,
        seconds]``, longest first, over the first device (the one whose
        dispatch the host spans describe)."""
        if not self.devices:
            return []
        lo, hi = self._window
        _, merged = interval_union(self._clipped(self.ran(self.devices[0])))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps = [
            (edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]
        ]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self._host_activity(s, e), (e - s) / 1e9] for s, e in gaps[:n]]

    def _host_activity(self, start: float, end: float) -> str:
        """The benchmark's host span, other than the window's own, that
        covers most of [start, end)."""
        best, best_cover = "host: no span of the benchmark", 0.0
        for span in self.spans:
            if span["name"] == WINDOW_SPAN:
                continue
            cover = min(end, span["start_ns"] + span["dur_ns"]) - max(
                start, span["start_ns"]
            )
            if cover > best_cover:
                best, best_cover = span["name"], cover
        return best

    # -- where the busy time goes ------------------------------------------------
    def top_ops(self, n: int) -> list:
        """The ``n`` operations with most device time as ``[name, seconds]``,
        summed over their runs in the window and averaged over devices; the
        name is the HLO instruction's and, where the program's HLO text gave
        an ``op_name``, its innermost scopes (``bwd`` marks a transpose)."""
        totals = collections.Counter()
        for d in self.devices:
            for e in self.ops[d]:
                totals[self._label(e)] += e["dur_ns"]
        k = max(1, len(self.devices))
        return [[name, ns / k / 1e9] for name, ns in totals.most_common(n)]

    @staticmethod
    def _label(event: dict) -> str:
        op_name = event["op_name"]
        if not op_name:
            return event["name"]
        direction = "bwd " if "transpose(" in op_name else ""
        return f"{event['name']} [{direction}{'/'.join(op_name.split('/')[-5:])}]"

    def seconds_where(self, keep) -> float:
        """Sum of operation durations for which ``keep(event)`` holds,
        averaged over devices."""
        return self._mean_over_devices(
            lambda d: sum(e["dur_ns"] for e in self.ops[d] if keep(e))
        ) / 1e9

    def scope_s(self, scope: str) -> float:
        return self.seconds_where(lambda e: in_scope(e["op_name"], scope))

    def _collective_intervals(self, d):
        """Merged intervals in which a collective was running or in flight
        on device ``d``: synchronous collectives, both halves of
        asynchronous ones, and their lifetimes from start to done."""
        events = [e for e in self.ops[d] if is_collective(e)]
        events += [e for e in self.lifetimes[d] if is_collective(e)]
        return interval_union(self._clipped(events))

    def collective_s(self) -> float:
        return self._mean_over_devices(
            lambda d: self._collective_intervals(d)[0]
        ) / 1e9

    def collective_exposed_frac(self) -> float | None:
        """The share of collective time with no other operation running on
        the same device; None where the trace holds no collective."""
        exposed = total = 0.0
        for d in self.devices:
            comm_ns, comm = self._collective_intervals(d)
            rest = [e for e in self.ops[d] if not is_collective(e)]
            _, rest = interval_union(self._clipped(rest))
            total += comm_ns
            exposed += comm_ns - intersection(comm, rest)
        return exposed / total if total else None

    def describe(self) -> str:
        n_ops = sum(len(v) for v in self.ops.values())
        n_enclosing = sum(len(v) for v in self.enclosing.values())
        return (
            f"{len(self.devices)} device plane(s), {n_ops} operation events, "
            f"{n_enclosing} enclosing ({'/'.join(ENCLOSING)}) left out of the sums, "
            f"{len(self.spans)} host spans, window {self.window_s():.4f} s, "
            f"busy {self.busy_s():.4f} s"
        )
