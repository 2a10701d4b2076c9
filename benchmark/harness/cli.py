"""One run of one cell: arguments, discovery, set-up clock, the last line.

``main`` loads the cell named by ``--workload``, hands a :class:`Run` to the
driver its traffic file names, and prints as the LAST line of standard output
one JSON object with ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` in a traced run). With ``--trace 0`` the
metrics are the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics. Nothing here names a cell, a configuration or a metric.

It refuses to measure on a platform that is not a TPU, on fewer chips than
the cell asks for, and on a device kind that is missing from ``peaks.json``.
Only ``--rehearse`` runs elsewhere: the configuration's and the traffic's
``rehearse`` overrides cut the run to a tiny size for the CPU, and the line
then carries no metric at all — a number from a CPU run never appears under a
device metric's name.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from benchmark.harness import trace as trace_lib
from benchmark.harness.clock import now
from benchmark.harness.compiles import CompileCounter
from benchmark.harness.discovery import Catalog, DiscoveryError
from benchmark.harness.observation import Observed

OUT_DIRNAME = ".bench_out"  # inside the checkout, git-ignored
REFUSED = 2


class Refused(SystemExit):
    """The run may not be measured here; exits non-zero, prints no result."""

    def __init__(self, why: str):
        print(f"benchmark: refused: {why}", file=sys.stderr, flush=True)
        super().__init__(REFUSED)


class Run:
    """One run's arguments, clocks and counters, as a driver sees them."""

    def __init__(self, catalog, cell, argv, t_process_start: float):
        args = parse(argv)
        self.argv = list(argv)  # a driver's child process gets the same
        self.catalog = catalog
        self.cell = cell
        self.root = catalog.root
        self.seed = int(args.seed)
        self.seconds = float(
            catalog.benchmark["run_seconds"] if args.seconds is None
            else args.seconds
        )
        self.trace = bool(args.trace)
        self.rehearse = bool(args.rehearse)
        self.compiles = CompileCounter()
        self.trace_dir = os.path.join(self.root, OUT_DIRNAME, "trace", cell.name)
        self.setup_s = None
        self.peaks = None
        self._t_process_start = t_process_start
        self._marks = [("start", t_process_start)]
        self._compiles_at_open = None
        self._sets = dict(s.split("=", 1) for s in args.set)

    def say(self, message: str) -> None:
        """An earlier line of output; the last line is the result's."""
        print(f"[{self.cell.name}] {message}", flush=True)

    def _with_sets(self, scope: str, values: dict) -> dict:
        for key, raw in self._sets.items():
            if key.startswith(scope + "."):
                values[key[len(scope) + 1:]] = json.loads(raw)
        return values

    def section(self, name: str) -> dict:
        """One section of the configuration as it is run: the file's, with
        the ``rehearse`` section's keys over it in a rehearsal."""
        values = dict(self.cell.config.get(name, {}))
        if self.rehearse:
            values.update(self.cell.config.get("rehearse", {}).get(name, {}))
        return self._with_sets(name, values)

    @property
    def traffic(self) -> dict:
        values = dict(self.cell.traffic)
        if self.rehearse:
            values.update(values.get("rehearse", {}))
        return self._with_sets("traffic", values)

    def mark(self, phase: str) -> None:
        """End of one phase of set-up; the phases go on an earlier line."""
        self._marks.append((phase, now()))

    def admit_device(self, platform: str, kind: str, count: int) -> None:
        """Refuse before any measurement unless this is the cell's device."""
        if self.rehearse:
            self.say(f"rehearsal on platform={platform}: no metric will be printed")
            return
        if platform != "tpu":
            raise Refused(
                f"platform is {platform!r}, not a TPU; a time, a rate or a "
                "utilization comes only from the chip (--rehearse runs a "
                "tiny size elsewhere and prints no metric)"
            )
        if count < self.cell.chips:
            raise Refused(
                f"cell {self.cell.name!r} asks for {self.cell.chips} chip(s), "
                f"jax sees {count}"
            )
        try:
            self.peaks = self.catalog.peaks(kind)
        except DiscoveryError as e:
            raise Refused(str(e)) from e

    def open_window(self) -> None:
        """The first instant of the measured window: set-up ends here."""
        self._compiles_at_open = self.compiles.snapshot()
        self.setup_s = now() - self._t_process_start
        hits_misses = {k: self._compiles_at_open[k] for k in ("hits", "misses")}
        phases = ", ".join(
            f"{name} {t - self._marks[i][1]:.1f}"
            for i, (name, t) in enumerate(self._marks[1:])
        )
        self.say(
            f"set-up {self.setup_s:.3f} s ({phases}); compile lookups "
            f"{self._compiles_at_open['lookups']} "
            f"({self._compiles_at_open['seconds']:.1f} s), cache {hits_misses}"
        )

    def compiles_since_open(self) -> int:
        return self.compiles.snapshot()["lookups"] - self._compiles_at_open["lookups"]


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None,
                   help="length of the measured window (BENCHMARK.json's "
                        "run_seconds when left out)")
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="tiny size on any platform; prints no metric")
    p.add_argument("--set", action="append", default=[], metavar="SECTION.KEY=JSON",
                   help="sweep hook: run with one parameter of the "
                        "configuration (train_job.per_chip_batch=256) or the "
                        "traffic (traffic.rate_per_s=300) replaced; how the "
                        "numbers fixed in the files were found")
    return p.parse_args(argv)


def result_line(run: Run, observation) -> dict:
    cell = run.cell
    device = {
        k: observation.device[k]
        for k in ("platform", "kind", "count", "memory_peak_bytes")
    }
    compiled = int(observation.counters.get("compiles_in_window", 0))
    line = {
        # a compilation inside the window is a measurement of the compiler
        "correct": bool(observation.correct and compiled == 0),
        "attempted": int(observation.attempted),
        "failed": int(observation.failed),
        "metrics": {},
        "device": device,
    }
    if compiled:
        run.say(f"{compiled} compilation(s) inside the window: not correct")
    reduction = None
    if observation.trace_path:
        reduction = trace_lib.Reduction.from_file(
            observation.trace_path, observation.trace_op_names_path
        )
        run.say(f"trace: {reduction.describe()}")
    if run.rehearse:
        return line
    end_to_end = {**observation.end_to_end, "setup_s": run.setup_s}
    if not run.trace:
        for metric in cell.end_to_end:
            line["metrics"][metric["name"]] = {
                "value": end_to_end[metric["name"]], "unit": metric["unit"],
            }
        return line
    observed = Observed(
        cell=cell, section=run.section, traffic=run.traffic,
        end_to_end=end_to_end, counters=observation.counters,
        device=observation.device, peaks=run.peaks, catalog=run.catalog,
        trace=reduction,
    )
    reported = {m["name"] for m in cell.end_to_end}
    for metric in cell.per_layer:
        if metric["moves"] not in reported:
            continue  # reported only where the metric it moves is
        value = run.catalog.layer_metric(metric).read(observed)
        if value is not None:
            line["metrics"][metric["name"]] = {
                "value": value, "unit": metric["unit"],
            }
    if reduction is not None and reduction.devices:
        device["busy_s"] = reduction.busy_s()
        device["window_s"] = reduction.window_s()
        line["breakdown"] = {
            "device_ops": reduction.top_ops(10),
            "idle_gaps": reduction.idle_gaps(10),
        }
    return line


def main(argv, t_process_start: float) -> int:
    args = parse(argv)
    try:
        catalog = Catalog()
        run = Run(catalog, catalog.cell(args.workload), argv, t_process_start)
        driver = catalog.driver(run.traffic["driver"])
    except DiscoveryError as e:
        raise Refused(str(e)) from e
    observation = driver.run(run)
    line = result_line(run, observation)
    if observation.compared:
        # each number compared beside its limit: the last lines of standard
        # error, and the last key of the result's line
        line["compared"] = observation.compared
        for name, c in observation.compared.items():
            print(f"compared {name} {c['value']:.6g} limit {c['limit']:.6g}",
                  file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
