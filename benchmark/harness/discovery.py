"""Discovery by name: everything that belongs to one cell, configuration,
traffic mix or metric is a file of its own, found through ``BENCHMARK.json``.

Adding a configuration, a traffic mix, a driver, a cost function or a
per-layer metric is adding files plus entries in ``BENCHMARK.json``; nothing
here, and no file that is already there, is edited for it
(tests/benchmark/test_benchmark_discovery.py proves it on a temp copy).
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIRNAME = os.path.basename(os.path.dirname(_HERE))
DEFAULT_ROOT = os.path.dirname(os.path.dirname(_HERE))

# what a per-layer metric's own file must say the same as BENCHMARK.json
_DECLARED = ("layer", "unit", "source", "moves")


class DiscoveryError(LookupError):
    """A name in ``BENCHMARK.json`` or a data file leads nowhere."""


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything its names resolve to."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: tuple  # metric entries of BENCHMARK.json that this cell reports
    per_layer: tuple


class Catalog:
    """The benchmark's files under one checkout ``root``."""

    def __init__(self, root: str = DEFAULT_ROOT):
        self.root = os.path.abspath(root)
        self.bench_dir = os.path.join(self.root, BENCH_DIRNAME)
        path = os.path.join(self.root, "BENCHMARK.json")
        if not os.path.exists(path):
            raise DiscoveryError(f"no BENCHMARK.json at {self.root}")
        with open(path) as f:
            self.benchmark = json.load(f)

    # -- data files ---------------------------------------------------------
    def _json(self, path: str, what: str) -> dict:
        if not os.path.exists(path):
            raise DiscoveryError(f"{what}: no file {path}")
        with open(path) as f:
            return json.load(f)

    def config(self, name: str) -> dict:
        for entry in self.benchmark["configs"]:
            if entry["name"] == name:
                return self._json(
                    os.path.join(self.root, entry["file"]), f"config {name!r}"
                )
        raise DiscoveryError(f"config {name!r} is not in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return self._json(
            os.path.join(self.bench_dir, "traffic", f"{name}.json"),
            f"traffic mix {name!r}",
        )

    def peaks(self, device_kind: str) -> dict:
        """The published peaks of one device kind. A kind that is not in the
        table is an error, never a default: a utilization against a guessed
        peak is a guess."""
        table = self._json(os.path.join(self.bench_dir, "peaks.json"), "peaks")
        if device_kind not in table["devices"]:
            raise DiscoveryError(
                f"device_kind {device_kind!r} is not in {BENCH_DIRNAME}/"
                f"peaks.json ({sorted(table['devices'])}): add its published "
                "peaks with their source before measuring on it"
            )
        return table["devices"][device_kind]

    # -- code files, one per name --------------------------------------------
    def _module(self, kind: str, name: str):
        path = os.path.join(self.bench_dir, kind, f"{name}.py")
        if not os.path.exists(path):
            raise DiscoveryError(f"{kind[:-1]} {name!r}: no file {path}")
        # metric names carry dots, so the module is loaded by path
        modname = f"_{BENCH_DIRNAME}_{kind}_{name.replace('.', '_')}"
        spec = importlib.util.spec_from_file_location(modname, path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def driver(self, name: str):
        return self._module("drivers", name)

    def costs(self, name: str):
        return self._module("costs", name)

    def reference(self, name: str):
        return self._module("reference", name)

    def layer_metric(self, entry: dict):
        """The reader of one per-layer metric. Its file declares layer, unit,
        source and the end-to-end metric it moves; a declaration that parted
        from ``BENCHMARK.json`` is refused, so the two cannot drift."""
        module = self._module("layer_metrics", entry["name"])
        declared = getattr(module, "METRIC", None)
        if not isinstance(declared, dict) or not callable(
            getattr(module, "read", None)
        ):
            raise DiscoveryError(
                f"layer metric {entry['name']!r}: its file must define "
                "METRIC = {layer, unit, source, moves} and read(observed)"
            )
        for key in _DECLARED:
            if declared.get(key) != entry.get(key):
                raise DiscoveryError(
                    f"layer metric {entry['name']!r}: {key}="
                    f"{declared.get(key)!r} in its file, {entry.get(key)!r} "
                    "in BENCHMARK.json"
                )
        return module

    # -- cells -----------------------------------------------------------------
    def cell(self, workload: str) -> Cell:
        for entry in self.benchmark["workloads"]:
            if entry["name"] == workload:
                break
        else:
            names = [w["name"] for w in self.benchmark["workloads"]]
            raise DiscoveryError(
                f"workload {workload!r} is not in BENCHMARK.json ({names})"
            )

        def mine(metrics):
            return tuple(
                m for m in metrics
                if "workloads" not in m or workload in m["workloads"]
            )

        return Cell(
            name=workload,
            chips=int(entry["chips"]),
            config_name=entry["config"],
            config=self.config(entry["config"]),
            traffic_name=entry["traffic"],
            traffic=self.traffic(entry["traffic"]),
            end_to_end=mine(self.benchmark["end_to_end"]),
            per_layer=mine(self.benchmark["per_layer"]),
        )
