"""Driven by data: a configuration, a traffic mix, a driver and a per-layer
metric arrive as NEW files plus entries in BENCHMARK.json; no file that is
already there is edited, and the harness finds them by name."""

import hashlib
import json
import os

import pytest

import test_benchmark_contract as contract
from benchmark.harness.discovery import Catalog, DiscoveryError
from benchmark_testlib import REPO, finish, make_root, pending_entries, start_run

NEW_DRIVER = '''
from benchmark.harness.observation import Observation


def run(run):
    # a test double: claims the device the peaks table knows
    run.admit_device("tpu", "TPU v5 lite", run.cell.chips)
    run.open_window()
    widgets = run.traffic["widgets"] * run.section("job")["factor"]
    return Observation(
        correct=True, attempted=widgets, failed=0,
        end_to_end={"widgets_per_s": widgets / run.seconds},
        counters={"widgets": widgets, "compiles_in_window": 0},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1},
    )
'''

NEW_READER = '''
"""Widgets made, straight from the driver's counter."""

METRIC = {"layer": "widgets", "unit": "count", "source": "program_counter",
          "moves": "widgets_per_s"}


def read(observed):
    return float(observed.counters["widgets"])
'''

NEW_ENTRIES = {
    "configs": [{"name": "gadget", "source": "https://example.org/gadget",
                 "file": "benchmark/configs/gadget.json", "reduced": [],
                 "why": "a second family"}],
    "workloads": [{"name": "gadget.burst", "config": "gadget",
                   "traffic": "burst", "chips": 1, "why": "bursts"}],
    "end_to_end": [{"name": "widgets_per_s", "unit": "widgets/s",
                    "better": "higher", "bound": 0.05, "source": "host_clock",
                    "workloads": ["gadget.burst"]}],
    "per_layer": [{"name": "widgets.made", "unit": "count", "better": "higher",
                   "source": "program_counter", "layer": "widgets",
                   "moves": "widgets_per_s", "workloads": ["gadget.burst"]}],
}


def _digests(root):
    out = {}
    for base, _dirs, files in os.walk(os.path.join(root, "benchmark")):
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[path] = hashlib.sha256(f.read()).hexdigest()
    return out


def test_new_cell_needs_only_new_files_and_entries(tmp_path):
    root = make_root(tmp_path, [NEW_ENTRIES])
    before = _digests(root)
    bench = os.path.join(root, "benchmark")
    new_files = {
        "configs/gadget.json": json.dumps({"name": "gadget", "job": {"factor": 3}}),
        "traffic/burst.json": json.dumps({"driver": "widget_maker", "widgets": 7}),
        "drivers/widget_maker.py": NEW_DRIVER,
        "layer_metrics/widgets.made.py": NEW_READER,
    }
    for rel, text in new_files.items():
        with open(os.path.join(bench, rel), "w") as f:
            f.write(text)
    after = _digests(root)
    assert {p: after[p] for p in before} == before  # nothing there was edited

    catalog = Catalog(root)
    cell = catalog.cell("gadget.burst")
    assert cell.config["job"]["factor"] == 3 and cell.traffic["widgets"] == 7
    assert {m["name"] for m in cell.end_to_end} == {"widgets_per_s", "setup_s"}
    assert [m["name"] for m in cell.per_layer if "workloads" in m] == ["widgets.made"]
    assert callable(catalog.driver("widget_maker").run)
    assert callable(catalog.layer_metric(NEW_ENTRIES["per_layer"][0]).read)

    # and the copy's own command runs the new cell end to end, both ways
    runs = [start_run(root, "--workload", "gadget.burst", "--seconds", "2",
                      "--trace", t) for t in ("0", "1")]
    plain, traced = [finish(p) for p in runs]
    assert plain[0] == 0 and traced[0] == 0, (plain[2], traced[2])
    line = json.loads(plain[1].strip().splitlines()[-1])
    assert line["metrics"]["widgets_per_s"] == {"value": 10.5, "unit": "widgets/s"}
    assert set(line["metrics"]) == {"widgets_per_s", "setup_s"}
    line = json.loads(traced[1].strip().splitlines()[-1])
    assert line["metrics"]["widgets.made"] == {"value": 21.0, "unit": "count"}
    # the repo's own cells do not report the new metrics
    assert "widgets_per_s" not in [
        m["name"] for m in catalog.cell("resnet50.train").end_to_end
    ]


EIGHTH = {"name": "regnety_160.trainloop_hostfed", "config": "regnety_160",
          "traffic": "train_loop_hostfed", "chips": 1,
          "why": "made up by a test: an accepted configuration under an accepted traffic file"}
EIGHTH_READS = ("train_items_per_s_per_chip", "models.mfu", "device.hbm_peak_frac",
                "trainer.fetch_ms_per_step")


def test_an_eighth_cell_is_appended_entries_and_nothing_else(tmp_path):
    """What a PR of another kind does to add a cell of an accepted
    configuration under an accepted traffic mix: one entry at the end of
    ``workloads``, its name appended to the ``workloads`` of the metrics it
    reports. No file is added or edited, no test pins how many cells there
    are or where an entry stands, and the contract's checks pass."""
    root = make_root(tmp_path)
    before = _digests(root)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        benchmark = json.load(f)
    had = [w["name"] for w in benchmark["workloads"]]
    benchmark["workloads"].append(EIGHTH)
    for m in benchmark["end_to_end"] + benchmark["per_layer"]:
        if m["name"] in EIGHTH_READS:
            m["workloads"].append(EIGHTH["name"])
    with open(path, "w") as f:
        json.dump(benchmark, f)
    assert _digests(root) == before  # nothing under benchmark/ was touched

    catalog = Catalog(root)
    cell = catalog.cell(EIGHTH["name"])
    assert (cell.config_name, cell.traffic_name, cell.chips) == (
        "regnety_160", "train_loop_hostfed", 1)
    assert cell.config["architecture"]["parameters"] == 83590140
    assert callable(catalog.driver(cell.traffic["driver"]).run)
    assert {m["name"] for m in cell.end_to_end} == {
        "train_items_per_s_per_chip", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "entry.compiles_in_window", *EIGHTH_READS[1:]}
    for metric in cell.per_layer:
        catalog.layer_metric(metric)
    # the cells that were there report what they reported
    accepted = Catalog(REPO)
    for name in had:
        assert [m["name"] for m in catalog.cell(name).per_layer] == [
            m["name"] for m in accepted.cell(name).per_layer]
    # the contract's own checks (the repo's files are the copy's)
    contract.test_keys_sizes_and_names(benchmark)
    contract.test_configs_and_cells(benchmark)
    contract.test_metrics(benchmark)
    contract.test_a_full_check_fits_its_time_limit(benchmark)


def test_every_name_in_the_benchmark_leads_somewhere():
    catalog = Catalog(REPO)
    for workload in catalog.benchmark["workloads"]:
        cell = catalog.cell(workload["name"])
        assert callable(catalog.driver(cell.traffic["driver"]).run)
        assert callable(catalog.costs(cell.config["costs"]).forward_macs_per_item)
        assert callable(catalog.reference(cell.config["reference"]).logits)
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for metric in cell.per_layer:
            catalog.layer_metric(metric)  # declaration equals BENCHMARK.json
    # what is kept for a later benchmark PR resolves too
    for entries in pending_entries():
        for metric in entries["per_layer"]:
            catalog.layer_metric(metric)
        for workload in entries["workloads"]:
            assert catalog.traffic(workload["traffic"])["driver"]


def test_a_name_that_leads_nowhere_is_an_error(tmp_path):
    catalog = Catalog(REPO)
    with pytest.raises(DiscoveryError, match="not in BENCHMARK.json"):
        catalog.cell("resnet50.nothing")
    with pytest.raises(DiscoveryError, match="no file"):
        catalog.traffic("nothing")
    with pytest.raises(DiscoveryError, match="no file"):
        catalog.driver("nothing")
    drifted = dict(catalog.benchmark["per_layer"][0], unit="furlongs")
    with pytest.raises(DiscoveryError, match="unit="):
        catalog.layer_metric(drifted)
    with pytest.raises(DiscoveryError, match="no BENCHMARK.json"):
        Catalog(str(tmp_path))


def test_an_unknown_device_kind_is_an_error_not_a_default():
    catalog = Catalog(REPO)
    assert catalog.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert catalog.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with open(os.path.join(catalog.bench_dir, "peaks.json")) as f:
        assert "cpu" not in json.dumps(json.load(f)["devices"]).lower()  # no CPU row
    with pytest.raises(DiscoveryError, match="TPU v9"):
        catalog.peaks("TPU v9")

    from benchmark.harness import cli

    run = cli.Run(catalog, catalog.cell("resnet50.train"),
                  ["--workload", "resnet50.train"], 0.0)
    with pytest.raises(SystemExit) as refused:
        run.admit_device("tpu", "TPU v9", 1)
    assert refused.value.code == cli.REFUSED
    with pytest.raises(SystemExit):
        run.admit_device("cpu", "cpu", 1)
    with pytest.raises(SystemExit):
        cli.Run(catalog, catalog.cell("resnet50.train_dp4"),
                ["--workload", "resnet50.train_dp4"], 0.0
                ).admit_device("tpu", "TPU v5 lite", 1)
    run.admit_device("tpu", "TPU v5 lite", 1)
    assert run.peaks["bf16_flops_per_s"] == 197e12
