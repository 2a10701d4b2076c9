"""Entries are APPENDED to ``BENCHMARK.json``: a configuration, a cell, a
per-layer metric and a cell's name at the end of an accepted metric's list
(``PERF.md`` section 7, "How a cell is added now"). So no test under
``tests/benchmark/`` may hold the manifest to a position or a count: such an
assertion fails on the next appended entry, wherever it is put, and only a
``benchmark`` PR may edit the file that holds it (PR 51's first tree was
refused over ``test_benchmark_sdar.py``; PR 52 turned its assertions, and
``test_benchmark_trinity.py``'s, into lookups by name). The first test reads
every test file's source for such an assertion; the second appends a made-up
configuration, cell and per-layer entry to a copy of the manifest in memory
and shows that every accepted cell still resolves to the same files and
metric names."""

import copy
import functools
import glob
import importlib.util
import json
import os
import re

import pytest

from benchmark.harness.discovery import Catalog
from benchmark_testlib import REPO

HERE = os.path.dirname(os.path.abspath(__file__))
LISTS = "configs|workloads|end_to_end|per_layer"
# one of the manifest's lists, then an index or a slice counted from the end
FROM_THE_END = re.compile(r"""\[["'](?:%s)["']\]\s*\[\s*-""" % LISTS)
# the length of one of them on either side of an equality
COUNT = re.compile(
    r"""len\([^()]*\[["'](?:%s)["']\]\s*\)\s*==|==\s*len\([^()]*\[["'](?:%s)["']\]\s*\)"""
    % (LISTS, LISTS))


@functools.cache
def cell_list():
    """A list held equal to a list literal of accepted cells' names (the
    parent's test_benchmark_trinity.py held the four-chip cells to one)."""
    cells = "|".join(re.escape(w["name"]) for w in Catalog(REPO).benchmark["workloads"])
    return re.compile(r"""==\s*\[\s*["'](?:%s)["']""" % cells)


def positional(source: str) -> list:
    """The lines of ``source`` that hold the manifest to a position from the
    end, to a count, or a list of its cells to the cells it has today."""
    return [line.strip() for line in source.splitlines()
            if FROM_THE_END.search(line) or COUNT.search(line) or cell_list().search(line)]


def test_no_test_holds_the_manifest_to_a_position_or_a_count():
    files = sorted(glob.glob(os.path.join(HERE, "test_*.py")))
    assert os.path.abspath(__file__) in files and len(files) >= 18  # the directory it stands in
    found = {}
    for path in files:
        with open(path) as f:
            lines = positional(f.read())
        if lines:
            found[os.path.basename(path)] = lines
    assert not found, (
        "an appended entry would fail these: look the entry up by name "
        f"(`in`, `>=`), PERF.md section 7: {found}")


@pytest.mark.parametrize("line", [
    # the parent's (9040ce1) test_benchmark_sdar.py, lines 87, 155, 172, 178, 187
    'assert CATALOG.benchmark[{q}configs{q}][-1] is entry  # appended',
    'assert CATALOG.benchmark[{q}workloads{q}][-1][{q}name{q}] == CELL  # appended',
    'assert [m[{q}name{q}] for m in CATALOG.benchmark[{q}per_layer{q}][-2:]] == list(NEW)',
    'assert m[{q}workloads{q}][-1] == CELL  # appended to its list',
    'assert len(CATALOG.benchmark[{q}workloads{q}]) == 10 and len(CATALOG.benchmark[{q}configs{q}]) == 8',
    'assert 8 == len(benchmark[{q}configs{q}])',
    # the parent's test_benchmark_trinity.py, line 189 (and test_benchmark_sdar.py's 186)
    'assert [w[{q}name{q}] for w in four] == [{q}resnet50.train_dp4{q}]',
])
def test_the_lint_finds_what_the_parent_held(line):
    # the quote is put in here so that this file does not find itself
    for quote in ('"', "'"):
        assert positional(line.format(q=quote)) == [line.format(q=quote)]


@pytest.mark.parametrize("line", [
    'assert CELL in [w["name"] for w in CATALOG.benchmark["workloads"]]',
    'assert 2 <= len(benchmark["workloads"]) <= 24',
    'assert four <= max(1, len(benchmark["workloads"]) // 4)',
    'line = json.loads(out.strip().splitlines()[-1])',
    'drifted = dict(catalog.benchmark["per_layer"][0], unit="furlongs")',
    'mine = [m for m in CATALOG.benchmark["per_layer"] if m["name"] in NEW]',
    'assert CELL not in four and "resnet50.train_dp4" in four',
    'assert [m["name"] for m in cell.per_layer if "workloads" in m] == ["widgets.made"]',
])
def test_the_lint_lets_membership_bounds_and_other_lists_be(line):
    assert positional(line) == []


def resolved(catalog, name) -> dict:
    """What one cell's names lead to: files, driver, metric names in order."""
    cell = catalog.cell(name)
    entry = [c for c in catalog.benchmark["configs"] if c["name"] == cell.config_name][0]
    return {
        "chips": cell.chips, "config": cell.config_name, "file": entry["file"],
        "body": cell.config, "traffic": cell.traffic_name,
        "driver": cell.traffic["driver"],
        "driver_file": catalog.driver(cell.traffic["driver"]).__file__,
        "reference_file": catalog.reference(cell.config["reference"]).__file__,
        "costs_file": catalog.costs(cell.config["costs"]).__file__,
        "end_to_end": [m["name"] for m in cell.end_to_end],
        "per_layer": [m["name"] for m in cell.per_layer],
        "readers": [catalog.layer_metric(m).__file__ for m in cell.per_layer],
    }


def test_appended_entries_leave_every_accepted_cell_as_it_resolved(tmp_path):
    """PERF.md section 7's steps on a copy of the manifest in memory: (1) new
    files (here: a configuration's file; the reader is one that has a file
    and no entry), (2) the configuration, the cell and the metric appended,
    and the cell's name appended to the lists of the accepted metrics it
    reports, (3) a decoder's cell lists the decoders' accepted readers."""
    accepted = Catalog(REPO)
    names = [w["name"] for w in accepted.benchmark["workloads"]]
    before = {name: resolved(accepted, name) for name in names}

    body = dict(accepted.config("sdar_30b_a3b"), name="made_up_7th")
    config_file = tmp_path / "made_up_7th.json"  # (1)
    config_file.write_text(json.dumps(body))
    reader = "trainer.dispatch_ms_per_step"  # a file in layer_metrics/, no entry
    assert reader not in {m["name"] for m in accepted.benchmark["per_layer"]}
    spec = importlib.util.spec_from_file_location(
        "_made_up_reader", os.path.join(REPO, "benchmark", "layer_metrics", reader + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    cell = "made_up_7th.train_seq8192"
    reads = ("train_items_per_s_per_chip", "models.mfu", "device.hbm_peak_frac",
             "models.attn_ms_per_step", "models.recompute_ms_per_step",  # (3)
             "kernels.flash_attn_roofline", "models.attn_prologue_ms_per_step")

    changed = Catalog(REPO)
    manifest = changed.benchmark = copy.deepcopy(accepted.benchmark)
    manifest["configs"].append(  # (2)
        {"name": "made_up_7th", "source": "https://example.org/made-up-7th",
         "file": str(config_file), "reduced": ["layers"], "why": "made up by a test"})
    manifest["workloads"].append(
        {"name": cell, "config": "made_up_7th",
         "traffic": "train_device_tokens_diffusion", "chips": 1,
         "why": "made up by a test"})
    manifest["per_layer"].append(
        {"name": reader, "better": "lower", **module.METRIC, "workloads": [cell]})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] in reads:
            m["workloads"].append(cell)

    mine = resolved(changed, cell)
    assert (mine["config"], mine["file"], mine["body"]["name"]) == (
        "made_up_7th", str(config_file), "made_up_7th")
    assert mine["driver"] == "lm_diffusion_train_step"
    assert mine["end_to_end"] == ["train_items_per_s_per_chip", "setup_s"]
    assert set(mine["per_layer"]) == {"entry.compiles_in_window", *reads[1:], reader}
    # every accepted cell: the same files, the same metric names in the same order
    for name in names:
        assert resolved(changed, name) == before[name], name
    assert accepted.benchmark != manifest  # the copy alone was changed
