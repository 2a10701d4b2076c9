"""BENCHMARK.json against the builder's contract, as far as a CPU can check."""

import json
import os
import re

import pytest

from benchmark_testlib import REPO, pending_entries

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
PLAIN_PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection",
               "_dim", "_rank", "head", "expansion", "width", "experts_per")


@pytest.fixture(scope="module")
def benchmark():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        text = f.read()
    assert len(text.encode()) <= 64 * 1024
    return json.loads(text)


def test_keys_sizes_and_names(benchmark):
    assert set(benchmark) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(benchmark["command"]) <= 32
    assert benchmark["command"][1].startswith(benchmark["paths"][0] + "/")
    assert 1 <= len(benchmark["paths"]) <= 16
    for path in benchmark["paths"]:
        assert PLAIN_PATH.match(path) and not path.startswith("/") and ".." not in path
        assert os.path.isdir(os.path.join(REPO, path))
    assert isinstance(benchmark["run_seconds"], int)
    assert 1 <= benchmark["run_seconds"] <= 51
    assert 1 <= len(benchmark["configs"]) <= 24
    assert 2 <= len(benchmark["workloads"]) <= 24
    assert 1 <= len(benchmark["end_to_end"]) <= 16
    assert 1 <= len(benchmark["per_layer"]) <= 128
    names = [e["name"] for key in ("configs", "workloads", "end_to_end", "per_layer")
             for e in benchmark[key]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names), names
    for key in ("configs", "workloads"):
        assert all(len(e["why"]) <= 200 for e in benchmark[key])


def test_every_file_under_paths_has_a_plain_name(benchmark):
    for path in benchmark["paths"]:
        for base, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(base, name), REPO)
                assert PLAIN_PATH.match(rel), rel


def test_configs_and_cells(benchmark):
    files = [c["file"] for c in benchmark["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in benchmark["workloads"]}
    for config in benchmark["configs"]:
        assert config["name"] in used
        assert config["source"].startswith("https://")
        assert any(config["file"].startswith(p + "/") for p in benchmark["paths"])
        with open(os.path.join(REPO, config["file"])) as f:
            body = json.load(f)
        assert body["source"] == config["source"]
        assert body["reduced"] == config["reduced"]
        assert not [k for k in config["reduced"]
                    if any(w in k.lower() for w in WIDTH_WORDS)]
    pairs = [(w["config"], w["traffic"]) for w in benchmark["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert all(w["chips"] in (1, 4) for w in benchmark["workloads"])
    four = sum(w["chips"] == 4 for w in benchmark["workloads"])
    assert four <= max(1, len(benchmark["workloads"]) // 4)
    for w in benchmark["workloads"]:
        assert os.path.exists(os.path.join(
            REPO, benchmark["paths"][0], "traffic", w["traffic"] + ".json"))


def test_metrics(benchmark):
    end_to_end = {m["name"]: m for m in benchmark["end_to_end"]}
    assert "setup_s" in end_to_end
    cells = {w["name"] for w in benchmark["workloads"]}
    for m in benchmark["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["better"] in ("higher", "lower")
        assert set(m.get("workloads", cells)) <= cells
    for m in benchmark["per_layer"]:
        assert m["source"] in SOURCES and "bound" not in m
        assert m["moves"] in end_to_end
        assert set(m.get("workloads", cells)) <= cells
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for cell in cells:
        reported = {n for n, m in end_to_end.items()
                    if cell in m.get("workloads", cells)}
        assert "setup_s" in reported and len(reported) >= 2
        assert any(cell in m.get("workloads", cells) and m["moves"] in reported
                   for m in benchmark["per_layer"])


def test_a_full_check_fits_its_time_limit(benchmark):
    # 2 + 14 x cells runs of run_seconds + 60 s, 2 x 90 s per cell to
    # compile, 1200 s spare: within 43200 s even with the full 24 cells
    cells = 24
    total = (2 + 14 * cells) * (benchmark["run_seconds"] + 60) + cells * 180 + 1200
    assert total <= 43200


def test_pending_entries_are_of_the_same_form(benchmark):
    """What is kept for a later benchmark PR (benchmark/pending/) would pass
    the same checks once its bounds and rate are measured."""
    for entries in pending_entries():
        for w in entries["workloads"]:
            assert NAME.match(w["name"]) and len(w["why"]) <= 200
            assert w["config"] in {c["name"] for c in benchmark["configs"]}
        end_to_end = {m["name"] for m in entries["end_to_end"]}
        for m in entries["end_to_end"] + entries["per_layer"]:
            assert NAME.match(m["name"]) and m["source"] in SOURCES
        assert all(m["moves"] in end_to_end for m in entries["per_layer"])
