"""Each driver at a tiny size on the CPU, through the real command: the last
printed line is the contract's and carries no metric; without ``--rehearse``
the command refuses. The rehearsals run as processes of their own (a cell
sizes its mesh from the devices its process sees), side by side."""

import json

import pytest

from benchmark_testlib import REPO, finish, make_root, pending_entries, start_run

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def rehearsals(tmp_path_factory):
    served_root = make_root(tmp_path_factory.mktemp("served"), pending_entries())
    started = {
        "train": start_run(REPO, "--workload", "resnet50.train", "--seed", "3",
                           "--seconds", "1", "--trace", "1", "--rehearse"),
        "serve": start_run(served_root, "--workload", "resnet50.serve_jpeg_steady",
                           "--seed", "3", "--seconds", "2", "--trace", "1",
                           "--rehearse"),
        "refused": start_run(REPO, "--workload", "resnet50.train", "--seed", "3",
                             "--seconds", "1", "--trace", "0"),
    }
    return {name: finish(process) for name, process in started.items()}


def _last_line(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("driver,least", [("train", 4), ("serve", 10)])
def test_rehearsal_prints_the_contracts_line_and_no_metric(rehearsals, driver, least):
    code, out, err = rehearsals[driver]
    assert code == 0, err[-3000:]
    line = _last_line(out)
    assert set(line) == CONTRACT_KEYS  # traced or not: nothing from a CPU
    assert line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= least
    # the earlier lines are free, and say what the run held itself to
    assert "reference:" in out and "agrees" in out
    assert "trace:" in out


def test_rehearsed_server_is_held_to_the_reference_and_the_due_time(rehearsals):
    _code, out, _err = rehearsals["serve"]
    assert "latency from due time" in out and "generator lateness" in out
    assert "'unanswered': 0" in out


def test_without_rehearse_the_command_refuses_off_the_chip(rehearsals):
    code, out, err = rehearsals["refused"]
    assert code != 0
    assert "not a TPU" in err
    assert not [ln for ln in out.splitlines() if ln.startswith("{")]
