"""The host-fed cell ``resnet50.trainloop_hostfed``, which waits in
``benchmark/pending/`` (its file's note says why): what ``BENCHMARK.json``
holds for it once ``run_pending.py`` has merged its entries in, its nine
``trainer.*`` / ``loader.*`` readers on synthetic spans, counters and device
events and on the recorded loop
(``fixtures/trainloop_spans``), the accepted readers on this driver's
observation, and the driver at a tiny size through the real command."""

import json
import os

import pytest

from benchmark import run_pending
from benchmark.harness import loop_capture, program_spans, trace
from benchmark.harness.discovery import Catalog
from benchmark.harness.observation import Observed
from benchmark.harness.trace import Reduction
from benchmark_testlib import REPO, finish, start_run

CELL = "resnet50.trainloop_hostfed"
FIXTURE = os.path.join(REPO, "benchmark", "fixtures", "trainloop_spans.xplane.pb.gz")
MS = 1e6  # the synthetic timeline is written in ms, the clock counts ns

# name -> (layer, source, unit), as ISSUE 35's table has them
NINE = {
    "trainer.data_wait_frac": ("trainer", "program_counter", "fraction"),
    "trainer.h2d_ms_per_step": ("trainer", "program_counter", "ms"),
    "trainer.fetch_ms_per_step": ("trainer", "program_counter", "ms"),
    "trainer.dispatch_ms_per_step": ("trainer", "program_span", "ms"),
    "trainer.loop_self_ms_per_step": ("trainer", "program_span", "ms"),
    "trainer.idle_under_wait_frac": ("trainer", "program_span", "fraction"),
    "trainer.idle_at_fence_frac": ("trainer", "program_span", "fraction"),
    "trainer.idle_unattributed_frac": ("trainer", "program_span", "fraction"),
    "loader.batch_ms": ("loader", "program_span", "ms"),
}
ACCEPTED = ("models.mfu", "models.fwd_bwd_ms_per_step", "kernels.opt_update_ms_per_step",
            "kernels.opt_update_roofline", "device.idle_frac", "device.hbm_peak_frac")

FWD = "jit(train_step)/jvp(fwd)/ResNet/ConvBN_0/conv_general_dilated"
KERNEL = "jit(train_step)/optimizer_update/opt_kernel/dtpu_opt_update_sgd/pallas_call"


def op(name, start, dur, op_name=""):
    _, opcode = trace.parse_instruction(name)
    return {"plane": "/device:TPU:0", "line": "XLA Ops", "name": name,
            "opcode": opcode, "op_name": op_name,
            "start_ns": start * MS, "dur_ns": dur * MS}


def host(name, start, dur):
    return {"plane": "host", "line": "python3", "name": name, "opcode": "",
            "op_name": "", "start_ns": start * MS, "dur_ns": dur * MS}


def span(name, start, dur, thread="python3#0"):
    return {"name": "dtpu." + name, "thread": thread,
            "start_ns": start * MS, "dur_ns": dur * MS}


def synthetic():
    """A traced epoch of five steps, in ms. The device is busy [0, 100),
    [130, 200) and [230, 300) of a ``bench.window`` [0, 320): idle 80. The
    loop: a wait of 16 on the loader, a print's fence in the middle, the
    epoch's flush at the end; one worker thread assembles two batches."""
    events = [
        host("bench.window", 0, 320),
        op("fusion.1", 0, 90, FWD), op("dtpu_opt_update_sgd.1", 90, 10, KERNEL),
        op("fusion.1", 130, 60, FWD), op("dtpu_opt_update_sgd.1", 190, 10, KERNEL),
        op("fusion.1", 230, 60, FWD), op("dtpu_opt_update_sgd.1", 290, 10, KERNEL),
    ]
    spans = [
        span("trainer.epoch", 0, 310),
        span("trainer.step", 5, 5), span("trainer.step", 60, 5),
        span("trainer.wait", 102, 16), span("trainer.h2d", 118, 4),
        span("trainer.step", 122, 6), span("trainer.step", 135, 5),
        span("trainer.metrics_fetch", 195, 20),
        span("trainer.wait", 216, 4), span("trainer.h2d", 220, 4),
        span("trainer.step", 226, 3),
        span("trainer.metrics_fetch", 295, 10),
        span("loader.decode", 90, 20, "python3#1"),
        span("loader.assemble", 110, 4, "python3#1"),
        span("loader.decode", 150, 20, "python3#1"),
        span("loader.assemble", 170, 2, "python3#1"),
    ]
    return events, spans


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout whose ``BENCHMARK.json`` has the pending entries merged in,
    as ``run_pending.py`` makes it."""
    return run_pending.make_root(str(tmp_path_factory.mktemp("pending") / "checkout"))


def observed_for(root, counters, events=None):
    catalog = Catalog(root)
    cell = catalog.cell(CELL)
    return Observed(
        cell=cell, section=lambda name: cell.config[name], traffic=cell.traffic,
        end_to_end={"train_items_per_s_per_chip": 2700.0, "setup_s": 1.0},
        counters=counters,
        device={"memory_peak_bytes": 5 * 2**30, "memory_limit_bytes": 16 * 2**30},
        peaks=catalog.peaks("TPU v5 lite"), catalog=catalog,
        trace=None if events is None else Reduction(events),
    )


def read(observed, name):
    by_name = {m["name"]: m for m in observed.catalog.benchmark["per_layer"]}
    return observed.catalog.layer_metric(by_name[name]).read(observed)


def traced_counters():
    events, spans = synthetic()
    counters = {
        "trace_steps": 5, "window_s": 20.0, "trainer.steps": 100.0,
        "trainer.wait_s": 0.5, "trainer.h2d_s": 0.2, "trainer.fetch_s": 0.4,
        "trainer.h2d_bytes": 1e9, "trainer.epochs": 3.0,
        "param_bytes": 100e6, "moment_bytes": 100e6,
    }
    counters.update(loop_capture.reduce_loop(spans, Reduction(events)))
    return counters, events


# ---------------------------------------------------------------- declared
def test_the_cell_is_one_chip_on_resnet50_with_the_nine_declared(root):
    catalog = Catalog(root)
    assert CELL not in [w["name"] for w in Catalog().benchmark["workloads"]]  # pending
    (entry,) = [w for w in catalog.benchmark["workloads"] if w["name"] == CELL]
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "resnet50", "train_loop_hostfed", 1)
    cell = catalog.cell(CELL)
    assert cell.traffic["driver"] == "train_loop"
    assert (cell.traffic["pool_images"], cell.traffic["epoch_steps"],
            cell.traffic["warmup_steps"], cell.traffic["trace_steps"]) == (
        2048, 128, 32, 64)
    # the issue's parameters and no others: nothing here opens a sink
    assert set(cell.traffic) == {
        "driver", "description", "pool_images", "warmup_steps", "epoch_steps",
        "trace_steps", "overrides", "rehearse"}
    assert cell.traffic["overrides"] == {}
    assert {m["name"] for m in cell.end_to_end} == {
        "train_items_per_s_per_chip", "setup_s"}
    by_name = {m["name"]: m for m in catalog.benchmark["per_layer"]}
    for name, (layer, source, unit) in NINE.items():
        m = by_name[name]
        assert (m["layer"], m["source"], m["unit"], m["better"], m["moves"]) == (
            layer, source, unit, "lower", "train_items_per_s_per_chip")
        assert m["workloads"] == [CELL]
        catalog.layer_metric(m)  # its file declares the same
    # what reads the traced epoch is held back beyond the pin: a capture of
    # this traffic measures the tracer (the pending file says why)
    with open(os.path.join(run_pending.REPO, "benchmark", "pending",
                           CELL + ".json")) as f:
        held = json.load(f)["not_admissible_yet"]["per_layer"]
    assert set(held) == {"device.idle_frac"} | {
        name for name, (_, source, _) in NINE.items() if source == "program_span"}
    reported = {m["name"] for m in cell.per_layer}
    assert set(NINE) | set(ACCEPTED) | {"entry.compiles_in_window"} == reported
    # new entries stand at the end of their lists, the cell last in each
    assert catalog.benchmark["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in catalog.benchmark["per_layer"][-9:]] == list(NINE)
    for name in ACCEPTED + ("train_items_per_s_per_chip",):
        entry = by_name.get(name) or catalog.benchmark["end_to_end"][0]
        assert entry["workloads"][-1] == CELL


# ------------------------------------------------------- on synthetic events
EXPECTED = {
    "trainer.data_wait_frac": 0.5 / 20,
    "trainer.h2d_ms_per_step": 2.0,
    "trainer.fetch_ms_per_step": 4.0,
    "trainer.dispatch_ms_per_step": 24 / 5,
    # the epoch's 310 less its children on the loop's thread: steps 24,
    # waits 20, h2d 8, fetches 30; the worker's 46 are another thread's
    "trainer.loop_self_ms_per_step": (310 - 82) / 5,
    "trainer.idle_under_wait_frac": 16 / 320,
    # [200, 226) under the fetch and up to the next step, [300, 320) after
    # the epoch's flush
    "trainer.idle_at_fence_frac": 46 / 320,
    # [100, 102), [128, 130) and [229, 230): under the epoch alone
    "trainer.idle_unattributed_frac": 5 / 320,
    "loader.batch_ms": 46 / 2,
}


@pytest.mark.parametrize("name", list(NINE))
def test_reader_on_synthetic_spans_counters_and_device_events(root, name):
    """A case a reader: its value on the synthetic epoch, and nothing (no
    exception either) where the program has no such span or counter: the
    parent of the PR that added them, or an untraced run."""
    counters, events = traced_counters()
    assert read(observed_for(root, counters, events), name) == pytest.approx(EXPECTED[name])
    assert read(observed_for(root, {"trace_steps": 0, "window_s": 20.0}), name) is None
    # the parent's program traced: wait/h2d/step/metrics_fetch, no epoch, no
    # worker annotations, no counter
    _, spans = synthetic()
    old = [s for s in spans if s["name"].split(".")[-1] in
           ("wait", "h2d", "step", "metrics_fetch")]
    parent = {"trace_steps": 5, "window_s": 20.0,
              **loop_capture.reduce_loop(old, Reduction(events))}
    value = read(observed_for(root, parent, events), name)
    if NINE[name][1] == "program_counter" or name in (
            "trainer.loop_self_ms_per_step", "loader.batch_ms"):
        assert value is None
    else:
        assert value == pytest.approx(EXPECTED[name])


def test_idle_by_cause_sums_to_the_devices_idle_share(root):
    counters, events = traced_counters()
    observed = observed_for(root, counters, events)
    idle = counters["idle_s"]
    assert idle == {
        "window": pytest.approx(0.320), "idle": pytest.approx(0.080),
        "fence": pytest.approx(0.046), "wait": pytest.approx(0.016),
        "h2d": pytest.approx(0.004), "step": pytest.approx(0.009),
        "unattributed": pytest.approx(0.005)}
    assert sum(idle[c] for c in loop_capture.CAUSES) == pytest.approx(idle["idle"])
    whole = read(observed, "device.idle_frac")
    assert whole == pytest.approx(0.25) == pytest.approx(idle["idle"] / idle["window"])
    three = sum(read(observed, n) for n in (
        "trainer.idle_under_wait_frac", "trainer.idle_at_fence_frac",
        "trainer.idle_unattributed_frac"))
    assert three <= whole
    _, spans = synthetic()
    assert loop_capture.workers_busy_share_of_wait(spans) == pytest.approx(12 / 20)
    assert loop_capture.workers_busy_share_of_wait([]) is None


def test_interval_arithmetic_and_the_empty_trace():
    assert loop_capture.minus([[0, 10], [20, 30]], [[5, 22], [25, 26]]) == [
        [0, 5], [22, 25], [26, 30]]
    assert loop_capture.minus([[0, 10]], []) == [[0, 10]]
    assert loop_capture.minus([[0, 10]], [[0, 10]]) == []
    assert loop_capture.fence_intervals(
        [span("trainer.metrics_fetch", 1, 2), span("trainer.step", 2, 1),
         span("trainer.step", 7, 1)], hi=9 * MS) == [[1 * MS, 7 * MS]]
    empty = Reduction([])
    assert loop_capture.window_of(empty) is None
    assert loop_capture.reduce_loop([], empty) == {"program_spans": {}, "idle_s": None}
    # workers' spans on the loop's thread would be its children: why
    # load_spans tells the lines apart
    _, spans = synthetic()
    merged = [dict(s, thread="python3") for s in spans]
    wrong = program_spans.ProgramSpans(merged).totals()["dtpu.trainer.epoch"]["self_s"]
    right = program_spans.ProgramSpans(spans).totals()["dtpu.trainer.epoch"]["self_s"]
    assert right == pytest.approx(0.228) and wrong < right


def test_the_accepted_readers_read_this_drivers_observation_as_they_are(root):
    """Why the cell stands in their ``workloads``: the driver saves the
    step's ``op_name``s, counts ``param_bytes`` / ``moment_bytes`` /
    ``trace_steps`` and reports the rate and the memory as ``train_step``
    does."""
    counters, events = traced_counters()
    observed = observed_for(root, counters, events)
    assert read(observed, "models.fwd_bwd_ms_per_step") == pytest.approx(210 / 5)
    assert read(observed, "kernels.opt_update_ms_per_step") == pytest.approx(30 / 5)
    moved = 300e6 + 200e6  # read p, g, m; write p, m
    assert read(observed, "kernels.opt_update_roofline") == pytest.approx(
        100 * (moved / 819e9) / 6e-3, rel=1e-3)
    assert read(observed, "device.hbm_peak_frac") == pytest.approx(5 / 16)
    assert 0.3 < read(observed, "models.mfu") < 0.4
    assert read(observed, "entry.compiles_in_window") in (0, None)


# ------------------------------------------------------ on the recorded loop
def test_span_sourced_readers_on_the_recorded_train_loop(root):
    """``train_net.py`` on one v5e chip under the program's own capture
    (chip run of PR 24; ``test_benchmark_scoped_readers.py`` says what it
    holds): four steps, one print's fence, no ``epoch`` span, no worker
    annotation, and the profiler stopping while the device sat idle."""
    events, spans = loop_capture.load_capture(FIXTURE)
    assert {s["thread"] for s in spans} == {"python3#0"}
    assert spans == loop_capture.load_spans(FIXTURE)
    assert events == trace.load_events(FIXTURE)  # one pass, the same events
    device = Reduction(events)
    counters = {"trace_steps": 4, **loop_capture.reduce_loop(spans, device)}
    observed = observed_for(root, counters)
    observed.trace = device
    values = {name: read(observed, name) for name in NINE}
    assert values == {
        "trainer.data_wait_frac": None,
        "trainer.h2d_ms_per_step": None,
        "trainer.fetch_ms_per_step": None,
        "trainer.dispatch_ms_per_step": pytest.approx(6.67778625, abs=1e-6),
        "trainer.loop_self_ms_per_step": None,
        "trainer.idle_under_wait_frac": pytest.approx(2.252e-08, abs=1e-10),
        "trainer.idle_at_fence_frac": pytest.approx(0.0108597764, abs=1e-9),
        "trainer.idle_unattributed_frac": pytest.approx(0.3203522358, abs=1e-9),
        "loader.batch_ms": None,
    }
    idle = counters["idle_s"]
    assert idle["window"] == pytest.approx(0.93248605, abs=1e-9)
    assert idle["idle"] / idle["window"] == pytest.approx(device.idle_frac())
    assert sum(idle[c] for c in loop_capture.CAUSES) == pytest.approx(idle["idle"])
    assert loop_capture.workers_busy_share_of_wait(spans) == 0.0


# ---------------------------------------------------- through the real command
@pytest.fixture(scope="module")
def rehearsal(root):
    return finish(start_run(
        root, "--workload", CELL, "--seed", "2500000011", "--seconds", "1",
        "--trace", "1", "--rehearse"))


def test_rehearsal_prints_the_contracts_line_and_no_metric(rehearsal):
    code, out, err = rehearsal
    assert code == 0, err[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 8
    assert "reference:" in out and "agrees" in out and "trace:" in out


def test_rehearsed_loop_is_held_to_the_plain_loop_and_says_its_spans(rehearsal):
    _code, out, _err = rehearsal
    assert "state bit-identical True" in out
    assert "every batch once, in order" in out
    assert "trainer.steps 4" in out
    for name in ("dtpu.trainer.epoch", "dtpu.trainer.step", "dtpu.trainer.wait",
                 "dtpu.trainer.h2d", "dtpu.trainer.metrics_fetch",
                 "dtpu.loader.decode", "dtpu.loader.assemble"):
        assert f"span {name}:" in out
    assert "counters over the window: trainer.steps" in out
