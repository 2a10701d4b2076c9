"""The host-fed cell ``resnet50.trainloop_hostfed``: what ``BENCHMARK.json``
holds for it (the three counter readers and the accepted readers that read
this driver's observation; the six span-sourced readers and
``device.idle_frac`` are held back, ``HELD_BACK`` says why), the nine
``trainer.*`` / ``loader.*`` readers on synthetic spans, counters and device
events and on the recorded loop (``fixtures/trainloop_spans``), the accepted
readers on this driver's observation, and the driver at a tiny size through
the real command."""

import json
import os

import pytest

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
            "kernels.opt_update_roofline", "device.hbm_peak_frac",
            # the same resnet50 step through the same lowering and the
            # process-wide registry as ``resnet50.train``, which lists them
            "models.fwd_ms_per_step", "models.bwd_ms_per_step",
            "kernels.opt_kernel_ms_per_step", "entry.lower_s", "entry.init_state_s")
# Readers whose FILES and tests are here and whose entries are not in
# BENCHMARK.json for this cell: they read the traced epoch, and a capture of
# this traffic measures the tracer (PJRT lays a uint8 NHWC batch out tile by
# tile on the host, ~400,000 host events a batch: the traced epoch runs at
# 240 ms a step against the window's 48; PERF.md section 6, PR 35). The
# ledger must not carry them for this cell until the batch goes over the
# wire flat (PERF.md section 7).
HELD_BACK = {"device.idle_frac"} | {
    name for name, (_, source, _) in NINE.items() if source == "program_span"}
MOVES = "train_items_per_s_per_chip"

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


def observed_for(counters, events=None):
    catalog = Catalog()
    cell = catalog.cell(CELL)
    return Observed(
        cell=cell, section=lambda name: cell.config[name], traffic=cell.traffic,
        end_to_end={"train_items_per_s_per_chip": 2700.0, "setup_s": 1.0},
        counters=counters,
        device={"memory_peak_bytes": 5 * 2**30, "memory_limit_bytes": 16 * 2**30},
        peaks=catalog.peaks("TPU v5 lite"), catalog=catalog,
        trace=None if events is None else Reduction(events),
    )


def entry_of(catalog, name):
    """A reader's entry: ``BENCHMARK.json``'s where it has one, else (the
    held-back six) what its file must declare, from ISSUE 35's table."""
    for m in catalog.benchmark["per_layer"]:
        if m["name"] == name:
            return m
    layer, source, unit = NINE[name]
    return {"name": name, "layer": layer, "source": source, "unit": unit,
            "moves": MOVES}


def read(observed, name):
    catalog = observed.catalog
    return catalog.layer_metric(entry_of(catalog, name)).read(observed)


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
def test_the_cell_is_one_chip_on_resnet50_with_the_nine_declared():
    catalog = Catalog()
    (entry,) = [w for w in catalog.benchmark["workloads"] if w["name"] == CELL]  # landed
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "resnet50", "train_loop_hostfed", 1)
    # it says itself that it carries no idle figure, and so does its config
    assert len(entry["why"]) <= 200 and "no idle figure" in entry["why"]
    (config,) = [c for c in catalog.benchmark["configs"] if c["name"] == "resnet50"]
    assert "no idle figure" in config["why"]
    cell = catalog.cell(CELL)
    assert cell.traffic["driver"] == "train_loop"
    # 16 traced steps: the admitted readers of the capture read device
    # kernels, which 16 steps give as well as 64 (stop_trace 35 s, not 122)
    # ONE epoch fills the window (384 steps of ~48 ms in 20 s), so prints,
    # flush and turn weigh as in a user's long epoch; the reference follows
    # the warm-up epoch's first three steps
    assert (cell.traffic["pool_images"], cell.traffic["epoch_steps"],
            cell.traffic["warmup_steps"], cell.traffic["trace_steps"],
            cell.traffic["follow_steps"]) == (2048, 384, 32, 16, 3)
    # the loop's parameters, what the reference is told of the optimizer and
    # a limit for each number compared; nothing here opens a sink
    assert set(cell.traffic) == {
        "driver", "description", "pool_images", "warmup_steps", "epoch_steps",
        "trace_steps", "follow_steps", "reference_sgd", "limits", "overrides",
        "rehearse"}
    assert cell.traffic["reference_sgd"]["lr"] == 0.002  # 0.02 x WARMUP_FACTOR
    for limits in (cell.traffic["limits"], cell.traffic["rehearse"]["limits"]):
        assert set(limits) == {
            "set_from", "statistics_norm_median_leaf", "gradient_norm_median_leaf",
            "change_norm_median_leaf"}
        assert all(0 < v < 1 for k, v in limits.items() if k != "set_from")
    assert cell.traffic["overrides"] == {}
    assert {m["name"] for m in cell.end_to_end} == {MOVES, "setup_s"}
    by_name = {m["name"]: m for m in catalog.benchmark["per_layer"]}
    for name, (layer, source, unit) in NINE.items():
        m = entry_of(catalog, name)
        assert (m["layer"], m["source"], m["unit"], m["moves"]) == (
            layer, source, unit, MOVES)
        catalog.layer_metric(m)  # its file declares the same
        if source == "program_counter":  # landed: this cell lists it
            assert by_name[name]["better"] == "lower"
            assert CELL in by_name[name]["workloads"]
    # what reads the traced epoch is held back: the readers' files are here,
    # this cell's line (and so the ledger) never carries them
    assert len(HELD_BACK) == 7
    reported = {m["name"] for m in cell.per_layer}
    assert not reported & HELD_BACK
    assert not (HELD_BACK - {"device.idle_frac"}) & set(by_name)
    assert CELL not in by_name["device.idle_frac"]["workloads"]
    assert reported == (set(NINE) - HELD_BACK) | set(ACCEPTED) | {
        "entry.compiles_in_window"}
    # the cell is a member of each accepted entry it reports under: where it
    # stands in a list is nobody's business
    for name in ACCEPTED:
        assert CELL in by_name[name]["workloads"]
    (rate,) = [m for m in catalog.benchmark["end_to_end"] if m["name"] == MOVES]
    assert CELL in rate["workloads"] and rate["bound"] == 0.01
    # nothing is left of the second entry point or of the parked entries
    bench = os.path.join(REPO, "benchmark")
    assert not os.path.exists(os.path.join(bench, "run_pending.py"))
    assert not os.path.exists(os.path.join(bench, "pending", CELL + ".json"))


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
def test_reader_on_synthetic_spans_counters_and_device_events(name):
    """A case a reader: its value on the synthetic epoch, and nothing (no
    exception either) where the program has no such span or counter: the
    parent of the PR that added them, or an untraced run."""
    counters, events = traced_counters()
    assert read(observed_for(counters, events), name) == pytest.approx(EXPECTED[name])
    assert read(observed_for({"trace_steps": 0, "window_s": 20.0}), name) is None
    # the parent's program traced: wait/h2d/step/metrics_fetch, no epoch, no
    # worker annotations, no counter
    _, spans = synthetic()
    old = [s for s in spans if s["name"].split(".")[-1] in
           ("wait", "h2d", "step", "metrics_fetch")]
    parent = {"trace_steps": 5, "window_s": 20.0,
              **loop_capture.reduce_loop(old, Reduction(events))}
    value = read(observed_for(parent, events), name)
    if NINE[name][1] == "program_counter" or name in (
            "trainer.loop_self_ms_per_step", "loader.batch_ms"):
        assert value is None
    else:
        assert value == pytest.approx(EXPECTED[name])


def test_idle_by_cause_sums_to_the_devices_idle_share():
    counters, events = traced_counters()
    observed = observed_for(counters, events)
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


def test_the_accepted_readers_read_this_drivers_observation_as_they_are():
    """Why the cell stands in their ``workloads``: the driver saves the
    step's ``op_name``s, counts ``param_bytes`` / ``moment_bytes`` /
    ``trace_steps`` and reports the rate and the memory as ``train_step``
    does."""
    counters, events = traced_counters()
    observed = observed_for(counters, events)
    assert read(observed, "models.fwd_bwd_ms_per_step") == pytest.approx(210 / 5)
    assert read(observed, "kernels.opt_update_ms_per_step") == pytest.approx(30 / 5)
    moved = 300e6 + 200e6  # read p, g, m; write p, m
    assert read(observed, "kernels.opt_update_roofline") == pytest.approx(
        100 * (moved / 819e9) / 6e-3, rel=1e-3)
    assert read(observed, "device.hbm_peak_frac") == pytest.approx(5 / 16)
    assert 0.3 < read(observed, "models.mfu") < 0.4
    assert read(observed, "entry.compiles_in_window") in (0, None)
    assert read(observed, "kernels.opt_kernel_ms_per_step") == pytest.approx(30 / 5)
    # the synthetic step has no ``bwd`` scope: every operation would read as
    # forward, so ``fwd`` says nothing (and ``bwd`` has nothing to sum)
    assert read(observed, "models.fwd_ms_per_step") is None
    assert read(observed, "models.bwd_ms_per_step") is None


# ------------------------------------------------------ on the recorded loop
def test_span_sourced_readers_on_the_recorded_train_loop():
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
    observed = observed_for(counters)
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
def rehearsal():
    return finish(start_run(
        REPO, "--workload", CELL, "--seed", "2500000011", "--seconds", "1",
        "--trace", "1", "--rehearse"))


def test_rehearsal_prints_the_contracts_line_and_no_metric(rehearsal):
    code, out, err = rehearsal
    assert code == 0, err[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device",
                          "compared"]  # each number beside its limit, last
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 8
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    assert "reference: 3 steps followed" in out and "trace:" in out
    assert err.strip().splitlines()[-1].startswith("compared nonfinite_losses 0 limit 0")


def test_rehearsed_loop_is_held_to_the_plain_loop_and_counts_its_batches(rehearsal):
    _code, out, _err = rehearsal
    assert "state bit-identical True" in out
    assert "batches assembled 4, missed by the loop's counts 0" in out
    assert "every batch once, in order" in out
    # the traced path reads device events alone: the spans' totals and the
    # idle time by cause measure the tracer in this traffic and are not
    # worked out (their readers keep their tests above)
    assert "span dtpu." not in out and "traced epoch: 4 steps" in out
    assert "counters over the window: trainer.steps" in out
