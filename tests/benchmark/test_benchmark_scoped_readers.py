"""The readers of the step's inner scopes (``fwd``/``bwd``,
``opt_tile``/``opt_kernel``), of the set-up counters, and the program-span
reduction: on synthetic events, and pinned on a recorded trace of this
installation (``fixtures/resnet50_train_scoped``)."""

import json
import os

import pytest

from benchmark.harness import program_spans, trace
from benchmark.harness.discovery import Catalog
from benchmark.harness.observation import Observed
from benchmark.harness.trace import Reduction
from benchmark_testlib import REPO
from distribuuuu_tpu.telemetry import get_registry

FIXTURES = os.path.join(REPO, "benchmark", "fixtures")
NEW = ("models.fwd_ms_per_step", "models.bwd_ms_per_step",
       "kernels.opt_kernel_ms_per_step", "kernels.opt_tile_ms_per_step",
       "entry.lower_s", "entry.init_state_s")

FWD = "jit(train_step)/jvp(fwd)/ResNet/ConvBN_0/conv_general_dilated"
BWD = "jit(train_step)/bwd/transpose(jvp(fwd))/ResNet/ConvBN_0/conv_general_dilated"
LOSS = "jit(train_step)/jvp(jit(log_softmax))/sub"
LOSS_BWD = "jit(train_step)/bwd/transpose(jvp(jit(log_softmax)))/mul"
TILE = "jit(train_step)/optimizer_update/opt_tile/reshape"
KERNEL = "jit(train_step)/optimizer_update/opt_kernel/dtpu_opt_update_sgd/pallas_call"
SHARDED = "jit(train_step)/optimizer_update/shard_map/opt_kernel/dtpu_opt_update_sgd/pallas_call"
UPDATE_REST = "jit(train_step)/optimizer_update/add"


def op(name, start, dur, op_name="", plane="/device:TPU:0", line="XLA Ops"):
    _, opcode = trace.parse_instruction(name)
    return {"plane": plane, "line": line, "name": name, "opcode": opcode,
            "op_name": op_name, "start_ns": float(start), "dur_ns": float(dur)}


def span(name, start, dur, thread="python3"):
    return {"name": name, "thread": thread, "start_ns": float(start),
            "dur_ns": float(dur)}


@pytest.fixture
def registry():
    reg = get_registry()
    reg.reset()
    yield reg
    reg.reset()


def observed_for(cell_name, events, counters, catalog=None):
    catalog = catalog or Catalog()
    cell = catalog.cell(cell_name)
    return Observed(
        cell=cell, section=lambda name: cell.config[name], traffic=cell.traffic,
        end_to_end={"train_items_per_s_per_chip": 1.0, "setup_s": 1.0},
        counters=counters, device={}, peaks=catalog.peaks("TPU v5 lite"),
        catalog=catalog, trace=None if events is None else Reduction(events),
    )


def read_new(observed):
    by_name = {m["name"]: m for m in observed.catalog.benchmark["per_layer"]}
    return {n: observed.catalog.layer_metric(by_name[n]).read(observed) for n in NEW}


def test_the_six_are_declared_for_the_cells_that_report_them():
    catalog = Catalog()
    for cell_name in ("regnety_160.train", "resnet50.train_dp4"):
        names = [m["name"] for m in catalog.cell(cell_name).per_layer]
        assert set(NEW) <= set(names)
    # one chip of ResNet-50 tiles no leaf (`opt_tile` read 1.6e-05 ms a step
    # on the chip, PR 40): the cell lists the other five
    names = {m["name"] for m in catalog.cell("resnet50.train").per_layer}
    assert set(NEW) - names == {"kernels.opt_tile_ms_per_step"}
    by_name = {m["name"]: m for m in catalog.benchmark["per_layer"]}
    assert [by_name[n]["moves"] for n in NEW] == (
        ["train_items_per_s_per_chip"] * 4 + ["setup_s"] * 2)
    assert [by_name[n]["source"] for n in NEW] == (
        ["device_trace"] * 4 + ["program_counter"] * 2)


def test_scope_readers_on_synthetic_events(registry):
    """Two steps on two devices; per step and device: forward 10, loss 1,
    backward 20 + 2 (the loss's), an all-reduce 4 under the backward's scope,
    tiling 3, kernel 5, the update's own fusion 1."""
    events = []
    for plane in ("/device:TPU:0", "/device:TPU:1"):
        t = 0
        for _step in range(2):
            for name, dur, op_name in (
                ("fusion.1", 10e6, FWD), ("fusion.2", 1e6, LOSS),
                ("fusion.3", 2e6, LOSS_BWD), ("fusion.4", 20e6, BWD),
                ("all-reduce.1", 4e6, BWD), ("bitcast.1", 3e6, TILE),
                ("dtpu_opt_update_sgd.1", 5e6, SHARDED), ("fusion.5", 1e6, UPDATE_REST),
            ):
                events.append(op(name, t, dur, op_name, plane=plane))
                t += dur
    registry.counter("setup.lower_s").inc(1.25)
    registry.counter("setup.init_state_s").inc(7.5)
    observed = observed_for("resnet50.train_dp4", events, {"trace_steps": 2})
    values = read_new(observed)
    assert values == {
        "models.fwd_ms_per_step": pytest.approx(10.0),
        "models.bwd_ms_per_step": pytest.approx(22.0),
        "kernels.opt_kernel_ms_per_step": pytest.approx(5.0),
        "kernels.opt_tile_ms_per_step": pytest.approx(3.0),
        "entry.lower_s": 1.25,
        "entry.init_state_s": 7.5,
    }
    # the identities of the split: the parts stay inside the wholes
    catalog = observed.catalog
    by_name = {m["name"]: m for m in catalog.benchmark["per_layer"]}
    whole = catalog.layer_metric(by_name["models.fwd_bwd_ms_per_step"]).read(observed)
    update = catalog.layer_metric(by_name["kernels.opt_update_ms_per_step"]).read(observed)
    assert whole == pytest.approx(33.0) and update == pytest.approx(9.0)
    assert values["models.fwd_ms_per_step"] + values["models.bwd_ms_per_step"] <= whole
    assert (values["kernels.opt_kernel_ms_per_step"]
            + values["kernels.opt_tile_ms_per_step"]) <= update


def test_readers_find_nothing_in_a_program_without_the_scopes(registry):
    """The parent's program: ``fwd`` and ``optimizer_update`` only, no
    counter. Every new reader returns nothing and none raises; so does each
    without a trace."""
    old_bwd = "jit(train_step)/transpose(jvp(fwd))/ResNet/ConvBN_0/conv_general_dilated"
    old_kernel = "jit(train_step)/optimizer_update/pallas_call"
    events = [
        op("fusion.1", 0, 10e6, FWD), op("fusion.4", 10e6, 20e6, old_bwd),
        op("optimizer_update.1", 30e6, 5e6, old_kernel),
    ]
    observed = observed_for("regnety_160.train", events, {"trace_steps": 1})
    assert read_new(observed) == dict.fromkeys(NEW)
    assert read_new(observed_for("regnety_160.train", None, {})) == dict.fromkeys(NEW)


def test_program_span_totals_self_time_and_gap_attribution():
    spans = [
        span("dtpu.trainer.wait", 0, 10),
        span("dtpu.trainer.h2d", 10, 5),
        span("dtpu.trainer.step", 15, 5),
        span("dtpu.ckpt.ckpt_save", 40, 50),
        span("dtpu.ckpt.ckpt_snapshot", 45, 20),     # nested in ckpt_save
        span("dtpu.trainer.wait", 50, 10, thread="loader"),  # another thread
        span("dtpu.trainer.metrics_fetch", 90, 10),
    ]
    found = program_spans.ProgramSpans(spans)
    totals = found.totals()
    assert totals["dtpu.trainer.wait"] == {
        "count": 2, "total_s": pytest.approx(20e-9), "self_s": pytest.approx(20e-9)}
    assert totals["dtpu.ckpt.ckpt_save"]["total_s"] == pytest.approx(50e-9)
    assert totals["dtpu.ckpt.ckpt_save"]["self_s"] == pytest.approx(30e-9)
    assert totals["dtpu.ckpt.ckpt_snapshot"]["self_s"] == pytest.approx(20e-9)
    assert set(found.totals(lo=40, hi=90)) == {
        "dtpu.ckpt.ckpt_save", "dtpu.ckpt.ckpt_snapshot", "dtpu.trainer.wait"}
    assert found.totals(lo=40, hi=90)["dtpu.trainer.wait"]["count"] == 1
    # of two spans that cover an interval whole, the inner one
    assert found.covering(46, 49) == "dtpu.ckpt.ckpt_snapshot"
    assert found.covering(70, 80) == "dtpu.ckpt.ckpt_save"
    assert found.covering(20, 40) == program_spans.NO_SPAN

    # device busy [20, 40) and [65, 90), then [100, 110): gaps [40, 65) and
    # [90, 100); the first lies under ckpt_save (25) with the snapshot
    # covering 20 of it and the loader thread's wait 10
    device = Reduction([op("fusion.1", 20, 20), op("fusion.2", 65, 25),
                        op("fusion.3", 100, 10)])
    assert program_spans.device_window(device) == (20.0, 110.0)
    assert program_spans.device_gaps(device) == [(40.0, 65.0), (90.0, 100.0)]
    assert found.idle_gaps(device, 5) == [
        ["dtpu.ckpt.ckpt_save", pytest.approx(25e-9)],
        ["dtpu.trainer.metrics_fetch", pytest.approx(10e-9)],
    ]
    # a wider window shows the start-up gap too, under wait/h2d/step
    assert found.idle_gaps(device, 1, lo=0)[0] == [
        "dtpu.ckpt.ckpt_save", pytest.approx(25e-9)]
    assert found.idle_gaps(device, 5, lo=0)[1] == [
        "dtpu.trainer.wait", pytest.approx(20e-9)]
    assert program_spans.ProgramSpans([]).idle_gaps(Reduction([]), 3) == []


# ------------------------------------------------- a trace of this installation
XPLANE = os.path.join(FIXTURES, "resnet50_train_scoped.xplane.pb.gz")
OP_NAMES = os.path.join(FIXTURES, "resnet50_train_scoped.op_names.json.gz")


@pytest.fixture(scope="module")
def recorded():
    """Three steps of ``resnet50.train`` between two fences on one v5e chip
    (jax 0.9.0, libtpu 0.0.34; chip run of PR 24), as the driver captures
    them from the program that carries the ``bwd`` / ``opt_tile`` /
    ``opt_kernel`` scopes, with the op_name map from its compiled HLO text."""
    return Reduction.from_file(XPLANE, OP_NAMES)


def test_recorded_trace_names_the_kernel_and_carries_the_scopes(recorded):
    ops = recorded.ops["/device:TPU:0"]
    kernels = [e for e in ops if trace.in_scope(e["op_name"], "opt_kernel")]
    assert len(kernels) == 3 * 161  # one Pallas call per parameter leaf per step
    # the instruction is named after the kernel's name=, not after a scope
    assert all(e["name"].startswith("dtpu_opt_update_sgd.") for e in kernels)
    assert all(e["opcode"] == "custom-call" for e in kernels)
    assert kernels[0]["op_name"] == (
        "jit(train_step)/optimizer_update/opt_kernel/dtpu_opt_update_sgd/pallas_call")
    assert any("bwd/transpose(jvp(fwd))" in e["op_name"] for e in ops)
    assert not any(trace.in_scope(e["op_name"], "opt_tile")
                   and trace.in_scope(e["op_name"], "opt_kernel") for e in ops)
    assert recorded.window_s() == pytest.approx(0.148262976, abs=1e-9)
    assert recorded.busy_s() == pytest.approx(0.14506294, abs=1e-9)


def test_the_six_readers_on_the_recorded_trace(recorded, registry):
    registry.counter("setup.lower_s").inc(2.897524152)   # that run's own
    registry.counter("setup.init_state_s").inc(23.719429112)
    observed = observed_for("regnety_160.train", None, {"trace_steps": 3})
    observed.trace = recorded
    values = read_new(observed)
    assert values == {
        "models.fwd_ms_per_step": pytest.approx(14.3313417, abs=1e-6),
        "models.bwd_ms_per_step": pytest.approx(29.3704907, abs=1e-6),
        "kernels.opt_kernel_ms_per_step": pytest.approx(0.4234627, abs=1e-6),
        "kernels.opt_tile_ms_per_step": pytest.approx(1.445259, abs=1e-6),
        "entry.lower_s": pytest.approx(2.897524152),
        "entry.init_state_s": pytest.approx(23.719429112),
    }
    by_name = {m["name"]: m for m in observed.catalog.benchmark["per_layer"]}

    def old(name):
        return observed.catalog.layer_metric(by_name[name]).read(observed)

    # the wholes read what they read before the scopes (the first fixture:
    # 46.4830747 and 1.8688847 ms), and the parts stay inside them: 2.78 ms of
    # a step is loss, metrics and copies; the update is tile + kernel
    whole, update = old("models.fwd_bwd_ms_per_step"), old("kernels.opt_update_ms_per_step")
    assert whole == pytest.approx(46.4855917, abs=1e-6)
    assert update == pytest.approx(1.8687217, abs=1e-6)
    assert whole - values["models.fwd_ms_per_step"] - values["models.bwd_ms_per_step"] \
        == pytest.approx(2.7837593, abs=1e-6)
    assert (values["kernels.opt_kernel_ms_per_step"]
            + values["kernels.opt_tile_ms_per_step"]) == pytest.approx(update, abs=1e-6)


def test_device_gaps_of_the_recorded_trace(recorded):
    """The driver's trace holds no program span (the cells call the jitted
    step directly); the gaps are the device's own, found without a window
    span: first operation start to last operation end."""
    found = program_spans.ProgramSpans.from_file(XPLANE)
    assert found.names() == []
    gaps = program_spans.device_gaps(recorded)
    assert (gaps[0][1] - gaps[0][0]) == pytest.approx(16451, abs=1)  # between steps
    assert found.idle_gaps(recorded, 1) == [
        [program_spans.NO_SPAN, pytest.approx(16.451e-6, abs=1e-9)]]


LOOP_XPLANE = os.path.join(FIXTURES, "trainloop_spans.xplane.pb.gz")


def test_program_spans_of_a_recorded_train_loop():
    """``train_net.py`` (ResNet-50, bf16, batch 128, dummy input) on one v5e
    chip with ``PROF.START_STEP 58 PROF.NUM_STEPS 4`` (chip run of PR 24):
    the capture holds the print at batch 60, whose ``float(loss)`` reads
    are the loop's only fence. The file is the profiler's, cut to what the
    two loaders read (the device's ``XLA Ops`` / ``Async XLA Ops`` lines and
    the host's ``dtpu.*`` events; 40 MB of Python-tracer events dropped)."""
    found = program_spans.ProgramSpans.from_file(LOOP_XPLANE)
    assert found.names() == [
        "dtpu.trainer.h2d", "dtpu.trainer.metrics_fetch", "dtpu.trainer.step",
        "dtpu.trainer.wait"]
    totals = found.totals()
    assert {n: t["count"] for n, t in totals.items()} == {
        "dtpu.trainer.step": 4, "dtpu.trainer.wait": 3, "dtpu.trainer.h2d": 3,
        "dtpu.trainer.metrics_fetch": 1}
    assert totals["dtpu.trainer.metrics_fetch"]["total_s"] == pytest.approx(
        0.509732665, abs=1e-9)
    assert totals["dtpu.trainer.step"]["self_s"] == pytest.approx(0.026711145, abs=1e-9)
    assert totals["dtpu.trainer.h2d"]["total_s"] == pytest.approx(0.006459902, abs=1e-9)

    device = Reduction(trace.load_events(LOOP_XPLANE))
    assert device.devices == ["/device:TPU:0"] and device.spans == []  # no bench.* span
    lo, hi = program_spans.device_window(device)
    assert (hi - lo) / 1e9 == pytest.approx(0.93248605, abs=1e-9)
    assert device.idle_frac() == pytest.approx(0.3324968, abs=1e-6)
    # same clock: the fetch waits for the device to drain the steps the host
    # had dispatched ahead, so the device is busy for nearly all of it
    (fetch,) = [s for s in found.spans if s["name"] == "dtpu.trainer.metrics_fetch"]
    inside = sum(
        e["dur_ns"] for e in device.ops["/device:TPU:0"]
        if fetch["start_ns"] <= e["start_ns"]
        and e["start_ns"] + e["dur_ns"] <= fetch["start_ns"] + fetch["dur_ns"])
    assert 0.95 < inside / fetch["dur_ns"] <= 1.0
    gaps = found.idle_gaps(device, 3)
    # the longest gap is the profiler stopping (no program span covers it);
    # the next is the queue run dry before the fetch returned to the host
    assert gaps[0] == [program_spans.NO_SPAN, pytest.approx(0.298710363, abs=1e-9)]
    assert gaps[1] == ["dtpu.trainer.metrics_fetch", pytest.approx(0.011070167, abs=1e-9)]
    # what trace.py alone says of the same gaps: nothing
    assert device.idle_gaps(1)[0][0] == "host: no span of the benchmark"
