"""The trace reduction: pure arithmetic on synthetic events (the cases of
tests/test_costmodel.py and tests/test_zero_overlap.py, kept beside the
benchmark's own copy of the reduction), the HLO-text parsing, and a recorded
trace of this installation with its numbers pinned."""

import os

import pytest

from benchmark.harness import trace
from benchmark.harness.trace import Reduction

FIXTURES = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "benchmark", "fixtures",
)


def op(name, start, dur, op_name="", plane="/device:TPU:0", line="XLA Ops"):
    _, opcode = trace.parse_instruction(name)
    return {"plane": plane, "line": line, "name": name, "opcode": opcode,
            "op_name": op_name, "start_ns": float(start), "dur_ns": float(dur)}


def span(name, start, dur):
    return {"plane": "host", "line": "python3", "name": "bench." + name,
            "opcode": "", "op_name": "", "start_ns": float(start),
            "dur_ns": float(dur)}


def test_interval_union_and_intersection():
    total, merged = trace.interval_union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert total == 6 and merged == [[0, 3], [5, 8]]
    assert trace.intersection([[0, 3], [5, 8]], [[2, 6], [7, 10]]) == 1 + 1 + 1
    assert trace.intersection([[0, 1]], [[1, 2]]) == 0


def test_scopes_unwrap_autodiff():
    fwd = "jit(train_step)/jit(main)/jvp(fwd)/ResNet/conv_general_dilated"
    bwd = "jit(train_step)/jit(main)/transpose(jvp(fwd))/ResNet/conv_general_dilated"
    assert trace.in_scope(fwd, "fwd") and trace.in_scope(bwd, "fwd")
    assert trace.in_scope("jit(x)/optimizer_update/mul", "optimizer_update")
    assert not trace.in_scope("jit(x)/misc/dot_general", "fwd")
    assert trace.scope_path("a/transpose(jvp(f))/b") == ["a", "f", "b"]


def test_instruction_parsing_takes_the_opcode_not_a_mention():
    text = ("%fusion.7 = (f32[8]{0:T(256)S(1)}, /*index=1*/bf16[4,4]{1,0:T(8,128)(2,1)}) "
            "fusion(f32[8]{0} %all-reduce.3, bf16[4,4]{1,0} %copy-done.1), "
            "kind=kOutput, calls=%fused_computation.2")
    assert trace.parse_instruction(text) == ("fusion.7", "fusion")
    # an operand that IS a collective's result does not make this one
    assert not trace.is_collective(op(text, 0, 1))
    assert trace.parse_instruction(
        "%all-reduce-start.1 = f32[64]{0:T(128)} all-reduce-start(f32[64]{0} %x), "
        "replica_groups={{0,1,2,3}}"
    ) == ("all-reduce-start.1", "all-reduce-start")
    assert trace.is_collective(op("all-reduce-done.1", 0, 1))
    assert trace.is_collective(op("reduce-scatter.3", 0, 1))
    assert not trace.is_collective(op("copy-start.2", 0, 1))
    hlo = (
        'ENTRY %main {\n'
        '  %p = f32[2]{0} parameter(0)\n'
        '  %optimizer_update.4 = f32[2]{0} custom-call(f32[2]{0} %p), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(train_step)/jit(main)/optimizer_update/pallas_call" '
        'source_file="opt_update.py" source_line=77}\n'
        '  ROOT %fusion.1 = f32[2]{0} fusion(f32[2]{0} %optimizer_update.4), '
        'kind=kLoop, calls=%fc, metadata={op_name="jit(train_step)/jit(main)/'
        'jvp(fwd)/ResNet/add"}\n}\n'
    )
    assert trace.op_names_from_hlo(hlo) == {
        "optimizer_update.4": "jit(train_step)/jit(main)/optimizer_update/pallas_call",
        "fusion.1": "jit(train_step)/jit(main)/jvp(fwd)/ResNet/add",
    }


def test_busy_idle_and_gap_attribution():
    events = [
        span("window", 0, 100),
        span("dispatch", 0, 12),
        span("fence", 12, 88),
        op("fusion.1", 10, 30),          # busy [10, 40)
        op("fusion.2", 35, 15),          # overlaps: union [10, 50)
        op("copy-start.1", 0, 100, line="Async XLA Ops"),  # a lifetime: not busy
        op("fusion.3", 70, 20),          # busy [70, 90)
        op("fusion.4", 95, 20),          # clipped to the window: [95, 100)
    ]
    r = Reduction(events)
    assert r.window_s() == pytest.approx(100e-9)
    assert r.busy_s() == pytest.approx((40 + 20 + 5) * 1e-9)
    assert r.idle_frac() == pytest.approx(0.35)
    gaps = r.idle_gaps(10)
    # longest first: [50, 70) under the fence, [0, 10) under dispatch, [90, 95)
    assert [g[0] for g in gaps] == ["bench.fence", "bench.dispatch", "bench.fence"]
    assert [g[1] for g in gaps] == pytest.approx([20e-9, 10e-9, 5e-9])
    assert r.idle_gaps(1) == [gaps[0]]


def test_window_falls_back_to_the_operations_and_gaps_say_so():
    r = Reduction([op("fusion.1", 10, 10), op("fusion.2", 40, 10)])
    assert r.window_s() == pytest.approx(40e-9)
    assert r.idle_frac() == pytest.approx(0.5)
    assert r.idle_gaps(3) == [["host: no span of the benchmark", pytest.approx(20e-9)]]
    empty = Reduction([])
    assert empty.devices == [] and empty.idle_frac() is None
    assert empty.top_ops(3) == [] and empty.idle_gaps(3) == []


def test_scope_time_top_ops_and_per_device_mean():
    update = "jit(train_step)/jit(main)/optimizer_update/pallas_call"
    conv = "jit(train_step)/jit(main)/transpose(jvp(fwd))/ResNet/ConvBN_0/conv_general_dilated"
    events = []
    for plane, scale in (("/device:TPU:0", 1.0), ("/device:TPU:1", 3.0)):
        events += [
            op("fusion.2", 0, 6e6 * scale, conv, plane=plane),
            op("fusion.2", 10e6 * scale, 4e6 * scale, conv, plane=plane),
            op("optimizer_update.1", 30e6 * scale, 1e6 * scale, update, plane=plane),
        ]
    r = Reduction(events)
    assert r.devices == ["/device:TPU:0", "/device:TPU:1"]
    assert r.scope_s("optimizer_update") == pytest.approx(2e-3)  # mean of 1 and 3 ms
    assert r.seconds_where(lambda e: not trace.in_scope(e["op_name"], "optimizer_update")) \
        == pytest.approx(20e-3)
    top = r.top_ops(1)
    assert top[0][0] == (
        "fusion.2 [bwd jit(main)/transpose(jvp(fwd))/ResNet/ConvBN_0/conv_general_dilated]"
    )
    assert top[0][1] == pytest.approx(20e-3)


@pytest.mark.parametrize("enclosing", [
    "%while.3 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %tuple.1), "
    "condition=%cond.1, body=%body.1",
    "%conditional.2 = f32[8]{0} conditional(pred[] %p, f32[8]{0} %a, f32[8]{0} %b), "
    "true_computation=%t, false_computation=%f",
    "%call.5 = f32[8]{0} call(f32[8]{0} %a), to_apply=%scanned",
])
def test_a_loop_is_counted_once(enclosing):
    """A ``while`` (or ``conditional``, ``call``) is an event AND so is every
    operation of its body: the sums and the breakdown take the body alone,
    the busy union takes both (the loop's control between two body
    operations is busy time)."""
    scope = "jit(train_step)/jvp(fwd)/Model/mixer/while/body/dot_general"
    events = [
        span("window", 0, 100),
        op(enclosing, 10, 60, scope),            # [10, 70): spans its body
        op("fusion.1", 12, 10, scope),           # the body's three operations
        op("fusion.2", 30, 10, scope),
        op("fusion.1", 50, 10, scope),
        op("fusion.9", 80, 10, "jit(train_step)/optimizer_update/mul"),
    ]
    r = Reduction(events)
    assert r.scope_s("mixer") == pytest.approx(30e-9)  # not 90
    assert r.seconds_where(lambda e: True) == pytest.approx(40e-9)
    assert [name.split(" ")[0] for name, _ in r.top_ops(5)] == ["fusion.1", "fusion.2", "fusion.9"]
    assert r.top_ops(1)[0][1] == pytest.approx(20e-9)
    # busy: [10, 70) and [80, 90), as it read while the loop was a summand
    assert r.busy_s() == pytest.approx(70e-9)
    assert r.idle_frac() == pytest.approx(0.3)
    assert [g[1] for g in r.idle_gaps(5)] == pytest.approx([10e-9, 10e-9, 10e-9])
    assert "5 operation events" not in r.describe()
    assert "4 operation events, 1 enclosing" in r.describe()
    # a plane that holds nothing but the loop's own event is still a device
    alone = Reduction([op(enclosing, 0, 10)])
    assert alone.devices == ["/device:TPU:0"] and alone.busy_s() == pytest.approx(10e-9)
    assert alone.seconds_where(lambda e: True) == 0 and alone.top_ops(3) == []


def test_a_collective_inside_a_loop_is_not_hidden_by_the_loop():
    loop = "%while.1 = (f32[8]{0}) while((f32[8]{0}) %t), condition=%c, body=%b"
    r = Reduction([op(loop, 0, 100), op("all-reduce.1", 10, 40), op("fusion.1", 30, 40)])
    assert r.collective_s() == pytest.approx(40e-9)
    assert r.collective_exposed_frac() == pytest.approx(0.5)  # [10, 30) of [10, 50)


def test_collective_time_and_exposed_share():
    # synchronous collective [0, 100); compute [50, 150): half hidden
    half = Reduction([op("all-reduce.1", 0, 100), op("fusion.1", 50, 100)])
    assert half.collective_s() == pytest.approx(100e-9)
    assert half.collective_exposed_frac() == pytest.approx(0.5)
    # fully serialized: fully exposed
    serial = Reduction([op("all-gather.1", 0, 100), op("fusion.1", 100, 100)])
    assert serial.collective_exposed_frac() == pytest.approx(1.0)
    # an asynchronous pair: brief start and done on the operations line, the
    # transfer's lifetime on the async line, compute under all of it
    hidden = Reduction([
        op("all-reduce-start.1", 10, 1),
        op("all-reduce-start.1", 10, 60, line="Async XLA Ops"),
        op("all-reduce-done.1", 69, 1),
        op("fusion.1", 0, 100),
    ])
    assert hidden.collective_s() == pytest.approx(60e-9)
    assert hidden.collective_exposed_frac() == pytest.approx(0.0)
    # the same pair with compute ending early: the done waits, exposed
    waits = Reduction([
        op("all-reduce-start.1", 10, 1),
        op("all-reduce-start.1", 10, 60, line="Async XLA Ops"),
        op("all-reduce-done.1", 40, 30),
        op("fusion.1", 0, 40),
    ])
    assert waits.collective_exposed_frac() == pytest.approx(0.5)
    assert Reduction([op("fusion.1", 0, 10)]).collective_exposed_frac() is None
    assert Reduction([op("fusion.1", 0, 10)]).collective_s() == 0.0


# ------------------------------------------------- a trace of this installation
XPLANE = os.path.join(FIXTURES, "resnet50_train.xplane.pb.gz")
OP_NAMES = os.path.join(FIXTURES, "resnet50_train.op_names.json.gz")


@pytest.fixture(scope="module")
def recorded():
    """Three steps of ``resnet50.train`` between two fences on one v5e chip
    (jax 0.9.0, libtpu 0.0.34; chip run of PR 22), as the driver captures
    them, with the op_name map from the program's compiled HLO text."""
    return Reduction.from_file(XPLANE, OP_NAMES)


def test_recorded_trace_busy_idle_and_gaps(recorded):
    assert recorded.devices == ["/device:TPU:0"]
    assert recorded.window_s() == pytest.approx(0.147810616, abs=1e-9)
    assert recorded.busy_s() == pytest.approx(0.145055878, abs=1e-9)
    assert recorded.idle_frac() == pytest.approx(0.0186369, abs=1e-6)
    gaps = recorded.idle_gaps(5)
    # the longest gap is the host noticing the end; then the first dispatch
    assert gaps[0] == ["bench.fence", pytest.approx(0.002408645, abs=1e-9)]
    assert gaps[1] == ["bench.dispatch", pytest.approx(0.000288529, abs=1e-9)]
    assert all(g[1] < 2e-5 for g in gaps[2:])  # between steps: microseconds
    assert recorded.collective_s() == 0.0
    assert recorded.collective_exposed_frac() is None  # one chip


def test_recorded_trace_scopes_and_top_operation(recorded):
    # 161 Pallas calls, the reshapes around them and a few fusions per step
    assert recorded.scope_s("optimizer_update") * 1e3 / 3 == pytest.approx(
        1.8688847, abs=1e-6)
    top = recorded.top_ops(10)
    assert top[0] == [
        "fusion.29 [bwd ResNet/Bottleneck_1/ConvBN_0/Conv_0/conv_general_dilated]",
        pytest.approx(0.004531759, abs=1e-9),
    ]
    assert len(top) == 10 and all(a[1] >= b[1] for a, b in zip(top, top[1:]))
    ops = recorded.ops["/device:TPU:0"]
    kernels = [e for e in ops if e["opcode"] == "custom-call"
               and trace.in_scope(e["op_name"], "optimizer_update")]
    assert len(kernels) == 3 * 161  # one per parameter leaf per step
    # the backward is the transpose of the forward's scope
    assert any("transpose(jvp(fwd))" in e["op_name"] for e in ops)


def test_readers_on_the_recorded_trace(recorded):
    """Every per-layer reader of the cell, on the recorded trace and the
    counters that run had."""
    from benchmark.harness.discovery import Catalog
    from benchmark.harness.observation import Observed

    catalog = Catalog()
    cell = catalog.cell("resnet50.train")
    observed = Observed(
        cell=cell, section=lambda name: cell.config[name], traffic=cell.traffic,
        end_to_end={"train_items_per_s_per_chip": 2635.0, "setup_s": 40.0},
        counters={"trace_steps": 3, "param_bytes": 4 * 25557032,
                  "moment_bytes": 4 * 25557032, "compiles_in_window": 0},
        device={"memory_peak_bytes": 5013287936, "memory_limit_bytes": 16909336064},
        peaks=catalog.peaks("TPU v5 lite"), catalog=catalog, trace=recorded,
    )
    values = {m["name"]: catalog.layer_metric(m).read(observed) for m in cell.per_layer}
    # the values this trace pins; a reader listed on the cell later (PR 40:
    # the scopes' readers, which find nothing in this scope-less recording,
    # and two that read the process's registry) is allowed beside them
    pinned = {
        "entry.compiles_in_window": 0.0,
        # 3 x 2 x 4.089 GMAC x 2635 items/s over 197 TFLOP/s
        "models.mfu": pytest.approx(0.3281726, abs=1e-6),
        "models.fwd_bwd_ms_per_step": pytest.approx(46.4830747, abs=1e-6),
        "kernels.opt_update_ms_per_step": pytest.approx(1.8688847, abs=1e-6),
        # 5 x 102.2 MB at 819 GB/s is 0.624 ms
        "kernels.opt_update_roofline": pytest.approx(33.394428, abs=1e-5),
        "device.idle_frac": pytest.approx(0.0186369, abs=1e-6),
        "device.hbm_peak_frac": pytest.approx(0.2964805, abs=1e-6),
    }
    assert {name: values[name] for name in pinned} == pinned
    # a trace recorded before the program had scopes: their readers find
    # nothing, and say nothing
    for name in ("models.fwd_ms_per_step", "models.bwd_ms_per_step",
                 "kernels.opt_kernel_ms_per_step"):
        assert values[name] is None
    # a reader that finds nothing to read returns nothing
    observed.trace = None
    observed.counters = {}
    quiet = {m["name"]: catalog.layer_metric(m).read(observed) for m in cell.per_layer}
    assert [k for k in pinned if quiet[k] is not None] == [
        "models.mfu", "device.hbm_peak_frac"]
