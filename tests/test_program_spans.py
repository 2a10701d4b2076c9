"""One span API, two sinks, one clock: every program span is also a
``jax.profiler.TraceAnnotation`` named ``dtpu.<layer>.<name>``; the set-up
timers and the compile listener count into the registry whether or not a sink
is open; the static check knows the span names."""

import glob
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from distribuuuu_tpu import telemetry, trainer
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu.parallel import mesh as mesh_lib
from distribuuuu_tpu.parallel.partition import lowering, topology as topo_lib
from distribuuuu_tpu.telemetry import runtime, schema, spans
from distribuuuu_tpu.utils.optim import construct_optimizer

from benchmark.harness import loop_capture, program_spans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


@pytest.fixture
def registry():
    # a sink that an earlier test of this worker left open (any test that
    # runs ``trainer.train_model`` does) is not this file's to assert on
    spans.close_telemetry()
    reg = telemetry.get_registry()
    reg.reset()
    yield reg
    reg.reset()
    spans.close_telemetry()


def _capture(tmp_path, body):
    trace_dir = str(tmp_path / "profile")
    jax.profiler.start_trace(trace_dir)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return path


def test_program_spans_land_in_a_profiler_capture_on_its_clock(tmp_path, registry):
    """``span()`` and ``annotate()`` under a CPU capture: found by
    ``program_spans`` under their ``dtpu.*`` names, nested as opened, and the
    span brackets the device-side event dispatched (and fenced) inside it —
    with the JSONL sink closed, and with it open the record is unchanged."""
    matmul = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((256, 256))
    matmul(x).block_until_ready()  # compiled before the capture

    def body():
        with spans.span("ckpt_save", ckpt="c"):
            with spans.annotate("step"):
                y = matmul(x)
            with spans.span("metrics_fetch"):
                float(y)

    assert not spans.enabled()
    path = _capture(tmp_path, body)
    found = program_spans.ProgramSpans.from_file(path)
    assert found.names() == [
        "dtpu.ckpt.ckpt_save", "dtpu.trainer.metrics_fetch",
        "dtpu.trainer.step",
    ]
    by_name = {s["name"]: s for s in found.spans}
    outer = by_name["dtpu.ckpt.ckpt_save"]
    for inner in ("dtpu.trainer.step", "dtpu.trainer.metrics_fetch"):
        assert outer["start_ns"] <= by_name[inner]["start_ns"]
        assert (by_name[inner]["start_ns"] + by_name[inner]["dur_ns"]
                <= outer["start_ns"] + outer["dur_ns"])
    totals = found.totals()
    assert totals["dtpu.ckpt.ckpt_save"]["self_s"] == pytest.approx(
        (outer["dur_ns"] - by_name["dtpu.trainer.step"]["dur_ns"]
         - by_name["dtpu.trainer.metrics_fetch"]["dur_ns"]) / 1e9)
    # the device-side event of the dispatched program, on the same clock
    (dot,) = program_spans.load_spans(path, prefix="dot_general")
    assert outer["start_ns"] <= dot["start_ns"]
    assert dot["start_ns"] + dot["dur_ns"] <= outer["start_ns"] + outer["dur_ns"]
    assert by_name["dtpu.trainer.step"]["start_ns"] <= dot["start_ns"]

    # the JSONL sink keeps its schema: bare names, no annotation fields
    sink = spans.setup_telemetry(str(tmp_path / "telemetry"))
    body()
    spans.close_telemetry()
    with open(sink) as f:
        records = [json.loads(line) for line in f]
    written = [r for r in records if r["kind"] == "span"]
    assert [r["name"] for r in written] == ["metrics_fetch", "ckpt_save"]
    assert written[0]["parent"] == "ckpt_save" and written[1]["ckpt"] == "c"
    for record in records:
        schema.validate_record(record)


def test_setup_counters_are_there_with_telemetry_off(registry):
    """``lower`` and ``create_train_state`` add their seconds to
    ``setup.*_s`` with no sink and no capture open: what the benchmark's
    ``entry.lower_s`` / ``entry.init_state_s`` read."""
    assert not spans.enabled()
    cfg.MODEL.ARCH = "resnet18"
    cfg.MODEL.NUM_CLASSES = 4
    mesh = mesh_lib.build_mesh()
    topology = topo_lib.from_cfg(cfg)
    model = trainer.build_model_from_cfg(topology)
    lowered = lowering.lower(model, construct_optimizer(), topk=2, mesh=mesh,
                             topology=topology, im_size=16)
    trainer.create_train_state(model, jax.random.key(0), mesh, 16,
                               layout=lowered.layout)
    counters = registry.snapshot()["counters"]
    assert {n for n in counters if n.startswith("setup.")} == {
        "setup.lower_s", "setup.init_state_s"}
    assert counters["setup.lower_s"] > 0 and counters["setup.init_state_s"] > 0
    first = counters["setup.lower_s"]
    lowering.lower(model, construct_optimizer(), topk=2, mesh=mesh,
                   topology=topology, im_size=16)
    assert registry.snapshot()["counters"]["setup.lower_s"] > first  # adds up


def test_compile_listener_counts_without_a_sink(registry):
    """``jit.compiles`` / ``jit.cache_hits`` / ``jit.compile_s`` count with
    the sink closed (a server's ``stats`` op read 0 before); a cache hit is
    still not a compile."""
    assert not spans.enabled()
    runtime._on_event("/jax/compilation_cache/cache_misses")
    runtime._on_event_duration("/jax/core/compile/backend_compile_duration", 1.5)
    runtime._on_event("/jax/compilation_cache/cache_hits")
    runtime._on_event_duration("/jax/core/compile/backend_compile_duration", 0.25)
    counters = registry.snapshot()["counters"]
    assert counters["jit.compiles"] == 1 and counters["jit.compile_s"] == 1.5
    assert counters["jit.cache_hits"] == 1 and counters["jit.cache_misses"] == 1
    assert counters["jit.cache_hit_s"] == 0.25


def test_span_name_table_and_its_static_check(tmp_path):
    """Every span name the package uses is in ``schema.SPANS``, the
    annotation names derive from it, and a name that is not is refused by
    the static check and by ``annotate`` itself."""
    import check_telemetry_schema as checker

    assert schema.ANNOTATIONS["wait"] == "dtpu.trainer.wait"
    assert schema.ANNOTATIONS["ckpt_commit"] == "dtpu.ckpt.ckpt_commit"
    with pytest.raises(KeyError):
        spans.annotate("bogus_span")
    assert set(schema.ANNOTATIONS) == set(schema.SPANS)
    assert all(name.startswith(schema.ANNOTATION_PREFIX)
               for name in schema.ANNOTATIONS.values())
    violations, _ = checker.check_tree(os.path.join(REPO, "distribuuuu_tpu"))
    assert violations == []
    bad = tmp_path / "mod.py"
    bad.write_text(
        "from distribuuuu_tpu.telemetry import spans\n"
        "with spans.span('ckpt_save'):\n    pass\n"
        "with spans.annotate('bogus_span'):\n    pass\n"
        "spans.emit_span('step', 0.0, 1.0)\n"
    )
    violations, _ = checker.check_file(str(bad), "mod.py")
    assert len(violations) == 1 and "bogus_span" in violations[0]


class _Pool:
    """32 tiny uint8 images: four batches of eight (the 8-device mesh)."""

    def __len__(self):
        return 32

    def __getitem__(self, i):
        import numpy as np

        return np.full((8, 8, 3), i, np.uint8), i % 4


def test_a_capture_of_the_loop_holds_the_epoch_and_the_loaders_workers(
        tmp_path, registry):
    """``train_epoch`` over the program's ``Loader`` under a CPU capture with
    the Python tracer off (as ``_ProfilerWindow`` and the benchmark's
    ``loop_capture`` start it): ``dtpu.trainer.epoch`` encloses every
    ``dtpu.trainer.step``, ``wait``, ``h2d`` and ``metrics_fetch`` on the
    loop's thread, and ``dtpu.loader.decode`` / ``assemble`` lie on the
    worker threads' lines, one pair a batch."""
    import types

    from distribuuuu_tpu.data.loader import Loader
    from distribuuuu_tpu.utils.logger import get_logger

    cfg.TRAIN.PRINT_FREQ = 2
    loader = Loader(_Pool(), batch_size=8, shuffle=True, drop_last=True,
                    workers=2, seed=3)
    state = trainer.TrainState(
        params={}, batch_stats={}, step=0, key=None,
        opt_state=types.SimpleNamespace(hyperparams={}),
    )
    double = jax.jit(lambda x: (x.astype(jnp.float32) * 2).sum())
    double(jnp.zeros((8, 8, 8, 3), jnp.uint8)).block_until_ready()

    def step(state, batch):
        loss = double(batch["image"])
        return state.replace(step=state.step + 1), {
            "loss": loss, "top1": loss, "topk": loss}

    trace_dir = str(tmp_path / "profile")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        trainer.train_epoch(
            loader=loader, mesh=mesh_lib.build_mesh(), state=state,
            train_step=step, epoch=0, logger=get_logger(),
        )
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    # one thread name a host line: the lines are all named after the process
    found = program_spans.ProgramSpans(loop_capture.load_spans(path))
    assert found.names() == [
        "dtpu.loader.assemble", "dtpu.loader.decode", "dtpu.trainer.epoch",
        "dtpu.trainer.h2d", "dtpu.trainer.metrics_fetch", "dtpu.trainer.step",
        "dtpu.trainer.wait"]
    (epoch,) = [s for s in found.spans if s["name"] == "dtpu.trainer.epoch"]
    counts = {n: t["count"] for n, t in found.totals().items()}
    assert counts["dtpu.trainer.step"] == counts["dtpu.trainer.h2d"] == 4
    assert counts["dtpu.loader.decode"] == counts["dtpu.loader.assemble"] == 4
    assert counts["dtpu.trainer.metrics_fetch"] == 2
    for s in found.spans:
        if s["name"].startswith("dtpu.trainer.") and s is not epoch:
            assert s["thread"] == epoch["thread"]
            assert epoch["start_ns"] <= s["start_ns"]
            assert (s["start_ns"] + s["dur_ns"]
                    <= epoch["start_ns"] + epoch["dur_ns"])
        if s["name"].startswith("dtpu.loader."):
            assert s["thread"] != epoch["thread"]
    # the epoch's self time is what no child holds: positive, under its total
    total = found.totals()["dtpu.trainer.epoch"]
    assert 0 < total["self_s"] < total["total_s"]
    assert registry.snapshot()["counters"]["trainer.steps"] == 4
