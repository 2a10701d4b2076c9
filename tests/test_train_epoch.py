"""The one dispatch loop's accounting (trainer.train_epoch: ``pending``,
``flush_pending``, ``maybe_print``), driven with a stub step so the metrics
of every step are known: each step's loss reaches the meters exactly once,
a step flagged non-finite under ``skip`` stays out of them, windows flush
at PRINT_FREQ and the last one at ``done == num_batches``."""

import types

import numpy as np
import pytest

import distribuuuu_tpu.config as config
from distribuuuu_tpu import trainer
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu.parallel import mesh as mesh_lib
from distribuuuu_tpu.utils.logger import get_logger

N_BATCHES = 5
SKIPPED = 2  # this step reports nonfinite; loss of step i is i + 1


class _Batches:
    def __len__(self):
        return N_BATCHES

    def set_epoch(self, epoch):
        pass

    def __iter__(self):
        for _ in range(N_BATCHES):
            yield {
                "image": np.zeros((8, 4, 4, 3), np.float32),
                "label": np.zeros((8,), np.int32),
            }


def _stub_step(state, batch):
    i = state.step
    metrics = {
        "loss": np.float32(i + 1), "top1": np.float32(10 * i),
        "topk": np.float32(20 * i),
        "nonfinite": np.float32(i == SKIPPED),
    }
    return state.replace(step=i + 1), metrics


@pytest.mark.parametrize("print_freq", [1, 2, 7])
def test_every_steps_metrics_reach_the_meters_once(print_freq, monkeypatch):
    config.reset_cfg()
    cfg.TRAIN.PRINT_FREQ = print_freq
    cfg.TRAIN.NONFINITE = "skip"
    mesh = mesh_lib.build_mesh()
    state = trainer.TrainState(
        params={}, batch_stats={}, step=0, key=None,
        opt_state=types.SimpleNamespace(hyperparams={}),
    )
    meters, printed = [], []
    real_meters = trainer.construct_meters

    def spy_meters(*args, **kwargs):
        meters[:] = real_meters(*args, **kwargs)
        return tuple(meters)

    monkeypatch.setattr(trainer, "construct_meters", spy_meters)
    monkeypatch.setattr(
        trainer, "metrics_log", lambda kind, **f: printed.append(f)
    )

    state, interrupted, done = trainer.train_epoch(
        loader=_Batches(), mesh=mesh, state=state, train_step=_stub_step,
        epoch=0, logger=get_logger(),
    )

    assert (state.step, interrupted, done) == (N_BATCHES, False, N_BATCHES)
    _, _, losses, top1, topk_m, _ = meters
    kept = [i for i in range(N_BATCHES) if i != SKIPPED]
    for meter, value in ((losses, lambda i: i + 1), (top1, lambda i: 10 * i),
                         (topk_m, lambda i: 20 * i)):
        assert (meter.count, meter.sum) == (
            len(kept), sum(value(i) for i in kept)
        )
    # a window is flushed when it prints, the last one at the epoch's end
    at = [b for b in range(1, N_BATCHES + 1)
          if b % print_freq == 0 or b == N_BATCHES]
    assert [r["batch"] for r in printed] == at
    for rec in printed:
        seen = [i + 1 for i in kept if i < rec["batch"]]
        assert rec["loss"] == pytest.approx(sum(seen) / len(seen))


def _bare_state():
    return trainer.TrainState(
        params={}, batch_stats={}, step=0, key=None,
        opt_state=types.SimpleNamespace(hyperparams={}),
    )


def test_the_loops_counters_count_with_every_sink_closed():
    """``trainer.*`` registry counters after one CPU epoch with no JSONL
    sink, no ``metrics.jsonl`` and no capture open: what the benchmark's
    ``trainer.*`` readers find in a process that opened none."""
    from distribuuuu_tpu import telemetry
    from distribuuuu_tpu.telemetry import spans

    config.reset_cfg()
    cfg.TRAIN.PRINT_FREQ = 2
    cfg.TRAIN.NONFINITE = "skip"  # the stub flags one step
    spans.close_telemetry()
    registry = telemetry.get_registry()
    registry.reset()
    try:
        state, _, done = trainer.train_epoch(
            loader=_Batches(), mesh=mesh_lib.build_mesh(), state=_bare_state(),
            train_step=_stub_step, epoch=0, logger=get_logger(),
        )
        counters = registry.snapshot()["counters"]
    finally:
        registry.reset()
    assert done == N_BATCHES
    assert {n for n in counters if n.startswith("trainer.")} == {
        "trainer.steps", "trainer.epochs", "trainer.wait_s", "trainer.h2d_s",
        "trainer.h2d_bytes", "trainer.fetch_s"}
    batch_bytes = 8 * 4 * 4 * 3 * 4 + 8 * 4  # one _Batches batch, as handed over
    assert counters["trainer.steps"] == N_BATCHES
    assert counters["trainer.epochs"] == 1
    assert counters["trainer.h2d_bytes"] == N_BATCHES * batch_bytes
    assert counters["trainer.wait_s"] > 0 and counters["trainer.h2d_s"] > 0
    assert counters["trainer.fetch_s"] > 0


def test_epoch_is_the_jsonl_parent_of_the_loops_four_spans(tmp_path):
    """With the sink open the ``epoch`` span closes last, carries ``epoch``
    and ``phase``, and ``wait``/``h2d``/``step``/``metrics_fetch`` name it
    as their parent."""
    import json

    from distribuuuu_tpu.telemetry import schema, spans

    assert schema.SPANS["epoch"] == "trainer"
    assert schema.ANNOTATIONS["epoch"] == "dtpu.trainer.epoch"
    config.reset_cfg()
    cfg.TRAIN.PRINT_FREQ = 2
    cfg.TRAIN.NONFINITE = "skip"  # the stub flags one step
    sink = spans.setup_telemetry(str(tmp_path / "telemetry"))
    try:
        trainer.train_epoch(
            loader=_Batches(), mesh=mesh_lib.build_mesh(), state=_bare_state(),
            train_step=_stub_step, epoch=0, logger=get_logger(),
        )
    finally:
        spans.close_telemetry()
    with open(sink) as f:
        records = [json.loads(line) for line in f]
    for record in records:
        schema.validate_record(record)
    written = [r for r in records if r["kind"] == "span"]
    assert written[-1]["name"] == "epoch"
    epoch = written[-1]
    # a track of its own: the pipeline track's extent is a window's wall
    # for run_report and live.py
    assert (epoch["epoch"], epoch["phase"], epoch["track"]) == (1, "train", "epoch")
    assert "parent" not in epoch
    children = written[:-1]
    assert {r["name"] for r in children} == {"wait", "h2d", "step", "metrics_fetch"}
    assert [r["name"] for r in children].count("step") == N_BATCHES
    for r in children:
        assert (r["parent"], r["depth"], r["track"]) == ("epoch", 1, "pipeline")
        assert epoch["t0"] <= r["t0"]
        assert r["t0"] + r["dur"] <= epoch["t0"] + epoch["dur"] + 1e-5
