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
