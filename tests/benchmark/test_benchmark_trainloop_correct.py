"""How ``resnet50.trainloop_hostfed`` decides ``correct``: the program's first
steps against the plain float32 reference (``reference/sgd_steps.py``), each
number beside a limit of its own. Here, at the size a test run can hold
(``--rehearse``: float32 resnet18, 32 px, batch 8, on the CPU):

* the CONTROL (the reference with every tensor held in bfloat16, put in the
  program's place) and two faults planted in the reference (half a batch, the
  update without Nesterov) read over a limit on every seed, and the program
  reads within every limit (``benchmark/readings_train_loop.py``, the script
  that took the chip's readings, one process for all seeds);
* the rest of a run through the real command's ``main`` with the timed path
  broken underneath (``trainloop_faults.py``): a state returned unchanged,
  half of the batch left out, a wrong update; ``correct`` comes out false,
  and the result's line and the last lines of standard error say by which
  number;
* the arithmetic of the numbers on small trees.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness.discovery import Catalog
from benchmark_testlib import REPO, finish

CELL = "resnet50.trainloop_hostfed"
NUMBERS = ("statistics_norm_median_leaf", "gradient_norm_median_leaf",
           "change_norm_median_leaf")
# read and said, never compared (no control and no fault reads three times
# their sound readings at the cell's size: PERF.md section 2)
OTHERS = ("loss_step1", "loss_step2", "loss_step3", "statistics_norm_worst_leaf",
          "gradient_norm_worst_leaf", "change_norm_worst_leaf")
EXACT = ("rows_not_from_pool", "state_bits_differ_from_plain_loop",
         "batches_missed_by_the_counts", "nonfinite_losses")
# the third is one of the two seeds in fourteen on which, at this size, the
# program's rounding takes a ReLU of the 1 x 1 last stage the other way (its
# first gradient's worst leaf then reads 0.011 and its median leaf 6e-4
# where the others read 5e-6 and 3e-7; a 1e-6 perturbation of the weights
# brings it back): a sound run, within the limits
SEEDS = (21, 2500000022, 4100000003)
FAULTS = ("state_unchanged", "half_batch", "rate_doubled")


def cpu_env():
    return dict(os.environ, JAX_PLATFORMS="cpu",
                XLA_FLAGS="--xla_force_host_platform_device_count=1")


def limits():
    return Catalog().cell(CELL).traffic["rehearse"]["limits"]


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    """{seed: line} of ``readings_train_loop.py`` at the rehearsal's size."""
    code, out, err = finish(subprocess.Popen(
        [sys.executable, os.path.join(REPO, "benchmark", "readings_train_loop.py"),
         "--workload", CELL, "--rehearse", "--out",
         str(tmp_path_factory.mktemp("readings")),
         "--seeds", *map(str, SEEDS)],
        cwd=REPO, env=cpu_env(), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True), timeout=600)
    assert code == 0, err[-3000:]
    lines = [json.loads(x) for x in out.splitlines() if x.startswith("{")]
    assert [x["seed"] for x in lines] == list(SEEDS)
    return {x["seed"]: x for x in lines}


@pytest.mark.parametrize("number", NUMBERS)
def test_the_program_reads_within_each_limit_on_every_seed(readings, number):
    for seed, line in readings.items():
        assert line["program"][number] <= limits()[number], (seed, number)
        assert set(OTHERS) <= set(line["program"])
        assert line["rows_not_from_pool"] == 0


@pytest.mark.parametrize("side", ["control", "half_batch", "plain_momentum"])
def test_the_control_and_the_planted_faults_read_over_a_limit(readings, side):
    """Each put in the program's place against the float32 reference: it has
    to fail one of the cell's numbers, not each."""
    for seed, line in readings.items():
        over = [n for n in NUMBERS if line[side][n] > limits()[n]]
        assert over, (seed, side, line[side])
        if side == "control":  # the forward statistics follow the precision
            assert "statistics_norm_median_leaf" in over
            assert line[side]["statistics_norm_median_leaf"] > 100 * line[
                "program"]["statistics_norm_median_leaf"]
        if side == "half_batch":
            assert over == list(NUMBERS)
        if side == "plain_momentum":  # the same forward and gradient, another update
            assert line[side]["gradient_norm_median_leaf"] == 0.0
            assert over == ["change_norm_median_leaf"]


@pytest.fixture(scope="module")
def broken_runs():
    """{fault: (code, result line, stderr)}, the three processes side by side."""
    started = {
        fault: subprocess.Popen(
            [sys.executable, os.path.join(REPO, "tests", "benchmark",
                                          "trainloop_faults.py"), fault,
             "--workload", CELL, "--seed", str(SEEDS[2]), "--seconds", "1",
             "--rehearse"],
            cwd=REPO, env=cpu_env(), stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True)
        for fault in FAULTS
    }
    runs = {}
    for fault, process in started.items():
        code, out, err = finish(process, timeout=600)
        assert code == 0, err[-3000:]
        runs[fault] = (json.loads(out.strip().splitlines()[-1]), err)
    return runs


@pytest.mark.parametrize("fault", FAULTS)
def test_a_broken_step_under_the_real_command_is_not_correct(broken_runs, fault):
    line, err = broken_runs[fault]
    assert line["correct"] is False and line["failed"] == 0
    assert list(line)[-1] == "compared"
    compared = line["compared"]
    assert list(compared) == list(NUMBERS + EXACT)
    over = {n for n, c in compared.items() if c["value"] > c["limit"]}
    # the loop itself is sound in all three: the plain loop runs the same
    # broken step, every batch is trained once and counted
    assert not over & set(EXACT)
    want = {
        # no statistics moved, no momentum, no change: all read 1 by the measure
        "state_unchanged": set(NUMBERS),
        "half_batch": set(NUMBERS),
        # the same forward and first gradient, twice the step
        "rate_doubled": {"change_norm_median_leaf"},
    }[fault]
    assert over == want, (fault, compared)
    if fault == "state_unchanged":
        for name in NUMBERS:
            assert compared[name]["value"] == pytest.approx(1, abs=0.05)
    if fault == "rate_doubled":
        assert compared["change_norm_median_leaf"]["value"] == pytest.approx(1, abs=0.1)
    # each number beside its limit: the last lines of standard error
    last = err.strip().splitlines()[-len(compared):]
    assert [x.split()[:2] for x in last] == [["compared", n] for n in compared]
    assert all(x.split()[3] == "limit" for x in last)


# ------------------------------------------------------------- arithmetic
def test_gaps_are_gaps_of_norms_over_the_larger_of_the_leafs_and_the_median_leafs():
    from benchmark.reference import sgd_steps

    ones = {"a": np.ones(4), "b": np.ones(4), "c": np.ones(4)}
    want = {"loss": [2.0, 1.0], "statistics": ones, "change": ones,
            "gradient": {"a": np.full(4, 1.0), "b": np.full(4, 1e-6),
                         "c": np.full(4, 3.0)}}
    got = {"loss": [2.02, 1.0],
           "statistics": {"a": 1.001 * np.ones(4), "b": np.ones(4), "c": 0.98 * np.ones(4)},
           # a: the norm 10 % over; b: all but zero in the reference, read
           # against the MEDIAN leaf's norm (a's, 2); c: turned round, the
           # same norm, no gap
           "gradient": {"a": np.full(4, 1.1), "b": np.full(4, 0.05), "c": np.full(4, -3.0)},
           # a leaf that did not move, or moved double, reads 1
           "change": {"a": np.ones(4), "b": np.zeros(4), "c": 2 * np.ones(4)}}
    numbers = sgd_steps.gaps(got, want)
    assert list(numbers) == list(NUMBERS)
    assert numbers["statistics_norm_median_leaf"] == pytest.approx(0.001, rel=1e-3)
    assert numbers["gradient_norm_median_leaf"] == pytest.approx(0.05, rel=1e-4)  # b's
    assert numbers["change_norm_median_leaf"] == pytest.approx(1.0)
    others = sgd_steps.others(got, want)
    assert set(others) == set(OTHERS) - {"loss_step3"}  # two steps followed here
    assert others["loss_step1"] == (pytest.approx(0.01), "") and others["loss_step2"][0] == 0
    value, where = others["gradient_norm_worst_leaf"]
    assert value == pytest.approx(0.1, rel=1e-5) and where == "['a']"
    assert others["change_norm_worst_leaf"][0] == pytest.approx(1.0)


def test_three_plain_steps_are_torch_ordered_sgd_with_nesterov():
    """On a model whose loss is linear in its one parameter, so that every
    gradient is known: p <- p - lr ((g + wd p) + mu m), m <- mu m + g + wd p."""
    import jax.numpy as jnp

    from benchmark.reference import sgd_steps

    class Linear:
        @staticmethod
        def logits(p, stats, images, **_):
            return images.astype(jnp.float32) @ p["w"]

    sgd = {"lr": 0.1, "momentum": 0.9, "nesterov": True, "weight_decay": 0.5}
    images = np.asarray([[1, 0], [1, 0]], np.uint8)
    batch = {"image": images, "label": np.zeros(2, np.int32)}
    w0 = np.asarray([[1.0, 2.0], [0.0, 0.0]], np.float32)
    step = sgd_steps.follower(Linear, {}, sgd, 2)
    still = lambda params, stats, batch: stats  # no BatchNorm in this model
    out = sgd_steps.follow(step, still, {"w": w0}, {}, [batch] * 3)

    def grad(w):  # d/dw of the mean cross-entropy of logits w[0] for label 0
        z = w[0] - w[0].max()
        soft = np.exp(z) / np.exp(z).sum()
        g = np.zeros_like(w)
        g[0] = soft - np.asarray([1.0, 0.0])
        return g

    w, m = w0.astype(np.float64), 0.0
    for i in range(3):
        d = grad(w) + 0.5 * w
        m = 0.9 * m + d
        if i == 0:
            assert np.allclose(out["gradient"]["w"], grad(w), atol=1e-6)
        w = w - 0.1 * (d + 0.9 * m)
    assert np.allclose(w0 + np.asarray(out["change"]["w"]), w, atol=1e-5)
    plain = sgd_steps.follow(
        sgd_steps.follower(Linear, {}, {**sgd, "nesterov": False}, 2), still,
        {"w": w0}, {}, [batch] * 3)
    assert not np.allclose(plain["change"]["w"], out["change"]["w"], atol=1e-3)


def test_the_control_holds_operands_in_the_lower_type_and_lets_gradients_through():
    import jax
    import jax.numpy as jnp

    from benchmark.reference import common

    x = jnp.asarray([0.3, 1.0, 7.3, -100.0], jnp.float32)
    assert common._held(x) is x  # outside the block: the reference itself
    with common.holding_operands_in(jnp.bfloat16):
        held = common._held(x)
        grad = jax.grad(lambda v: (common._held(v) ** 2).sum())(x)
    assert np.array_equal(held, x.astype(jnp.bfloat16).astype(jnp.float32))
    assert np.allclose(grad, 2 * held)  # straight through, at the held value
    with common.holding_operands_in(jnp.float8_e4m3fn):
        held8 = np.asarray(common._held(x))
    # scaled to the type's range: the largest magnitude is held exactly, the
    # others to three bits of mantissa
    assert held8[3] == -100.0 and abs(held8[2] - 7.3) / 7.3 < 2 ** -3
    assert not np.array_equal(held8, x) and common._HOLD_IN[0] is None


def test_the_statistics_a_step_leaves_are_torchs_running_mean_and_unbiased_variance():
    import jax.numpy as jnp

    from benchmark.reference import common, sgd_steps

    class OneNorm:
        @staticmethod
        def logits(p, stats, images, *, train, bn_group, **_):
            x = images.astype(jnp.float32)
            return common.batch_norm(x, p["bn"], stats["bn"], train=train,
                                     bn_group=bn_group).mean(axis=(1, 2))

    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, (4, 3, 3, 2), dtype=np.uint8)
    params = {"bn": {"scale": np.ones(2, np.float32), "bias": np.zeros(2, np.float32)}}
    stats = {"bn": {"mean": np.asarray([1.0, -1.0], np.float32),
                    "var": np.asarray([2.0, 3.0], np.float32)}}
    after = sgd_steps.statistics_after(OneNorm, {}, 4)(
        params, stats, {"image": images})
    flat = images.reshape(-1, 2).astype(np.float64)
    assert np.allclose(after["bn"]["mean"], 0.9 * stats["bn"]["mean"] + 0.1 * flat.mean(0))
    assert np.allclose(after["bn"]["var"],
                       0.9 * stats["bn"]["var"] + 0.1 * flat.var(0, ddof=1), rtol=1e-5)
    # two ghost groups: the mean of the groups' own statistics
    halves = sgd_steps.statistics_after(OneNorm, {}, 2)(params, stats, {"image": images})
    groups = images.reshape(2, -1, 2).astype(np.float64)
    assert np.allclose(halves["bn"]["var"], 0.9 * stats["bn"]["var"]
                       + 0.1 * groups.var(1, ddof=1).mean(0), rtol=1e-5)
    assert common._MOVED[0] is None


def test_the_reference_draws_its_batches_from_the_pool_and_counts_strangers():
    driver = Catalog().driver("train_loop")
    images, labels = driver.make_pool(5, 16, 32, 10)
    rows = [3, 3, 9, 0]
    batch = {"image": images[rows].copy(), "label": labels[rows].copy()}
    drawn, strangers = driver.drawn_from_pool(images, labels, [batch])
    assert strangers == 0
    assert np.array_equal(drawn[0]["image"], images[rows])
    # pixels altered on the way (below the first row) and a label swapped:
    # the reference takes the POOL's, so the program's loss will differ
    batch["image"][2, 5:] = 0
    batch["label"][0] = (labels[3] + 1) % 10
    drawn, strangers = driver.drawn_from_pool(images, labels, [batch])
    assert strangers == 0 and np.array_equal(drawn[0]["image"], images[rows])
    assert drawn[0]["label"][0] == labels[3]
    # a row that is no image of the pool is counted, a number of its own
    batch["image"][1, 0, 0, 0] ^= 1
    _drawn, strangers = driver.drawn_from_pool(images, labels, [batch])
    assert strangers == 1
