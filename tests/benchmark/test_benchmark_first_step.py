"""``lm_train_step.first_step_errors`` as all six decoder cells' drivers read
it (PR 52): ``update`` held in units the parameter can represent, a leaf too
small to carry a relative error read with its module. Each rule beside the
faults it must still catch, at SDAR's cell's own rate, AdamW constants and
limits (``benchmark/configs/sdar_30b_a3b.json``: the cell whose sound runs
the old rule called incorrect) and at Ouro's gradient limit, on a parameter
tree of a block's kinds of leaves at a size a test can hold."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax.struct import dataclass as struct

from benchmark.harness.discovery import Catalog

CATALOG = Catalog()
DRIVER = CATALOG.driver("lm_train_step")
SDAR = CATALOG.config("sdar_30b_a3b")["train_job"]
OURO = CATALOG.config("ouro_2_6b")["train_job"]
ADAMW, LR, LIMITS = SDAR["adamw"], SDAR["lr"], SDAR["reference_tolerance"]


@struct
class State:
    params: dict
    opt_state: tuple


def tree(key):
    """A block's kinds of leaves: norm scales at 1 (128 and 256 wide, the
    head norms' and the block norms' kinds), matrices at 0.02, a gate of one
    column with a bias of ONE float; and a gradient for each."""
    k = jax.random.split(key, 8)
    params = {
        "Block_0": {
            "attn": {"q_norm": {"scale": jnp.ones((128,))},
                     "q_proj": {"kernel": 0.02 * jax.random.normal(k[0], (256, 128))}},
            "norm": {"scale": jnp.ones((256,))},
        },
        "exit_gate": {"kernel": 0.02 * jax.random.normal(k[1], (256, 1)),
                      "bias": jnp.zeros((1,))},
    }
    grads = {
        "Block_0": {
            "attn": {"q_norm": {"scale": 1e-4 * jax.random.normal(k[2], (128,))},
                     "q_proj": {"kernel": 1e-5 * jax.random.normal(k[3], (256, 128))}},
            "norm": {"scale": 1e-4 * jax.random.normal(k[4], (256,))},
        },
        "exit_gate": {"kernel": 1e-3 * jax.random.normal(k[5], (256, 1)),
                      "bias": 1e-3 * jax.random.normal(k[6], (1,))},
    }
    return params, grads


def stepped(params, grads, fault=None):
    """The state a first AdamW step leaves, sound or with one fault."""
    b1, b2, eps, wd = (ADAMW[k] for k in ("b1", "b2", "eps", "weight_decay"))
    if fault == "moments_in_bfloat16":
        def low(x):
            return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

        mu = jax.tree.map(lambda g: low((1 - b1) * g), grads)
        nu = jax.tree.map(lambda g: low((1 - b2) * jnp.square(g)), grads)
        new = jax.tree.map(
            lambda p, m, v: p - LR * (m / (1 - b1) / (jnp.sqrt(v / (1 - b2)) + eps) + wd * p),
            params, mu, nu)
        return State(new, (optax.ScaleByAdamState(jnp.ones([], jnp.int32), mu, nu),))
    tx = optax.adamw(
        2 * LR if fault == "rate_doubled" else LR, b1=b1, b2=b2, eps=eps,
        weight_decay=0.0 if fault == "no_weight_decay" else wd)
    updates, opt_state = tx.update(grads, tx.init(params), params)
    return State(optax.apply_updates(params, updates), opt_state)


def worst(errors, kind):
    return max(e[kind] for e in errors.values())


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_sound_step_reads_under_every_limit(seed):
    params, grads = tree(jax.random.key(seed))
    errors = DRIVER.first_step_errors(ADAMW, LR, params, grads, stepped(params, grads))
    assert len(errors) == 5
    assert worst(errors, "update") <= LIMITS["update"] / 3
    assert worst(errors, "second_moment") <= LIMITS["second_moment"]
    assert worst(errors, "gradient") <= 1e-6


@pytest.mark.parametrize("fault,over", [
    ("rate_doubled", {"update"}),
    ("no_weight_decay", {"update"}),
    ("moments_in_bfloat16", {"update", "second_moment"}),
])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_a_wrong_step_still_reads_over_its_limit(fault, over, seed):
    """What the old rule caught, the new one catches: none of these moves a
    parameter by a neighbouring value alone."""
    params, grads = tree(jax.random.key(seed))
    errors = DRIVER.first_step_errors(
        ADAMW, LR, params, grads, stepped(params, grads, fault))
    read = {kind for kind in ("update", "second_moment")
            if worst(errors, kind) > LIMITS[kind]}
    assert read == over, errors
    assert worst(errors, "update") > 3 * LIMITS["update"]  # not by a hair
    if fault == "rate_doubled":  # every leaf, by the whole step
        assert min(e["update"] for e in errors.values()) == pytest.approx(1.0, abs=0.01)


def test_neighbouring_values_are_equal_and_the_next_are_not():
    """``beyond_one_spacing`` itself, across a power of two (the spacing
    under 1 is half the spacing over it) and at both signs."""
    one = np.float32(1.0)
    below, above = np.nextafter(one, np.float32(0)), np.nextafter(one, np.float32(2))
    below2, above2 = np.nextafter(below, np.float32(0)), np.nextafter(above, np.float32(2))
    want = jnp.asarray([one, one, one, one, one, -one, -one, 0.02, 0.02], jnp.float32)
    near = np.nextafter(np.float32(0.02), np.float32(1))
    far = np.nextafter(near, np.float32(1))
    got = jnp.asarray([one, below, above, below2, above2, -below, -above2, near, far],
                      jnp.float32)
    out = np.asarray(DRIVER.beyond_one_spacing(got, want))
    assert list(out[:3]) == [0, 0, 0] and out[5] == 0 and out[7] == 0
    assert out[3] == below2 - one and out[4] == above2 - one
    assert out[6] == -(above2 - one) and out[8] == far - np.float32(0.02)
    # not a number is not a neighbour
    assert np.isnan(np.asarray(DRIVER.beyond_one_spacing(
        jnp.asarray([jnp.nan]), jnp.asarray([1.0]))))[0]


def test_one_spacing_off_reads_nought_and_two_read_as_before():
    """SDAR's own event, planted: a 128-wide scale at 1, a step of 2**-15
    (the cell's 3e-5 on the float32 grid, so that the plain step's result is
    the same float in any order of the arithmetic), ONE element a
    neighbouring float off. The old rule read it over the cell's limit; it
    reads 0, and an element two spacings off reads what it read."""
    lr, adamw = 2.0 ** -15, dict(ADAMW, weight_decay=0.0)
    p0 = {"q_norm": {"scale": jnp.ones((128,))}}
    grads = {"q_norm": {"scale": jnp.full((128,), 1e-3)}}
    mu = jax.tree.map(lambda g: (1 - adamw["b1"]) * g, grads)
    nu = jax.tree.map(lambda g: (1 - adamw["b2"]) * jnp.square(g), grads)
    want = np.full((128,), 1 - lr, np.float32)
    spacing = float(want[0] - np.nextafter(want[0], np.float32(0)))
    assert spacing == 2.0 ** -24
    step_norm = np.sqrt(128) * lr

    def read(p1):
        state = State({"q_norm": {"scale": jnp.asarray(p1)}},
                      (optax.ScaleByAdamState(jnp.ones([], jnp.int32), mu, nu),))
        return DRIVER.first_step_errors(adamw, lr, p0, grads, state)[
            "['q_norm']['scale']"]["update"]

    assert read(want) == 0
    one_off = want.copy()
    one_off[5] = np.nextafter(want[5], np.float32(0))
    one_off[9] = np.nextafter(want[9], np.float32(2))
    assert read(one_off) == 0
    old = spacing / step_norm  # ONE element, as the old rule summed it
    assert 1.7e-4 < old < 1.76e-4 and old > LIMITS["update"]  # PERF.md section 7's reading
    two_off = one_off.copy()
    two_off[7] = np.nextafter(np.nextafter(want[7], np.float32(0)), np.float32(0))
    assert read(two_off) == pytest.approx(2 * spacing / step_norm, rel=1e-5)
    # and the rate doubled is 128 elements a whole step off
    assert read(np.full((128,), 1 - 2 * lr, np.float32)) == pytest.approx(1.0, rel=1e-5)


def planted_gate(bias_error, kernel_scale=1.0):
    """The gradient errors of a gate whose bias's reference gradient nearly
    cancels: 1e-6 of its kernel's norm, the step's ``bias_error`` of that
    norm away from it."""
    params, grads = tree(jax.random.key(3))
    sibling = float(jnp.linalg.norm(grads["exit_gate"]["kernel"]))
    grads["exit_gate"]["bias"] = jnp.full((1,), 1e-6 * sibling)
    applied = jax.tree.map(lambda g: g, grads)
    applied["exit_gate"] = {
        "kernel": kernel_scale * grads["exit_gate"]["kernel"],
        "bias": grads["exit_gate"]["bias"] + bias_error * sibling,
    }
    return DRIVER.first_step_errors(ADAMW, LR, params, grads, stepped(params, applied))


@pytest.mark.parametrize("bias_error", [1e-7, 5e-7, -2e-6])
def test_a_scalar_leaf_that_nearly_cancels_is_read_with_its_module(bias_error):
    """Alone the bias reads 0.1, 0.5 and 2 (Ouro's limit: 0.18): one float
    whose terms over 16,384 rows nearly cancel. With its kernel it reads
    what the module reads."""
    limit = OURO["reference_tolerance"]["gradient"]
    errors = planted_gate(bias_error)
    # the planted error over the MODULE's norm (and the kernel's float32 rounding)
    assert errors["['exit_gate']['bias']"]["gradient"] == pytest.approx(
        abs(bias_error), abs=5e-8)
    assert worst(errors, "gradient") < limit
    # a leaf of 8 elements or more is read alone, as before
    assert errors["['exit_gate']['kernel']"]["gradient"] < 1e-6
    assert DRIVER.SMALL_LEAF == 8


def test_a_wrong_gradient_of_the_module_fails_the_scalar_leaf_too():
    limit = OURO["reference_tolerance"]["gradient"]
    errors = planted_gate(1e-7, kernel_scale=1.5)
    assert errors["['exit_gate']['kernel']"]["gradient"] == pytest.approx(0.5, rel=1e-3)
    assert errors["['exit_gate']['bias']"]["gradient"] == pytest.approx(0.5, rel=1e-3)
    assert errors["['exit_gate']['bias']"]["gradient"] > limit
    # the leaves of other modules do not move
    assert errors["['Block_0']['norm']['scale']"]["gradient"] < 1e-6


def test_a_small_leaf_with_no_sibling_is_read_alone():
    params = {"gate": {"bias": jnp.zeros((1,))}, "w": jnp.ones((16,))}
    grads = {"gate": {"bias": jnp.full((1,), 1e-3)}, "w": jnp.full((16,), 1e-3)}
    applied = {"gate": {"bias": jnp.full((1,), 1.5e-3)}, "w": grads["w"]}
    errors = DRIVER.first_step_errors(ADAMW, LR, params, grads, stepped(params, applied))
    assert errors["['gate']['bias']"]["gradient"] == pytest.approx(0.5, rel=1e-4)
    assert errors["['w']"]["gradient"] < 1e-6
