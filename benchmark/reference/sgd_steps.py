"""The first steps of training as the configuration states them, plain:
float32 loss and gradient of an image reference, then torch-ordered SGD
(weight decay added to the gradient, momentum buffer, Nesterov look-ahead) in
float32. What a training cell's ``correct`` holds the program's first steps
to; imports nothing of the program.

``follower`` returns one jitted step; ``follow`` drives it over the batches
and returns each step's loss, the FIRST gradient (as the optimizer gets it:
before the weight decay) and the parameters' change after the last step;
``statistics_after`` gives the BatchNorm running statistics as the first step
leaves them. ``gaps`` reduces two such results to the numbers compared, each a
gap between the two sides' NORMS of a leaf (a norm, not the norm of a
difference: the contract's measure), over the reference's norm of that leaf or
of the median leaf, whichever is larger (some gradients are all but zero):

* ``statistics_norm_median_leaf``: the statistics' move. Forward only, a
  mean over every position of a batch: it follows the precision (float16,
  bfloat16 and fp8 tensors read 2e-5, 2e-4 and 4e-3 at 64 px on the CPU),
  which nothing that went through the backward pass does;
* ``gradient_norm_median_leaf``, ``change_norm_median_leaf``.

All three take the MEDIAN over the leaves. A BatchNorm ResNet from a random start scatters its backward
  pass (gradients explode towards the early layers): the worst leaf of the
  first gradient reads 0.2 to 0.6 whatever the arithmetic, float16 operands or
  fp8, and after two updates so does the reference against itself compiled
  another way (PERF.md section 2). The median leaf is steady from seed to seed
  and still reads 40 times higher for half a batch or a wrong update.

The losses and the three worst leaves are returned for the record (``others``),
not compared: no control and no fault reads three times their sound readings.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import common


def follower(reference, architecture: dict, sgd: dict, bn_group: int,
             hold_in=None):
    """``step(params, stats, trace, batch) -> (loss, gradient, params,
    trace)``, jitted. ``hold_in``: the CONTROL's type (None = the reference
    itself, float32 at precision ``highest``)."""
    lr, mu, wd = sgd["lr"], sgd["momentum"], sgd["weight_decay"]

    def step(params, stats, trace, batch):
        def loss_of(p):
            logits = reference.logits(
                p, stats, batch["image"], architecture=architecture,
                train=True, bn_group=bn_group, recompute=True,
            )
            return common.cross_entropy(logits, batch["label"])

        loss, grad = jax.value_and_grad(loss_of)(params)
        decayed = jax.tree.map(lambda g, p: g + wd * p, grad, params)
        trace = jax.tree.map(lambda t, g: mu * t + g, trace, decayed)
        update = (
            jax.tree.map(lambda g, t: g + mu * t, decayed, trace)
            if sgd["nesterov"] else trace
        )
        params = jax.tree.map(lambda p, u: p - lr * u, params, update)
        return loss, grad, params, trace

    def traced(*args):
        if hold_in is None:
            return step(*args)
        with common.holding_operands_in(hold_in):
            return step(*args)

    return jax.jit(traced)


def statistics_after(reference, architecture: dict, bn_group: int, hold_in=None):
    """``f(params, stats, batch) -> stats`` as the first training step leaves
    them (``common.moved_statistics``), jitted; forward only."""

    def moved(params, stats, batch):
        with common.moved_statistics(stats) as rebuilt:
            reference.logits(
                params, stats, batch["image"], architecture=architecture,
                train=True, bn_group=bn_group,
            )
            return rebuilt()

    def traced(*args):
        if hold_in is None:
            return moved(*args)
        with common.holding_operands_in(hold_in):
            return moved(*args)

    return jax.jit(traced)


def follow(step, statistics, params, stats, batches) -> dict:
    """``step`` over ``batches`` from ``params`` and a zero momentum buffer;
    ``statistics`` (of ``statistics_after``) on the first batch."""
    first = params = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params)
    moved = jax.tree.map(
        lambda a, b: a - b, statistics(params, stats, batches[0]), stats
    )
    trace = jax.tree.map(jnp.zeros_like, params)
    losses, gradient = [], None
    for batch in batches:
        loss, grad, params, trace = step(params, stats, trace, batch)
        losses.append(loss)
        gradient = grad if gradient is None else gradient
    change = jax.tree.map(lambda a, b: a - b, params, first)
    return {"loss": [float(x) for x in jax.device_get(losses)],
            "statistics": moved, "gradient": gradient, "change": change}


@jax.jit
def _norms(tree):
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree
    )


def leaf_norms(tree) -> list:
    """The norm of each leaf, in the tree's own order."""
    return [float(x) for x in jax.tree.leaves(jax.device_get(_norms(tree)))]


def leaf_paths(tree) -> list:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [jax.tree_util.keystr(path) for path, _ in flat]


def leaf_gaps(got: list, want: list) -> list:
    """Per leaf, the gap between the two sides' norms over the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    floor = float(np.median(want))
    return [abs(g - w) / max(w, floor, 1e-30) for g, w in zip(got, want)]


def worst_leaf(got, want) -> tuple[float, str]:
    """(the largest gap of norms over the leaves, that leaf's path)."""
    gaps = leaf_gaps(leaf_norms(got), leaf_norms(want))
    i = int(np.argmax(gaps))
    return gaps[i], leaf_paths(want)[i]


def median_leaf(got, want) -> float:
    return float(np.median(leaf_gaps(leaf_norms(got), leaf_norms(want))))


def gaps(got: dict, want: dict) -> dict:
    """name -> value: what ``got`` (the program's first steps, or a control's
    in its place) reads against ``want`` (the reference's)."""
    return {
        "statistics_norm_median_leaf":
            median_leaf(got["statistics"], want["statistics"]),
        "gradient_norm_median_leaf": median_leaf(got["gradient"], want["gradient"]),
        "change_norm_median_leaf": median_leaf(got["change"], want["change"]),
    }


def others(got: dict, want: dict) -> dict:
    """name -> (value, where): read and said, never compared."""
    out = {
        f"loss_step{i + 1}": (abs(g - w) / max(abs(w), 1e-30), "")
        for i, (g, w) in enumerate(zip(got["loss"], want["loss"]))
    }
    for name in ("statistics", "gradient", "change"):
        out[f"{name}_norm_worst_leaf"] = worst_leaf(got[name], want[name])
    return out
