"""One rehearsed run of the host-fed cell through the real command's ``main``
with the timed path broken underneath (``test_benchmark_trainloop_correct.py``
runs this file, one process a fault, and sees ``correct`` come out false).
``--rehearse`` skips the harness's look for a chip; everything else of a run
is driven: the plain loop, the warm-up epoch through ``train_epoch``, the
window, the reference, the comparison, the result's line.

    python tests/benchmark/trainloop_faults.py <fault> --workload ... --rehearse

Faults, each a wrapper around the program's compiled step (the driver's
``sabotage`` hook): ``state_unchanged`` (the step returns the state it was
given), ``half_batch`` (the second half of the batch's rows left out: the
first half takes their place, so the mean is over it alone), ``rate_doubled``
(a wrong update: twice the epoch's learning rate), ``none``.
"""

import os
import sys
import time

T0 = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def state_unchanged(step):
    import jax

    def broken(state, batch):
        kept = jax.tree.map(
            lambda x: x.copy() if isinstance(x, jax.Array) else x, state)
        _moved, metrics = step(state, batch)
        return kept, metrics

    return broken


def half_batch(step):
    import jax.numpy as jnp

    def broken(state, batch):
        half = batch["label"].shape[0] // 2
        return step(state, {
            k: jnp.concatenate([v[:half], v[:half]]) for k, v in batch.items()})

    return broken


def rate_doubled(step):
    import jax.numpy as jnp

    def broken(state, batch):
        rates = state.opt_state.hyperparams
        if "sound" not in seen:
            seen["sound"] = float(rates["learning_rate"])
        rates["learning_rate"] = jnp.asarray(2 * seen["sound"], jnp.float32)
        return step(state, batch)

    seen = {}
    return broken


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "rate_doubled": rate_doubled, "none": None}


def main() -> int:
    from benchmark.harness import cli
    from benchmark.harness.discovery import Catalog

    fault = FAULTS[sys.argv[1]]
    find = Catalog.driver

    def driver(self, name):
        module = find(self, name)
        if name == "train_loop":
            drive = module.drive
            module.run = lambda run: drive(run, sabotage=fault)
        return module

    Catalog.driver = driver
    return cli.main(sys.argv[2:], T0)


if __name__ == "__main__":
    sys.exit(main())
