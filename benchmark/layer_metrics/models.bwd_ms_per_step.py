"""Device time per step of the backward pass: the operations under the
step's ``bwd`` named scope (the transposed pass of ``lowering.py``'s
``grad_fn``, the loss's backward included), collectives left out: the
partitioner places the gradient all-reduce on a backward operation, and
``partition.collective_ms_per_step`` has it. Since PR 27 a decoder's head
computes its gradients in the forward walk (``ops/token_head.py``): on those
cells they are under ``models.fwd_ms_per_step``, not here."""

from benchmark.harness.trace import in_scope, is_collective

METRIC = {"layer": "models", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.per_step_ms(lambda trace: trace.seconds_where(
        lambda e: in_scope(e["op_name"], "bwd") and not is_collective(e)
    )) or None
