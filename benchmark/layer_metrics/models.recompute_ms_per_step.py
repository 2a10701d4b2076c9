"""Device time per step of the forward the backward runs AGAIN: the
operations of a ``jax.checkpoint`` body as its transpose re-traces it, which
carry ``rematted_computation`` in their ``op_name`` (``models/ouro.py``
recomputes every block application from its kept input). Work the step does
and ``models.mfu`` does not count. Nothing for a program that recomputes
nothing."""

METRIC = {"layer": "models", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}

RECOMPUTED = "rematted_computation"


def read(observed):
    return observed.per_step_ms(lambda trace: trace.scope_s(RECOMPUTED)) or None
