"""Device time per step of the forward pass: the operations under the step's
``fwd`` named scope that are not under ``bwd`` (the transposed pass carries
the forward's scope too, as ``bwd/transpose(jvp(fwd))``), collectives left
out. Nothing for a program that has no ``bwd`` scope: there every backward
operation would read as forward. Since PR 27 a decoder's head
(``ops/token_head.py``) works out its logits' cotangent, dX and dW in the
FORWARD walk, under ``lm_head`` and not under ``bwd``: on those cells ``fwd``
holds that backward work too (OLMoE's cell: all 67.5 ms of ``lm_head``)."""

from benchmark.harness.trace import in_scope, is_collective

METRIC = {"layer": "models", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    if not observed.per_step_ms(lambda trace: trace.scope_s("bwd")):
        return None
    return observed.per_step_ms(lambda trace: trace.seconds_where(
        lambda e: in_scope(e["op_name"], "fwd")
        and not in_scope(e["op_name"], "bwd") and not is_collective(e)
    )) or None
