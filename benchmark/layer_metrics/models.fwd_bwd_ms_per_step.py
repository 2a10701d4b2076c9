"""Device time per step of everything that is neither the optimizer update
nor a collective: forward, backward, loss and metrics."""

from benchmark.harness.trace import in_scope, is_collective

METRIC = {"layer": "models", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.per_step_ms(lambda trace: trace.seconds_where(
        lambda e: not in_scope(e["op_name"], "optimizer_update")
        and not is_collective(e)
    ))
