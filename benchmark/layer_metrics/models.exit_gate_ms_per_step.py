"""Device time per step under the ``exit_gate`` named scope
(``models/ouro.py``): the gate's projection of every pass's state, the exit
distribution over the passes, its entropy and the per-token weights the head
is given, forward and backward. Nothing for a program without the scope."""

METRIC = {"layer": "models", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.per_step_ms(lambda trace: trace.scope_s("exit_gate")) or None
