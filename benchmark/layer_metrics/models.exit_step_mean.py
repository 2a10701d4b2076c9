"""The pass after which a token leaves, in expectation: the step metric
``exit_step_mean`` (``sum_t t p_t`` over the exit distribution the model's
gate emits, a token mean; ``models/ouro.py``) averaged over the window's
steps as the driver read it. Between 1 and ``total_ut_steps``; 1.875 for
four passes at initialisation, and a gate that died reads 1 or 4. Nothing
where the program reports no such metric."""

METRIC = {"layer": "models", "unit": "ratio", "source": "program_counter",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.counters.get("exit_step_mean")
