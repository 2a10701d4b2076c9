"""The share of the router's (token, slot) choices that fell on the experts
this chip holds: the step metric ``moe_held_row_share``
(``models/glm_moe.py``; a mean over the mixtures) averaged over the window's
steps as the driver read it. ``experts_held / n_routed_experts`` under a
uniform router (0.125 for 8 of 64); the rows the held experts compute, and
so the time under ``moe_experts``, follow it. Nothing where the program
reports no such metric."""

METRIC = {"layer": "models", "unit": "ratio", "source": "program_counter",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.counters.get("moe_held_row_share")
