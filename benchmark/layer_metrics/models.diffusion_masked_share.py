"""The share of a step's positions that its noise masked: the step metric
``diffusion_masked_share`` (``models/sdar_moe.py``: the mean of ``[u < t]``)
averaged over the window's steps as the driver read it (the traced steps' in
a traced run). About 0.50 under the clipped uniform schedule; the share of
the head's rows that carry loss, and of the noised rows that are the one
``[MASK]`` embedding, which makes routing uneven. Nothing where the program
reports no such metric."""

METRIC = {"layer": "models", "unit": "ratio", "source": "program_counter",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.counters.get("diffusion_masked_share")
