"""Device time per step of latent attention (``models/glm_moe.py``): the
operations under the block's ``attn`` named scope (the norm, the five
low-rank projections, the two latent norms, rotary, the flash kernels, the
output projection), forward, the forward the backward runs again, and
backward, in every block, the MTP module's too. Nothing for a program
without the scope."""

METRIC = {"layer": "models", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.per_step_ms(lambda trace: trace.scope_s("attn")) or None
