"""Device time per step under ``mla_latent`` (``models/glm_moe.py``), inside
``attn``: what latent attention adds around the kernels and the output
projection: the down- and up-projections through the two latents, the
latents' norms, rotary on the query's rope dims and on the one shared rope
key, the concatenations into heads of score dim 256. Nothing for a program
without the scope."""

METRIC = {"layer": "models", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.per_step_ms(lambda trace: trace.scope_s("mla_latent")) or None
