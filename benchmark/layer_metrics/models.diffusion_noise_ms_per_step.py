"""Device time per step under the ``diffusion_noise`` named scope
(``models/sdar_moe.py``): the draws of a step's noise from its key (one level
a block a sequence, one uniform a position: threefry's rounds), the noised
copy of the tokens and the concatenation with the clean copy. Forward only:
nothing of it takes a gradient. Nothing for a program without the scope."""

METRIC = {"layer": "models", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.per_step_ms(lambda trace: trace.scope_s("diffusion_noise")) or None
