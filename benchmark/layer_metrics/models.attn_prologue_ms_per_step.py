"""Device time per step under the ``attn_prologue`` named scope
(``models/lfm2_moe.HeadNorm``): a q or k projection's way to the attention
kernels and NOT the projection's matmul: the heads-major layout, the
per-head RMSNorm, the rotary, the cast; forward, whatever of it the backward
runs again where blocks are recomputed, and backward. It reads the XLA
fusions or the ``dtpu_head_prologue_*`` kernels, whichever runs under the
scope (where XLA runs the chain it may fuse parts of it into neighbours
outside the scope, which this sum then lacks). Nothing for a program without
the scope."""

METRIC = {"layer": "models", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.per_step_ms(lambda trace: trace.scope_s("attn_prologue")) or None
