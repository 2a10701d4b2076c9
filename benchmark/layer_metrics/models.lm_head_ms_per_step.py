"""Device time per step under the ``lm_head`` named scope
(``lowering.loss_fn``, ``ops/token_head.py``): the vocabulary head, the
chunked loss and the top-k hits, forward, the backward's recomputation of
each chunk's logits, and backward. Nothing for a program without the scope."""

METRIC = {"layer": "models", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.per_step_ms(lambda trace: trace.scope_s("lm_head")) or None
