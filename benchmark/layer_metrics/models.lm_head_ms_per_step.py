"""Device time per step under the ``lm_head`` named scope
(``lowering.loss_fn`` or the model's ``head_loss`` hook, around
``ops/token_head.py`` ``weighted_loss`` / ``loss_and_accuracy``): a chunk
of positions at a time, the head matmul, the log-sum-exp and rank, and
(since PR 27) the logits' cotangent, dX and dW from the SAME logits in the
forward walk, so each chunk's logits are computed once; what is left of the
backward is a scalar multiply. Nothing for a program without the scope."""

METRIC = {"layer": "models", "unit": "ms", "source": "device_trace",
          "moves": "train_items_per_s_per_chip"}


def read(observed):
    return observed.per_step_ms(lambda trace: trace.scope_s("lm_head")) or None
