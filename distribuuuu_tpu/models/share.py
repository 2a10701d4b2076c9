"""What the decoders that are ONE chip's share of an expert-parallel group
declare the same way (``models/glm_moe.py``, ``models/lfm2_moe.py``,
``models/afmoe.py``), in one place:

* :class:`ShareOfALayer`: which experts and rows of the vocabulary the chip
  holds (``share_chips`` chips share each layer and this is ``share_rank``
  of them: ``models/glm_moe.py``'s module docstring, "the chip's share"), the
  loss of one head's cross-entropy and the mixtures' balancing term
  (:func:`mixture_metrics`), the partition layer's hooks, and
  :func:`share_kwargs_from_cfg`.
* :class:`PatternStack`: a share whose layers are a stage (``first_layer``,
  ``depth``) of a published ``layer_types``; :class:`Block`, one block of it
  around a mixer the model hands over, with a norm before each of its two
  parts and, where the model names them, one after each;
  :func:`run_blocks`, the stage under the recomputation policy
  (``models/ouro.recomputed``: a block keeps its float32 input, what the
  flash backward kernel reads and each branch's output that the backward
  reads again) and its ``share.plan`` record (:func:`say_plan`); and
  :func:`pattern_kwargs_from_cfg`.

The mixture itself is ``models/glm_moe.Mixture`` and the mixers are the
models' own: this module builds neither.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distribuuuu_tpu.models.layers import head_dtype
from distribuuuu_tpu.models.olmoe import RMSNorm, _normal, decoder_kwargs_from_cfg
from distribuuuu_tpu.models.ouro import MLP, branch_out, kept_plan, recomputed
from distribuuuu_tpu.ops import token_head


def mixture_metrics(stats) -> dict:
    """The step metrics every decoder with :class:`Mixture` layers reports,
    from their stacked statistics."""
    metrics = {
        "moe_aux": stats["aux"].mean(),
        "moe_dropped": jnp.float32(0.0),  # no capacity: nothing can drop
        "moe_load_max_over_mean": stats["load_max_over_mean"].max(),
        "moe_held_row_share": stats["held_row_share"].mean(),
    }
    if "bias_abs_max" in stats:  # a router that balances through a bias
        metrics["router_bias_abs_max"] = stats["bias_abs_max"].max()
    return metrics


class ShareOfALayer(nn.Module):
    """What a decoder that is ONE chip's share of an expert-parallel group
    (``models/glm_moe.py``, "the chip's share") declares the same way whatever
    its blocks: which experts and rows of the vocabulary it holds, the head's
    column of a token id, a loss of ONE head's cross-entropy and the
    mixtures' balancing term, and the partition layer's hooks. The fields
    (``share_chips``, ``share_rank``, ``num_experts``, ``vocab_size``,
    ``seq_len``, ``head_chunk``, ``aux_weight``) are the subclass's."""

    @property
    def held(self) -> tuple:
        """(first, count) of the routed experts this chip holds."""
        count = self.num_experts // self.share_chips
        return self.share_rank * count, count

    @property
    def vocab_held(self) -> int:
        return self.vocab_size // self.share_chips

    def _check_share(self) -> None:
        n, r = self.share_chips, self.share_rank
        if n < 1 or self.num_experts % n or self.vocab_size % n or not 0 <= r < n:
            raise ValueError(
                f"LM.SHARE_CHIPS={n}, LM.SHARE_RANK={r}: the chips that share "
                f"a layer must divide its {self.num_experts} routed experts "
                f"and the {self.vocab_size} vocabulary rows, and the rank "
                "lie under them"
            )

    def _check_input(self, tokens) -> None:
        if tokens.shape[1] > self.seq_len:
            raise ValueError(
                f"input length {tokens.shape[1]} exceeds the context "
                f"LM.SEQ_LEN={self.seq_len}"
            )
        self._check_share()

    def _embedding(self):
        """The held rows of the token embedding (inside the compact
        ``__call__``, which shifts the ids by the rank's first row)."""
        return nn.Embed(
            self.vocab_held, self.dim, name="tok_embed",
            dtype=head_dtype(self.dtype), param_dtype=jnp.float32,
            embedding_init=_normal(),
        )

    def head_labels(self, labels):
        """The head's column for a token id: the head holds the rank's rows
        of the vocabulary."""
        return labels - self.share_rank * self.vocab_held

    def head_loss(self, outputs, kernel, labels, *, topk):
        """``(loss, hits, step metrics)``: next-token cross-entropy and the
        mixtures' balancing term."""
        states, stats = outputs
        with jax.named_scope("lm_head"):
            ce, hits = token_head.loss_and_accuracy(
                states, kernel, self.head_labels(labels), topk=topk,
                chunk=self.head_chunk,
            )
        extra = {"ce": ce, **mixture_metrics(stats)}
        return ce + self.aux_weight * extra["moe_aux"], hits, extra

    def dummy_input(self):
        return jnp.full(
            (2, min(8, self.seq_len)), self.share_rank * self.vocab_held, jnp.int32)

    def param_spec_table(self):
        from distribuuuu_tpu.parallel.partition import specs

        return specs.lm_spec_table()

    def batch_spec_table(self):
        from distribuuuu_tpu.parallel.partition import specs

        return specs.TOKEN_BATCH_TABLE


def share_kwargs_from_cfg(cfg, topology) -> dict:
    """``models/olmoe.py``'s (context, depth, attention entry, mesh), the
    share and the balancing term's weight; every width is the arch's own."""
    kwargs = {**decoder_kwargs_from_cfg(cfg, topology),
              "aux_weight": float(cfg.MODEL.MOE.AUX_WEIGHT)}
    if int(cfg.LM.SHARE_CHIPS) > 0:  # 0 keeps the arch's own
        kwargs.update(share_chips=int(cfg.LM.SHARE_CHIPS),
                      share_rank=int(cfg.LM.SHARE_RANK))
    return kwargs


class Block(nn.Module):
    """One block: ``mixer`` builds its token mixer under the params' name and
    device scope ``mixer_scope``, ``mixture`` its ``models/glm_moe.Mixture`` or is
    None for the dense MLP. ``norms`` names the norm BEFORE each of the two
    parts; ``post_norms`` the one AFTER each, where the model has them (``h =
    x + N2(mixer(N1(x)))``, ``y = h + N4(F(N3(h)))``: ``models/afmoe.py``),
    None where it has none. Each part's output is named
    (``models/ouro.branch_out``): recomputed, the block keeps the mixer's
    (the norm after it reads it, or the sum the FFN's norm reads is made of
    it) and the FFN's where a norm follows it."""

    mixer: Any  # () -> the model's mixer for this layer
    mixer_scope: str  # the mixer's params' name and device scope
    mixture: Any  # () -> Mixture, or None
    mlp_hidden: int
    dim: int
    eps: float
    dtype: Any
    norms: tuple  # (before the mixer, before the FFN)
    post_norms: tuple = (None, None)  # (after the mixer, after the FFN)

    @nn.compact
    def __call__(self, x, positions):
        before_mixer, before_ffn = self.norms
        after_mixer, after_ffn = self.post_norms

        def norm(name, t):
            return t if name is None else RMSNorm(self.eps, name=name)(t)

        with jax.named_scope(self.mixer_scope):
            x = x + norm(after_mixer, branch_out(
                self.mixer(name=self.mixer_scope)(norm(before_mixer, x), positions)))
        if self.mixture is None:
            with jax.named_scope("mlp"):
                x = x + norm(after_ffn, branch_out(MLP(
                    self.dim, self.mlp_hidden, self.dtype, name="mlp")(
                        norm(before_ffn, x))))
            return x, {}
        with jax.named_scope("moe"):
            out, stats = self.mixture(name="moe")(norm(before_ffn, x))
            out = norm(after_ffn, branch_out(out))
        return x + out, stats


_planned: set = set()


def say_plan(model, batch: int, seq: int, post_norms: tuple) -> None:
    """One ``share.plan`` record a shape, at trace time, as
    ``models/glm_moe.py``'s, with the layer kinds the model built
    (``post_norms``: :class:`Block`'s)."""
    kinds = model.layer_kinds
    key = (type(model).__name__, model.share_chips, model.share_rank, kinds,
           model.dense_layers, batch, seq, model.recompute)
    if key in _planned:
        return
    _planned.add(key)
    from distribuuuu_tpu.telemetry import spans

    spans.emit_event(
        "share.plan", share_chips=model.share_chips, share_rank=model.share_rank,
        experts_held=model.held[1], experts_total=model.num_experts,
        vocab_held=model.vocab_held, vocab_total=model.vocab_size,
        layer_kinds=list(kinds), dense_layers=model.dense_here,
        **kept_plan(
            model, len(kinds), batch, seq, model.attn_head_dim,
            "every block of either kind",
            # the mixer's output, and the FFN's where a norm reads it
            branches=len(kinds) * (1 + (post_norms[1] is not None)),
            flash_blocks=sum(model.KINDS[kind] == "attn" for kind in kinds)),
    )


def run_blocks(model, x, positions, mixers: dict, mixture, norms,
               post_norms=(None, None)):
    """``x`` through the blocks of ``model``'s stage (a :class:`PatternStack`,
    inside its compact ``__call__``; ``mixers``: ``layer_types``' word -> the
    mixer's factory; ``norms`` and ``post_norms`` are :class:`Block`'s), each
    recomputed in the backward (``models/ouro.recomputed``) where
    ``model.recompute``, and said once a shape (:func:`say_plan`): ``(x, the
    mixtures' statistics, one dict a mixture)``."""
    say_plan(model, *x.shape[:2], post_norms)
    block = recomputed(Block) if model.recompute else Block
    stats = []
    for i, kind in enumerate(model.layer_kinds):
        x, s = block(
            mixers[kind], model.KINDS[kind],
            None if i < model.dense_here else mixture,
            model.mlp_hidden, model.dim, model.norm_eps, model.dtype, norms,
            post_norms, name=f"Block_{i}",
        )(x, positions)
        if s:
            stats.append(s)
    return x, stats


def stacked(stats: list) -> dict:
    """The mixtures' statistics ``[mixtures]`` a name, as ``head_loss``
    reads them."""
    return {k: jnp.stack([s[k] for s in stats]) for k in stats[0]}


class PatternStack(ShareOfALayer):
    """A share of a decoder whose layers are a stage of a published
    ``layer_types`` (fields ``layer_types``, ``first_layer``, ``depth``,
    ``dense_layers``, ``recompute`` and the block's ``mlp_hidden``, ``dim``,
    ``norm_eps``, ``dtype`` are the subclass's; ``KINDS`` the words its list
    may hold, each with its mixer's params' name and device scope)."""

    KINDS = {}

    @property
    def layer_kinds(self) -> tuple:
        """``layer_types`` of the layers this model builds, in order."""
        last = self.first_layer + self.depth if self.depth else len(self.layer_types)
        kinds = tuple(self.layer_types[self.first_layer:last])
        if len(kinds) != last - self.first_layer or not set(kinds) <= set(self.KINDS):
            raise ValueError(
                f"layers {self.first_layer}..{last - 1} of {len(self.layer_types)} "
                f"layer_types {sorted(set(self.layer_types))}: the stage must lie "
                f"inside the list, whose words are {sorted(self.KINDS)}"
            )
        return kinds

    @property
    def dense_here(self) -> int:
        """How many of the built layers carry the dense MLP: the published
        leading ones that fall into this stage."""
        return max(0, min(self.dense_layers - self.first_layer, len(self.layer_kinds)))

    @property
    def attn_head_dim(self) -> int:
        return self.dim // self.num_heads

    def _check_share(self) -> None:
        super()._check_share()
        if self.num_heads % self.kv_heads or self.dim % self.num_heads:
            raise ValueError(
                f"{self.num_heads} query heads on {self.kv_heads} key/value "
                f"heads at width {self.dim}: each must divide the one before"
            )

    @staticmethod
    def eval_hidden(outputs):
        return outputs[0]


def pattern_kwargs_from_cfg(cfg, topology) -> dict:
    """:func:`share_kwargs_from_cfg`'s (context, depth, attention entry, mesh,
    the share, the balancing term's weight), the stage's first layer and whether
    a block is recomputed; every width is the arch's own."""
    return {**share_kwargs_from_cfg(cfg, topology),
            "first_layer": int(cfg.LM.FIRST_LAYER),
            "recompute": bool(cfg.LM.RECOMPUTE)}
