"""Ouro (arXiv:2510.25741, "Scaling Latent Reasoning via Looped Language
Models"; HF ``model_type`` ``ouro``): a decoder whose stack of L blocks runs
R = ``total_ut_steps`` times over ONE set of parameters, with a learned gate
that says after which pass a token may leave.

    h(0) = Embed(tokens)
    for pass t = 1..R:                      the same blocks in every pass
        u = h(t-1)
        for every block:
            u = u + RMSNorm(Attn(RMSNorm(u)))       attn_norm, attn_post_norm
            u = u + RMSNorm(MLP(RMSNorm(u)))        mlp_norm, mlp_post_norm
        h(t)   = RMSNorm(u; final_norm)     the head reads it, pass t+1 starts from it
        z(t)   = h(t) . w_exit + b_exit     one number a token a pass
    lam = sigmoid(z);  p_1 = lam_1,  p_t = lam_t prod_{j<t}(1 - lam_j),
    p_R = prod_{j<R}(1 - lam_j):            after which pass the token exits
    loss = mean over tokens of [sum_t p_t nll_t - beta H(p)]   (the paper's Stage I)

* the four norms of a block are HF's ``input_layernorm``,
  ``input_layernorm_2``, ``post_attention_layernorm`` and
  ``post_attention_layernorm_2``, in that order; ``RMSNorm``, ``rotary`` and
  the attention entry are ``models/olmoe.py``'s, without its QK-norm.
* ``MLP(n) = W_down(silu(W_gate n) * (W_up n))``, bias-free, dense.
* parameters and the residual stream are float32, the matmuls read
  ``dtype``; norms, gate, exit distribution and loss are float32.
* every block application is recomputed in the backward (``recompute``,
  :func:`recomputed`) from its float32 input, each branch's output as the
  norm after it reads it (``branch_out``: that norm's backward is the one
  reader of a branch's last matmul) and, where the flash kernels ran, what
  the backward kernel reads: the forward kernel's output and log-sum-exp and
  its q, k and v (``ops/flash_attention.KEPT_UNDER_REMAT``). At the cell's
  shape that is 2 x 16 MiB and 16.3 + 48 MiB beside the input's 32, and the
  recomputation never runs the forward kernel, the three projections,
  rotary, the head layouts, ``W_o`` or ``down_proj`` again: it is the norms,
  ``gate_proj``, ``up_proj`` and the gated product. That is all it keeps:
  R x L applications hold R times the activations a parameter, and 32 of
  them at 4096 tokens do not fit a 16 GB chip beside the AdamW state
  otherwise (PERF.md section 4). The LAST applications of the forward, the
  first the backward reaches, also keep the two products of ``gate_proj``
  and ``up_proj`` (``KEPT_PROJ``: 2 x 44 MiB each at the cell's shape; their
  one reader is elementwise) and run neither again: as many as
  :func:`plan_kept_proj` finds room for on the device the step is compiled
  for, from shapes, at trace time.
  Passes and layers are Python loops: a ``while`` shows in a device trace
  as one operation AND its body's, and every scope sum would count twice.

As ``models/olmoe.py`` the head is left to the step: ``hidden_only=True``
returns ``(h [B, R, S, d], z [B, R, S])``, and the step's loss is
:meth:`Ouro.head_loss`, which stacks the R states along the batch for ONE
walk of ``ops/token_head.weighted_loss`` (one ``[d, V]`` gradient buffer,
not R). A plain call returns the last pass's ``[B, S, V]`` logits.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from distribuuuu_tpu.models.layers import head_dtype
from distribuuuu_tpu.models.olmoe import (
    Attention,
    RMSNorm,
    _normal,
    decoder_kwargs_from_cfg,
)
from distribuuuu_tpu.models.traits import ArchTraits
from distribuuuu_tpu.ops import token_head


class MLP(nn.Module):
    dim: int
    hidden: int
    dtype: Any

    @nn.compact
    def __call__(self, x, keep_proj: bool = False):
        """``keep_proj``: this application names the products of
        ``gate_proj`` and ``up_proj`` for :func:`recomputed`'s policy."""
        def proj(name, width):
            return nn.Dense(
                width, use_bias=False, dtype=self.dtype,
                param_dtype=jnp.float32, kernel_init=_normal(), name=name,
            )

        x = x.astype(self.dtype)
        gate, up = proj("gate_proj", self.hidden)(x), proj("up_proj", self.hidden)(x)
        if keep_proj:
            gate, up = checkpoint_name(gate, KEPT_PROJ), checkpoint_name(up, KEPT_PROJ)
        return proj("down_proj", self.dim)(nn.silu(gate) * up)


# the name a block gives the value each of its branches returns, as the next
# operation reads it: kept where the block is recomputed, nothing otherwise
BRANCH_OUT = "branch_out"
# ... and the name an :class:`MLP` gives the two products its activation
# reads, in the applications that were told to keep them
KEPT_PROJ = "kept_proj"


def branch_out(x):
    """``x``, a branch's output, named for :func:`recomputed`'s policy."""
    return checkpoint_name(x, BRANCH_OUT)


def recomputed(block, static_argnums=()):
    """``block`` (a module class) recomputed in the backward: THE definition
    of what a recomputed block keeps beside its input, for every decoder
    that recomputes (``models/glm_moe.py`` and ``models/share.py`` too): what
    the flash backward kernel reads, each branch's output and, in the
    applications that name them (``keep_proj``), the gated MLP's two
    products. The norm or the residual add after a branch is the one reader
    of its last matmul's result in the backward, so with that kept the second
    forward stops short of it; where nothing reads it, ``jax.checkpoint``
    keeps nothing. ``static_argnums`` (``self`` is 0) are the arguments of an
    application that are Python values."""
    from distribuuuu_tpu.ops.flash_attention import KEPT_UNDER_REMAT

    return nn.remat(
        block, static_argnums=static_argnums,
        policy=jax.checkpoint_policies.save_only_these_names(
            *KEPT_UNDER_REMAT, BRANCH_OUT, KEPT_PROJ))


class Block(nn.Module):
    dim: int
    num_heads: int
    mlp_hidden: int
    eps: float
    rope_theta: float
    dtype: Any
    attn_impl: str
    mesh: Any

    @nn.compact
    def __call__(self, x, positions, keep_proj: bool = False):
        """``x`` is the float32 residual stream; each branch is normed on
        its way in AND on its way out (the sandwich). ``keep_proj`` is
        :class:`MLP`'s, a Python value of THIS application."""

        def norm(name):
            return RMSNorm(self.eps, name=name)

        with jax.named_scope("attn"):
            x = x + norm("attn_post_norm")(branch_out(Attention(
                self.dim, self.num_heads, self.eps, self.rope_theta, self.dtype,
                self.attn_impl, self.mesh, qk_norm=False, name="attn",
            )(norm("attn_norm")(x), positions)))
        with jax.named_scope("mlp"):
            x = x + norm("mlp_post_norm")(branch_out(
                MLP(self.dim, self.mlp_hidden, self.dtype, name="mlp")(
                    norm("mlp_norm")(x), keep_proj)))
        return x


class ExitGate(nn.Module):
    """``h . w + b`` a token, float32 on the VPU (a [d, 1] matmul would run
    at the MXU's default precision for 2 d operations a token)."""

    @nn.compact
    def __call__(self, h):
        kernel = self.param("kernel", _normal(), (h.shape[-1], 1), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (1,), jnp.float32)
        return (h * kernel[:, 0]).sum(-1) + bias[0]


def exit_log_probs(z):
    """``log p`` of the exit distribution over the R passes (axis 1 of ``z
    [B, R, S]``) from the gates' logits, through ``log_sigmoid``: finite,
    with a finite gradient, however far a gate has gone to 0 or to 1."""
    log_stay = jnp.cumsum(jax.nn.log_sigmoid(-z), axis=1)  # log prod_{j<=t}(1 - lam_j)
    stayed = jnp.pad(log_stay[:, :-1], ((0, 0), (1, 0), (0, 0)))  # ... j < t
    log_exit = stayed[:, :-1] + jax.nn.log_sigmoid(z[:, :-1])
    return jnp.concatenate([log_exit, stayed[:, -1:]], axis=1)  # the last takes the rest


# Device bytes :func:`plan_kept_proj` leaves unplanned: the 1.7 GiB of a 16 GiB
# chip a compiled step stays out of (at 14.3 GiB XLA:TPU still places every
# buffer of the steps here; at 14.8 it rematerializes on its own) and the 0.45
# GiB the cell's step compiles to over what its shapes show once it keeps
# products (the flash backward's and the head's scratch, the gradients as
# they are summed, XLA's own temporaries and the holes between buffers). ONE
# constant, fixed from compiles of the real-size step for a described v5e
# (PERF.md section 6, PR 49), never read from a run.
RESERVE_BYTES = 2200 * 2**20


def plan_kept_proj(capacity_bytes, held_bytes: int, proj_bytes: int,
                   applications: int, reserve_bytes: int) -> int:
    """How many block applications keep their two MLP products
    (``KEPT_PROJ``), ``proj_bytes`` each: the most that leave ``held_bytes``
    (what the step holds without them, counted as if it were all live at
    once) and theirs under ``capacity_bytes - reserve_bytes``. A pure
    function of its arguments; no capacity (a device the table lacks, the
    CPU, a trace nobody declared a device for) plans none."""
    if not capacity_bytes or proj_bytes <= 0:
        return 0
    room = int(capacity_bytes) - reserve_bytes - held_bytes
    return max(0, min(applications, room // proj_bytes))


def _capacity_bytes():
    """HBM of the device the program being traced is compiled FOR
    (``ops/pallas.lowered_for``: on the chip the chip, under
    ``benchmark/rehearse_compile.py`` the chip it describes), by its kind from
    ``telemetry/costmodel.DEVICE_PEAKS``; None where nobody declared one or
    the table has no capacity for the kind. Never the live allocator's
    ``bytes_limit``, which a described device lacks and which may differ from
    process to process: every process must plan the same program."""
    from distribuuuu_tpu.ops import pallas as kernel_tier
    from distribuuuu_tpu.telemetry.costmodel import DEVICE_PEAKS

    kind = getattr(kernel_tier.target_device(), "device_kind", None)
    return DEVICE_PEAKS.get(kind, {}).get("capacity_bytes")


def loop_plan(model, batch: int, seq: int, param_bytes: int) -> dict:
    """The fields of ``loop.plan`` for one traced shape, from shapes alone:
    what the R x L recomputed block applications keep (:func:`kept_plan`)
    and how many of them, the LAST ``kept_proj_applications`` of the forward,
    also keep the MLP's two products (:func:`plan_kept_proj`). ``planned_bytes``
    is what the step is known to hold with them: ``param_bytes`` three times
    (the parameters and AdamW's two moments), everything kept, and the head's
    working set (its float32 ``[d, V]`` gradient and one chunk's logits over
    the R x B stacked rows)."""
    applications = model.depth * model.passes
    fields = kept_plan(
        model, applications, batch, seq, model.dim // model.num_heads,
        "every block application", branches=2 * applications)
    capacity = _capacity_bytes() if model.recompute else None
    proj = 2 * batch * seq * model.mlp_hidden * jnp.dtype(model.dtype).itemsize
    head = 4 * model.vocab_size * (
        model.dim + batch * model.passes * min(seq, model.head_chunk or seq))
    held = 3 * param_bytes + (fields["kept_bytes"] or 0) + head
    n = plan_kept_proj(capacity, held, proj, applications, RESERVE_BYTES)
    if n:
        fields["kept_bytes"] += n * proj
        fields["recomputed"] += (
            f"; the last {n} applications also keep the products of gate_proj "
            "and up_proj and run neither again")
    return {
        "layers": model.depth, "passes": model.passes,
        "block_applications": applications, **fields,
        "kept_proj_applications": n, "kept_proj_bytes": n * proj,
        "capacity_bytes": capacity, "planned_bytes": held + n * proj,
        "reserve_bytes": RESERVE_BYTES,
    }


_planned: set = set()


def _say_plan(model, batch: int, seq: int, param_bytes: int = 0) -> dict:
    """:func:`loop_plan`, said as one ``loop.plan`` record a shape and count,
    at trace time, beside ``kernel.select``: what the loop keeps for the
    backward and what it computes again."""
    plan = loop_plan(model, batch, seq, param_bytes)
    key = (model.depth, model.passes, batch, seq, model.dim, model.recompute,
           plan["kept_proj_applications"])
    if key not in _planned:
        _planned.add(key)
        from distribuuuu_tpu.telemetry import spans

        spans.emit_event("loop.plan", **plan)
        if plan["capacity_bytes"]:  # a run without a telemetry sink says it too
            from distribuuuu_tpu.utils.logger import get_logger

            get_logger().info("loop.plan: %s", {
                k: v for k, v in plan.items() if k != "recomputed"})
    return plan


def kept_plan(model, blocks: int, batch: int, seq: int, head_dim: int,
              what: str, branches: int, flash_blocks: int | None = None) -> dict:
    """The fields of a plan record (``loop.plan``; ``share.plan`` of
    ``models/glm_moe.py`` and ``models/share.py``) that say what ``blocks``
    recomputed blocks keep a step (:func:`recomputed`): ``kept_bytes`` (their
    float32 inputs, ``kept_branch_bytes`` and ``kept_flash_bytes``),
    ``kept_branch_bytes`` (the outputs of the ``branches`` branches, over all
    the blocks, that the backward reads again: ``[tokens, dim]`` each in the
    compute dtype), ``kept_flash_bytes`` (the flash kernel's output and
    log-sum-exp and its q, k and v, of the ``flash_blocks`` blocks that hold
    attention, every one unless given, k and v at ``model.kv_heads`` heads
    where the model has fewer of them; 0 where attention takes another path,
    which names nothing) and ``recomputed``."""
    if not model.recompute:
        return {"kept_bytes": None, "kept_branch_bytes": None,
                "kept_flash_bytes": None, "recomputed": "nothing"}
    from distribuuuu_tpu.models.vit import Attention as VitAttention
    from distribuuuu_tpu.ops.flash_attention import kept_under_remat_bytes

    itemsize = jnp.dtype(model.dtype).itemsize
    flash = 0
    if VitAttention.resolve_impl(model.attn_impl, seq, 0.0) == "flash":
        flash = (blocks if flash_blocks is None else flash_blocks) * (
            kept_under_remat_bytes(
                (batch, model.num_heads, seq, head_dim), itemsize, model.mesh,
                kv_heads=getattr(model, "kv_heads", None)))
    branch = branches * batch * seq * model.dim * itemsize
    return {
        "kept_bytes": blocks * batch * seq * model.dim * 4 + branch + flash,
        "kept_branch_bytes": branch,
        "kept_flash_bytes": flash,
        "recomputed": (
            f"{what}, from its float32 input, the outputs of its branches "
            "that are read again (whose last matmuls run once)" + (
                " and the flash kernel's output, log-sum-exp, q, k and v"
                if flash else "")),
    }


class Ouro(nn.Module):
    """Defaults are ``config.json``'s of ByteDance/Ouro-2.6B."""

    vocab_size: int = 49152
    seq_len: int = 4096  # the paper's Stage-I context; config.json allows 65,536 positions
    dim: int = 2048
    depth: int = 48
    passes: int = 4  # total_ut_steps
    num_heads: int = 16
    mlp_hidden: int = 5632
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    exit_beta: float = 0.05  # MODEL.EXIT_ENTROPY_WEIGHT
    dtype: Any = jnp.bfloat16
    attn_impl: str = "auto"
    mesh: Any = None
    # a block application keeps its input, its two branches' outputs and what
    # the flash backward kernel reads (the forward's output, log-sum-exp, q, k
    # and v); the last of them, as many as fit, the MLP's two products (see above)
    recompute: bool = True
    # positions of every row the head takes at a time; its rows are the
    # batch's sequences R times over
    head_chunk: int = 512

    @nn.compact
    def __call__(self, tokens, train: bool = False, hidden_only: bool = False):
        B, S = tokens.shape
        if S > self.seq_len:
            raise ValueError(
                f"input length {S} exceeds the context LM.SEQ_LEN={self.seq_len}"
            )
        # init differentiates nothing and its parameter tree is still empty
        plan = _say_plan(self, B, S, 0 if self.is_initializing() else sum(
            p.size * p.dtype.itemsize
            for p in jax.tree.leaves(self.variables["params"])))
        x = nn.Embed(
            self.vocab_size, self.dim, name="tok_embed",
            dtype=head_dtype(self.dtype), param_dtype=jnp.float32,
            embedding_init=_normal(),
        )(tokens)
        positions = jnp.arange(S, dtype=jnp.int32)
        block = recomputed(Block, static_argnums=(3,)) if self.recompute else Block
        blocks = [
            block(
                self.dim, self.num_heads, self.mlp_hidden, self.rms_norm_eps,
                self.rope_theta, self.dtype, self.attn_impl, self.mesh,
                name=f"Block_{i}",
            ) for i in range(self.depth)
        ]
        final_norm = RMSNorm(self.rms_norm_eps, name="final_norm")
        exit_gate = ExitGate(name="exit_gate")
        states, gates = [], []
        first_kept = self.depth * self.passes - plan["kept_proj_applications"]
        for t in range(self.passes):
            for i, apply_block in enumerate(blocks):
                x = apply_block(x, positions, t * self.depth + i >= first_kept)
            x = final_norm(x)
            states.append(x.astype(self.dtype))
            with jax.named_scope("exit_gate"):
                gates.append(exit_gate(x))
        kernel = self.param(
            "head", _normal(), (self.dim, self.vocab_size), jnp.float32
        )
        if hidden_only:
            return jnp.stack(states, axis=1), jnp.stack(gates, axis=1)
        return jnp.einsum(
            "bsd,dv->bsv", states[-1], kernel.astype(self.dtype),
            preferred_element_type=head_dtype(self.dtype),
        )

    # ------------------------------------------------ partition-layer hooks
    @staticmethod
    def head_kernel(params):
        return params["head"]

    @staticmethod
    def eval_hidden(outputs):
        """What evaluation's head reads: the last pass."""
        return outputs[0][:, -1]

    def head_loss(self, outputs, kernel, labels, *, topk):
        """``(loss, hits, step metrics)`` of the expected-exit loss.

        The R states go through the head as R x B rows of ONE
        ``weighted_loss`` walk, each token weighted by ``p_t / N`` as a
        constant: that carries the gradient to the stack and the head. The
        gate receives ``nll_t`` as its cotangent through ``sum((p - sg(p))
        * nll) / N``, whose value is 0."""
        states, z = outputs
        B, R, S, d = states.shape
        n = labels.size
        with jax.named_scope("exit_gate"):
            log_p = exit_log_probs(z)
            p = jnp.exp(log_p)
            constant = jax.lax.stop_gradient(p)
        with jax.named_scope("lm_head"):
            ce, (nll, rank) = token_head.weighted_loss(
                states.reshape(B * R, S, d), kernel, jnp.repeat(labels, R, axis=0),
                constant.reshape(B * R, S) / n, chunk=self.head_chunk,
            )
        with jax.named_scope("exit_gate"):
            nll = nll.reshape(B, R, S)
            entropy = -(p * log_p).sum(1).mean()
            loss = ce + ((p - constant) * nll).sum() / n - self.exit_beta * entropy
            steps = jnp.arange(1, R + 1, dtype=p.dtype)[None, :, None]
            extra = {
                "ce": ce, "exit_entropy": entropy,
                "exit_step_mean": (p * steps).sum(1).mean(),
                **{f"ce_pass_{t}": nll[:, t].mean() for t in range(R)},
            }
        last = rank.reshape(B, R, S)[:, -1]
        hits = [(last < k).mean(dtype=jnp.float32) * 100.0 for k in topk]
        return loss, hits, extra

    def dummy_input(self):
        return jnp.zeros((2, min(8, self.seq_len)), jnp.int32)

    def param_spec_table(self):
        from distribuuuu_tpu.parallel.partition import specs

        return specs.lm_spec_table()

    def batch_spec_table(self):
        from distribuuuu_tpu.parallel.partition import specs

        return specs.TOKEN_BATCH_TABLE


def ouro_2_6b(num_classes=49152, **kw):
    """Ouro-2.6B at its published sizes (2.67 B parameters at depth 48, run
    four times; ``depth`` is the one knob a single chip has to turn down)."""
    return Ouro(vocab_size=num_classes, **kw)


def ouro_tiny(num_classes=512, **kw):
    """The same loop at a size the CPU tests run: 64 wide, 4 heads of 16, an
    MLP of 176, 3 layers run 4 times; the head in chunks of 48, which do not
    divide its 128 positions."""
    for key, value in dict(
        seq_len=128, dim=64, depth=3, num_heads=4, mlp_hidden=176, head_chunk=48,
    ).items():
        kw.setdefault(key, value)
    return Ouro(vocab_size=num_classes, **kw)


def _kwargs_from_cfg(cfg, topology) -> dict:
    """``models/olmoe.py``'s (context, depth, attention entry, mesh) and the
    recipe's entropy weight; the pass count is the arch's own."""
    return {**decoder_kwargs_from_cfg(cfg, topology),
            "exit_beta": float(cfg.MODEL.EXIT_ENTROPY_WEIGHT)}


ouro_2_6b.traits = ouro_tiny.traits = ArchTraits(
    token_batch=True, batch_norm=False,
    mesh_axes=("data",),  # attention per device, as models/olmoe.py
    kwargs_from_cfg=_kwargs_from_cfg,
    serve_refusal=(
        "trains only: serving a looped stack takes a key/value cache a pass "
        "and the exit gate in the decode loop, which lm/generate.py lacks"
    ),
)
