"""The Pallas kernel tier (ISSUE 13): fused kernels for the memory-bound
programs the cost ledger pinned, as ONE subsystem instead of one-offs.

Eight kernels, one discipline:

* ``opt_update``     — fused optimizer update (opt_update.py): ONE HBM
                       pass over params+grads+moments for SGD-momentum
                       and AdamW, replacing the optax chain's re-read-
                       per-transform traffic (measured 5.4×/8× the
                       one-pass bytes on the lowered XLA programs).
* ``conv_epilogue``  — fused 1×1-conv(matmul)+BN-affine+activation for
                       the eval/inference path (conv_epilogue.py): the
                       epilogue rides the matmul tile, the conv output
                       never round-trips HBM unactivated.
* ``decode_attn``    — fused decode attention over the paged KV cache
                       (decode_attn.py): one kernel per (batch, head)
                       program, online softmax over cache blocks, no
                       [B,H,T,C] logits materialization and no fp32
                       cache copy.
* ``moe_gmm``        — the sorted experts' grouped matmuls on tile-
                       aligned groups (moe_gmm.py), with the gated
                       activation, its backward and the float32 dW in
                       their epilogues: six calls where XLA's
                       ``ragged_dot`` ran nine at 53 % of the MXU. It has
                       NO knob: it is ``auto`` always, its tiles follow
                       the shapes, and ``ragged_dot`` stays only where no
                       tile fits (and as the tests' oracle).
* ``moe_rows``       — the row movers of a held SHARE of the experts
                       (moe_rows.py): the tokens' rows into the live
                       tiles of the sorted buffer and the present slots
                       back out, weighted and summed, where XLA's gathers
                       moved every row of a buffer that is three quarters
                       dead. No knob either: it runs where ``moe_gmm``
                       does and some expert is not held.
* ``short_conv``     — LFM2's gated short convolution (short_conv.py):
                       gate -> filter -> gate as ONE call each way over
                       ``x W_in`` as it came, the taps' shifts on the
                       sublanes in VMEM and the filter's gradient summed
                       in the call, where XLA ran six loop fusions and
                       wrote shifted copies. No knob: it runs in a
                       one-device TPU program where the channels fill
                       the lanes and a sequence block divides the length.
* ``head_prologue``  — a q or k projection's way to the flash kernels
                       where every head has its own RMSNorm
                       (head_prologue.py): the norm, the rotary (its
                       half turn a permutation matmul on the idle MXU)
                       and the heads-major layout (the out block's
                       stride) as ONE call each way over the projection's
                       output as it came, where XLA ran separate float32
                       passes. No knob: it runs in a
                       one-device TPU program where a head fills the
                       lanes and a row block divides the length.
* ``ssd``            — Mamba-2's chunked scan (ssd.py; ops/ssd.py has
                       the mathematics and the ``jax.numpy`` body): the
                       chunks walked in order with the state in VMEM, a
                       head's masked-decay matrix built in registers and
                       fed to the MXU, ONE call each way where XLA sent
                       the ``[L, L]`` matrices and the chunks' states
                       through HBM. No knob: it runs in a one-device TPU
                       program where the chunk is the 128 lanes and a
                       group's heads fill whole lane tiles.

Tier discipline (every kernel, no exceptions):

* selection rides a ``KERNELS.*`` config knob — ``auto`` | ``pallas`` |
  ``xla`` — resolved HERE (:func:`select`) so policy lives in one place:
  ``auto`` engages the kernel on the TPU backend for supported shapes
  and stays on XLA elsewhere; ``pallas`` forces it (interpret mode
  off-TPU — the exact-but-slow CPU test path); ``xla`` is the
  always-available escape hatch. An op without a knob (``moe_gmm``, and
  ``flash_attn``: ops/flash_attention.py, whose callers choose it by
  ``attn_impl``) is ``auto``, and ``pallas`` where its caller forces the
  kernel (``interpret=``: the tests).
* every resolution emits a ``kernel.select`` telemetry record and every
  forced-but-unsupported resolution a ``kernel.fallback`` record with
  the reason (run_report's ``kernels`` section reads both), with a
  warn-once log so a silently-ignored knob cannot happen. A knobless op
  has no knob to ask for XLA with, so its ``kernel.fallback`` says every
  time why XLA ran: the platform, or the shape.
* every kernel has an interpret-mode CPU path (this repo's tier-1 story
  — the same ``pallas_call`` with ``interpret=True``) and a pinned
  bit-exactness or tolerance A/B test against the XLA reference
  (tests/test_pallas_kernels.py).
* a compiled kernel never meets GSPMD bare: XLA refuses to partition a
  Mosaic call ("Mosaic kernels cannot be automatically partitioned"), so
  on a program that spans several devices a kernel runs under
  ``shard_map`` or not at all. ``opt_update`` has the state layout and
  lowers per shard on every multi-device mesh. ``conv_epilogue`` and
  ``decode_attn`` sit inside model code that knows no mesh: they engage
  only where the caller declared a one-device program
  (:func:`single_device_program` — the serving engines, one replica per
  chip) or the backend has one device; in the trainer's eval step on a
  dp mesh and in the tensor-parallel decode engine ``auto`` means
  ``xla`` (PERF.md "Bring-up on the chip tool"; ROADMAP S1 decides
  whether they earn a shard_map of their own).

This tier supersedes the repo's earlier one-off Pallas work: the retired
r5 BoTNet attention kernel (deleted at 0.854× XLA e2e — PERF.md) and the
r2 flash-attention kernel (ops/flash_attention.py, which stays: the
decode kernel reuses its block machinery and its lesson — fuse the whole
memory-bound region or lose to XLA's epilogue fusion at the custom-call
boundary).
"""

from __future__ import annotations

import contextlib
import threading

VALID_IMPLS = ("auto", "pallas", "xla")

# op name -> KERNELS knob
KNOBS = {
    "opt_update": "OPT_UPDATE",
    "conv_epilogue": "CONV_EPILOGUE",
    "decode_attn": "DECODE_ATTN",
}

# ops without a knob: ``auto``, or ``pallas`` where the caller forces it
# (``flash_attn`` is ops/flash_attention.py's two kernels, outside this
# package; it resolves here so that its record sits beside the others)
KNOBLESS = ("moe_gmm", "moe_rows", "flash_attn", "short_conv", "head_prologue", "ssd")

# ops that have no shard_map of their own: they engage in a program their
# caller declared one-device (module docstring; ``moe_gmm``'s caller
# declares it inside its shard_map over the data axis, as ``flash_attn``'s
# does where it was handed a mesh)
_NO_SHARD_MAP = ("conv_epilogue", "decode_attn", "moe_gmm", "moe_rows", "flash_attn",
                 "short_conv", "head_prologue", "ssd")

# process-lifetime emission/warn dedup: one kernel.select per (op, impl,
# requested) resolution, one kernel.fallback + warning per (op, reason)
_emitted: set = set()
_warned: set = set()
# trace-time: is this a one-device program? which device is it compiled for?
_program = threading.local()


def reset_selection() -> None:
    """Forget emitted selections/fallbacks (tests)."""
    _emitted.clear()
    _warned.clear()


def validate_kernels_cfg(kcfg=None) -> None:
    """The KERNELS config refusals. An unknown impl name lists the valid
    set; a bad decode block names the lane constraint it violates."""
    if kcfg is None:
        from distribuuuu_tpu.config import cfg

        kcfg = cfg.KERNELS
    for op, knob in KNOBS.items():
        v = kcfg[knob]
        if v not in VALID_IMPLS:
            raise ValueError(
                f"KERNELS.{knob}={v!r} is not a known impl for the "
                f"{op} kernel — valid: {list(VALID_IMPLS)} (auto = pallas "
                "on TPU for supported shapes, xla elsewhere; xla = the "
                "always-available escape hatch)"
            )
    blk = int(kcfg.DECODE_BLOCK)
    if blk < 8 or blk % 8:
        raise ValueError(
            f"KERNELS.DECODE_BLOCK={blk} must be a positive multiple of "
            f"8 (the TPU sublane width): {blk} % 8 = {blk % 8} — the "
            "decode kernel tiles the KV cache into (DECODE_BLOCK, "
            "head_dim) VMEM blocks, with head_dim on the 128-lane axis "
            "and the key blocks on the sublane axis"
        )


def requested(op: str) -> str:
    """The validated KERNELS.* knob value for one op."""
    from distribuuuu_tpu.config import cfg

    validate_kernels_cfg(cfg.KERNELS)
    return str(cfg.KERNELS[KNOBS[op]])


def interpret_mode() -> bool:
    """Whether pallas kernels run the interpreter (any non-TPU backend —
    the tier-1 CPU story; TPU lowers the same call with interpret=False)."""
    import jax

    return jax.default_backend() != "tpu"


@contextlib.contextmanager
def single_device_program():
    """Declare that the code traced inside compiles for ONE device (a
    serving engine pinned to its chip, or the body of a caller's own
    ``shard_map``: ``flash_attention`` per data rank), whatever
    ``jax.device_count()`` says — the kernels without a shard_map of their
    own may engage."""
    prev = getattr(_program, "single", False)
    _program.single = True
    try:
        yield
    finally:
        _program.single = prev


@contextlib.contextmanager
def lowered_for(device):
    """Declare the device the code traced inside is compiled FOR: the step
    of ``partition/lowering.lower`` says its mesh's (on the chip the chip;
    under ``benchmark/rehearse_compile.py`` the described chip, where
    ``jax.devices()[0]`` is a CPU), so that a plan made from the device's
    size at trace time (``models/ouro.plan_kept_proj``) is the same number
    wherever the program is compiled."""
    prev = getattr(_program, "device", None)
    _program.device = device
    try:
        yield
    finally:
        _program.device = prev


def target_device():
    """The device :func:`lowered_for` declared around this trace, or None."""
    return getattr(_program, "device", None)


def compiled_across_devices() -> bool:
    """True when a kernel traced here would be a Mosaic call in a program
    that may span several devices — which GSPMD refuses to partition.
    Interpret mode is plain jax ops and partitions like any other."""
    import jax

    return (
        not interpret_mode()
        and not getattr(_program, "single", False)
        and jax.device_count() > 1
    )


def _emit_once(key, kind: str, **fields) -> None:
    if key in _emitted:
        return
    _emitted.add(key)
    from distribuuuu_tpu.telemetry import spans

    # a literal kind a call: the static schema check reads the call sites
    if kind == "kernel.select":
        spans.emit_event("kernel.select", **fields)
    else:
        spans.emit_event("kernel.fallback", **fields)


def select(op: str, *, supported: bool = True, reason: str = "",
           forced: bool = False, work: dict | None = None, **detail) -> str:
    """Resolve which impl runs for ``op`` right now: ``"pallas"`` or
    ``"xla"``. The ONE policy point of the tier:

    * ``xla`` requested → xla.
    * ``pallas`` requested → pallas when ``supported``; otherwise xla
      with a ``kernel.fallback`` record + ONE warning naming ``reason``
      (forced-but-impossible must be loud, never silent).
    * ``auto`` → pallas only on the TPU backend AND ``supported``; the
      CPU/test backends stay on XLA (interpret mode is exact but orders
      of magnitude slower — it is the *test* path, not the auto path).

    An op in ``KNOBLESS`` requests ``pallas`` where its caller ``forced``
    the kernel (a test) and ``auto`` otherwise, and says in a
    ``kernel.fallback`` record whenever XLA runs in its place.

    Every resolution emits ``kernel.select`` once per process (the
    run_report ``kernels`` section's source), with ``detail`` (the tiles
    a kernel chose) beside the impl, and ``work`` (the operation's own
    shape, ``ssd``'s chunking) whichever impl runs.
    """
    if op not in KNOBS and op not in KNOBLESS:
        raise ValueError(
            f"unknown kernel op {op!r} — one of {list(KNOBS) + list(KNOBLESS)}")
    if supported and op in _NO_SHARD_MAP and compiled_across_devices():
        supported, reason = False, (
            "the program may span several devices, GSPMD cannot partition "
            "a Mosaic call, and this call site has no shard_map"
        )
    if op in KNOBLESS:
        req = "pallas" if forced else "auto"
    else:
        req = requested(op)
    if req == "xla":
        impl = "xla"
    elif req == "pallas":
        impl = "pallas" if supported else "xla"
        if not supported:
            _emit_once(("fb", op, reason), "kernel.fallback", op=op,
                       requested=req, reason=reason or "unsupported")
            wkey = (op, reason)
            if wkey not in _warned:
                _warned.add(wkey)
                from distribuuuu_tpu.utils.logger import get_logger

                get_logger().warning(
                    "%s: pallas requested but unsupported here "
                    "(%s): falling back to the XLA reference path",
                    f"KERNELS.{KNOBS[op]}" if op in KNOBS else op,
                    reason or "unsupported shape",
                )
    else:  # auto
        if supported and interpret_mode():
            import jax

            supported, reason = False, (
                f"platform {jax.default_backend()}: off the TPU the kernel "
                "runs only in the interpreter, which is the tests' path"
            )
        impl = "pallas" if supported else "xla"
        if impl == "xla" and op in KNOBLESS:
            _emit_once(("fb", op, reason), "kernel.fallback", op=op,
                       requested=req, reason=reason)
    detail = {**(work or {}), **({} if impl == "xla" else detail)}
    _emit_once(("sel", op, impl, req, *sorted(detail.items())),
               "kernel.select", op=op, impl=impl, requested=req, **detail)
    return impl
