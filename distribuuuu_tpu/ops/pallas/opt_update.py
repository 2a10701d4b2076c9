"""Fused optimizer update: ONE HBM pass over params+grads+moments.

These kernels read each of p/g/m(/v) exactly once and write p/m(/v)
exactly once per leaf, in place: the per-shard fused weight update of
arXiv:2004.13336, and the fusion point of the gather-once ZeRO schedule.
Every leaf is updated where it rests — the kernel's operands are views
of the state's own buffers in the layout the device keeps them in
(``_plan``), aliased to the outputs, so no copy of a leaf goes into or out
of a call. The byte counts that first motivated the kernel
(tools/kernel_bench.py, BENCH_r09.json: the optax chain re-reads its
operands per transform, ~5.4× the one-pass bytes for SGD-momentum and ~8×
for AdamW) are a CPU lowering's; on the v5e XLA fuses the chain into the
gradient fusions, and PERF.md §6 (PR 25) has both arms measured.

Numerics are optax's EXACTLY — same op order, same promotion points
(``mom * trace`` in the trace's own dtype for the bf16 momentum
configuration, f32 elsewhere), same ``safe_int32_increment`` counters —
so the jit-vs-jit A/B against the reference chain is BIT-EXACT on the
CPU tier-1 backend (pinned: tests/test_pallas_kernels.py; on TPU
hardware Mosaic's FMA contraction may differ in the last ulp, covered by
the same test's documented tolerance).

Sharding: the update is elementwise per leaf, so it commutes with any
shard slicing — updating a ZeRO shard equals slicing the unsharded
update (pinned by test). Under a ZeRO layout the kernel lowers
PER-SHARD via :func:`per_shard_update` (shard_map over the rest
layout): each rank runs the one-pass kernel on its own 1/N slice, no
gather and no re-scatter — the fusion point of the gather-once schedule
(ISSUE 15, delivered ROADMAP #1). A plain-replicated layout on several
devices goes through the same shard_map (every rank updates its own
replica): XLA refuses to partition a bare Mosaic call. Only a one-device
mesh runs the whole-leaf call unwrapped.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.layout import Layout

# Block geometry. A leaf is updated in the layout it rests in. The device
# says which that is: the TPU rests an array in the dimension order that pads
# its (8, 128) tiles least, so ResNet-50's classifier [2048, 1000] rests
# column-major and a [1, 1, 256, 64] conv with 256 on the lanes. The kernel
# sees the leaf in that order as the 3-D view [prod(leading), second-minor,
# minor]: only the dimensions above the tiled two are collapsed, which is a
# bitcast whatever the shape and the tile (a stem [7, 7, 3, 64] and RegNetY's
# SE convs, whose 308 channels rest in (1, 128) tiles, included); a 1-D leaf
# stays 1-D. Nothing is flattened, padded, copied or sliced.
_LANES = 128
_SUBLANES = 8  # rows of a 32-bit tile; a 16-bit operand packs 16
# VMEM per operand block: 5 operands (AdamW: 7), double-buffered
_BLOCK_BYTES = 512 * _LANES * 4


def _resting_order(shape, dtype, device) -> tuple:
    """The dimensions of a leaf of this shape, major to minor, in the layout
    it rests in on ``device`` (what the client gives every array it holds,
    and so a step's state). Row-major where there is no device to ask."""
    if device is None or len(shape) < 2:
        return tuple(range(len(shape)))
    layout = device.client.get_default_layout(jnp.dtype(dtype), shape, device)
    return tuple(Layout.from_pjrt_layout(layout).major_to_minor)


def _plan(shape, dtypes, device=None):
    """How one leaf goes to the kernel, decided from its (local) shape, the
    operands' dtypes and the device's layout rule alone: ``(order, view,
    block, copied)``. The operands are transposed to ``order`` (the
    parameter's resting one) and reshaped to the 3-D ``view`` (a 1-D leaf of
    32-bit operands stays 1-D); ``block`` is the BlockSpec on it, sized to
    ``_BLOCK_BYTES`` of VMEM from the minor dimension up: the last dimension
    whole unless one sublane tile of it overflows, then whole [second-minor,
    minor] slabs if one fits, else rows of one. A ragged last block is masked
    by Pallas; no row is padded. ``copied`` says an operand of another dtype
    (a bfloat16 momentum) rests in another order than the parameter, so XLA
    re-lays it out for the call."""
    shape = tuple(shape)
    item = max(jnp.dtype(d).itemsize for d in dtypes)
    sub = max(_SUBLANES * 4 // jnp.dtype(d).itemsize for d in dtypes)
    order = _resting_order(shape, dtypes[0], device)
    if len(shape) == 1 and sub == _SUBLANES:
        # a 1-D leaf rests in 1-D tiles (of up to 1024 elements), which pad
        # otherwise than [1, 1, n]'s: Mosaic takes it as it is where every
        # operand is 32-bit (a 16-bit one it refuses at some lengths)
        return order, shape, (min(shape[0], _BLOCK_BYTES // item),), False
    rested = (1,) * (2 - len(shape)) + tuple(shape[d] for d in order)
    slabs, rows, cols = math.prod(rested[:-2]), rested[-2], rested[-1]
    blk_cols = min(cols, _BLOCK_BYTES // (sub * item) // _LANES * _LANES)
    row_bytes = -(-blk_cols // _LANES) * _LANES * item
    blk_rows = min(rows, _BLOCK_BYTES // row_bytes // sub * sub)
    # a second-minor dimension under a sublane tile rests in a smaller one
    tile_rows = min(sub, 1 << (rows - 1).bit_length())
    slab_bytes = -(-rows // tile_rows) * tile_rows * row_bytes
    blk_slabs = min(slabs, max(1, _BLOCK_BYTES // slab_bytes))
    copied = any(
        _resting_order(shape, d, device) != order for d in set(dtypes[1:])
    )
    return order, (slabs, rows, cols), (blk_slabs, blk_rows, blk_cols), copied


# Attribution (HLO metadata only, no instruction changes): what turns a leaf
# into the kernel's view and back (bitcasts, which take no device time;
# whatever XLA makes a copy after all shows here) reads ``opt_tile`` in a
# trace, the Pallas calls alone ``opt_kernel`` — the two halves of the step's
# ``optimizer_update`` scope that the benchmark's readers tell apart
# (kernels.opt_tile_ms_per_step / kernels.opt_kernel_ms_per_step).
TILE_SCOPE = "opt_tile"
KERNEL_SCOPE = "opt_kernel"
KERNEL_NAME = "dtpu_opt_update_{kind}"  # kind: sgd | sgd_plain | adamw


def _to_view(x, order, view):
    with jax.named_scope(TILE_SCOPE):
        return x.transpose(order).reshape(view)


def _from_view(t, order, shape):
    with jax.named_scope(TILE_SCOPE):
        rested = t.reshape([shape[d] for d in order])
        return rested.transpose([order.index(d) for d in range(len(order))])


def _call(kind, kernel, scalars, tensors, out_dtypes, interpret, device):
    """One Pallas call over one leaf's operands (all of the leaf's shape);
    returns the outputs in that shape."""
    shape = tensors[0].shape
    order, view, block, _ = _plan(shape, [t.dtype for t in tensors], device)
    spec = pl.BlockSpec(block, lambda *ijk: ijk)
    sspec = pl.BlockSpec(scalars.shape, lambda *ijk: (0, 0))
    views = [_to_view(t, order, view) for t in tensors]
    with jax.named_scope(KERNEL_SCOPE):
        outs = pl.pallas_call(
            kernel,
            # the outputs, and with them the operands aliased to them, are
            # declared in HBM: left to itself XLA stages a third of the state
            # through VMEM with asynchronous copies outside the call, which is
            # 0.2-0.5 ms a step faster and leaves the update's traffic where
            # no scope can read it (PERF.md §7 has both arms)
            out_shape=tuple(pltpu.HBM(view, d) for d in out_dtypes),
            grid=tuple(pl.cdiv(n, b) for n, b in zip(view, block)),
            in_specs=[sspec] + [spec] * len(views),
            out_specs=tuple(spec for _ in out_dtypes),
            # every output overwrites the operand it updates (p, then the
            # moments; g sits between): the step donates its state, and
            # without the alias XLA copies p and each moment in front of the
            # call to keep the donated buffers for the outputs
            input_output_aliases={
                1 if k == 0 else k + 2: k for k in range(len(out_dtypes))
            },
            interpret=interpret,
            # a stable kernel name: a trace reader must not depend on what
            # XLA happens to call the custom call under the current scopes
            name=KERNEL_NAME.format(kind=kind),
        )(scalars, *views)
    return tuple(_from_view(o, order, shape) for o in outs)


# ------------------------------------------------------------- the kernels


def _sgd_kernel(sc_ref, p_ref, g_ref, t_ref, po_ref, to_ref,
                *, wd, mom, nesterov):
    """torch-ordered SGD-momentum: decay into the grad, trace, (nesterov)
    lookahead, scale — optax's exact op order, one pass."""
    p = p_ref[...]
    g = g_ref[...]
    t = t_ref[...]
    lr = sc_ref[0, 0]
    u = g + wd * p
    # optax.trace computes decay*t in the TRACE dtype (bf16 momentum
    # rounds here) before the f32 add — mirrored for bit-exactness
    tn = u + (mom * t).astype(jnp.float32)
    upd = u + mom * tn if nesterov else tn
    po_ref[...] = (p + upd * (-lr)).astype(po_ref.dtype)
    to_ref[...] = tn.astype(to_ref.dtype)


def _sgd_plain_kernel(sc_ref, p_ref, g_ref, po_ref, *, wd):
    p = p_ref[...]
    g = g_ref[...]
    lr = sc_ref[0, 0]
    u = g + wd * p
    po_ref[...] = (p + u * (-lr)).astype(po_ref.dtype)


def _adamw_kernel(sc_ref, p_ref, g_ref, mu_ref, nu_ref,
                  po_ref, muo_ref, nuo_ref, *, b1, b2, eps, wd):
    """AdamW: moments, bias correction (the 1−βᵗ factors arrive
    precomputed as scalars — optax computes them once per tree, not per
    element), decoupled decay, scale — one pass over p/g/mu/nu."""
    p = p_ref[...]
    g = g_ref[...]
    mu = mu_ref[...]
    nu = nu_ref[...]
    lr = sc_ref[0, 0]
    c1 = sc_ref[0, 1]
    c2 = sc_ref[0, 2]
    mu_n = (1.0 - b1) * g + b1 * mu
    nu_n = (1.0 - b2) * (g * g) + b2 * nu
    u = (mu_n / c1) / (jnp.sqrt(nu_n / c2) + eps)
    u = u + wd * p
    po_ref[...] = (p + u * (-lr)).astype(po_ref.dtype)
    muo_ref[...] = mu_n.astype(muo_ref.dtype)
    nuo_ref[...] = nu_n.astype(nuo_ref.dtype)


# ------------------------------------------------------------ per-leaf ops


def sgd_leaf(p, g, t, lr, *, wd, mom, nesterov, interpret, device=None):
    """Fused SGD-momentum for ONE leaf → (p_new, trace_new). ``t=None``
    is the momentum-less configuration (no trace tensor at all).
    ``device`` is the one the leaf rests on (:func:`_resting_order`)."""
    sc = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    if t is None:
        (po,) = _call(
            "sgd_plain", functools.partial(_sgd_plain_kernel, wd=wd),
            sc, (p, g), (p.dtype,), interpret, device,
        )
        return po, None
    return _call(
        "sgd",
        functools.partial(_sgd_kernel, wd=wd, mom=mom, nesterov=nesterov),
        sc, (p, g, t), (p.dtype, t.dtype), interpret, device,
    )


def adamw_leaf(p, g, mu, nu, lr, c1, c2, *, b1, b2, eps, wd, interpret,
               device=None):
    """Fused AdamW for ONE leaf → (p_new, mu_new, nu_new). ``c1``/``c2``
    are the 1−β₁ᵗ / 1−β₂ᵗ bias corrections (traced scalars)."""
    sc = jnp.stack([
        jnp.asarray(lr, jnp.float32),
        jnp.asarray(c1, jnp.float32),
        jnp.asarray(c2, jnp.float32),
    ]).reshape(1, 3)
    return _call(
        "adamw",
        functools.partial(_adamw_kernel, b1=b1, b2=b2, eps=eps, wd=wd),
        sc, (p, g, mu, nu), (p.dtype, mu.dtype, nu.dtype), interpret, device,
    )


# ------------------------------------------------- the optax-shaped update


def _find_state(inner, field: str):
    """Locate the one namedtuple in the (possibly nested-tuple) inner
    chain state that carries ``field`` (TraceState.trace /
    ScaleByAdamState.mu). Returns (state, rebuild) where rebuild maps a
    replacement state back into the same nesting."""
    if hasattr(inner, "_fields") and field in inner._fields:
        return inner, lambda new: new
    if isinstance(inner, tuple):
        for i, sub in enumerate(inner):
            found = _find_state(sub, field)
            if found is not None:
                state, rebuild = found

                def wrap(new, i=i, rebuild=rebuild, outer=inner):
                    return tuple(
                        rebuild(new) if j == i else s
                        for j, s in enumerate(outer)
                    )

                return state, wrap
    return None


def fused_optimizer_update(params, grads, opt_state, *, kind: str,
                           wd: float, mom: float, nesterov: bool,
                           b1: float, b2: float, eps: float,
                           interpret: bool, device=None):
    """Drop-in replacement for ``optimizer.update`` + ``apply_updates``
    for the two shipped optimizers (utils/optim.construct_optimizer):
    reads the injected learning rate and the moment trees out of the
    live optax state, runs the fused kernel per leaf, and rebuilds the
    state structure exactly (counters via ``safe_int32_increment``, the
    same dict/namedtuple shapes — ``set_lr`` and checkpoint restore see
    no difference). Returns ``(new_params, new_opt_state)``."""
    import optax

    lr = opt_state.hyperparams["learning_rate"]
    inner = opt_state.inner_state
    if kind == "sgd":
        found = _find_state(inner, "trace") if mom else None
        if found is not None:
            trace_state, rebuild = found
            out = jax.tree.map(
                lambda p, g, t: sgd_leaf(
                    p, g, t, lr, wd=wd, mom=mom, nesterov=nesterov,
                    interpret=interpret, device=device,
                ),
                params, grads, trace_state.trace,
            )
            new_params = jax.tree.map(
                lambda _, o: o[0], params, out,
            )
            new_trace = jax.tree.map(lambda _, o: o[1], params, out)
            new_inner = rebuild(trace_state._replace(trace=new_trace))
        else:
            new_params = jax.tree.map(
                lambda p, g: sgd_leaf(
                    p, g, None, lr, wd=wd, mom=0.0, nesterov=False,
                    interpret=interpret, device=device,
                )[0],
                params, grads,
            )
            new_inner = inner
    elif kind == "adamw":
        adam_state, rebuild = _find_state(inner, "mu")
        count_inc = optax.safe_int32_increment(adam_state.count)
        c1 = 1 - b1 ** count_inc  # optax.tree_bias_correction's exact expr
        c2 = 1 - b2 ** count_inc
        out = jax.tree.map(
            lambda p, g, m, v: adamw_leaf(
                p, g, m, v, lr, c1, c2, b1=b1, b2=b2, eps=eps, wd=wd,
                interpret=interpret, device=device,
            ),
            params, grads, adam_state.mu, adam_state.nu,
        )
        new_params = jax.tree.map(lambda _, o: o[0], params, out)
        new_mu = jax.tree.map(lambda _, o: o[1], params, out)
        new_nu = jax.tree.map(lambda _, o: o[2], params, out)
        new_inner = rebuild(adam_state._replace(
            count=count_inc, mu=new_mu, nu=new_nu,
        ))
    else:
        raise ValueError(f"fused optimizer update: unknown kind {kind!r}")
    new_state = opt_state._replace(
        count=optax.safe_int32_increment(opt_state.count),
        inner_state=new_inner,
    )
    return new_params, new_state


def fused_update_for(optimizer_kind: str | None = None, layout=None):
    """The trainer hook (partition/lowering.py): resolve KERNELS.OPT_UPDATE
    for the configured optimizer and return the fused update callable, or
    ``None`` when the XLA reference path should run. Captures the OPTIM
    hyperparams at step-build time, like the optax chain itself does.

    ``layout`` is the ``specs.state_layout`` dict of the step being
    built: on a mesh of several devices the update lowers per shard
    through it (:func:`per_shard_update`). A step built without one
    (direct callers of the step builders) cannot shard_map, so where the
    kernel would compile into a multi-device program it is unsupported
    and the optax chain runs."""
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.ops import pallas as tier

    kind = optimizer_kind or str(cfg.OPTIM.OPTIMIZER)
    supported = kind in ("sgd", "adamw")
    reason = "" if supported else f"optimizer {kind!r} has no fused kernel"
    if supported and layout is None and tier.compiled_across_devices():
        supported, reason = False, (
            "the step was built without a state layout to shard_map over, "
            "and GSPMD cannot partition a Mosaic call across devices"
        )
    impl = tier.select("opt_update", supported=supported, reason=reason)
    if impl != "pallas":
        return None
    interpret = tier.interpret_mode()
    mesh = None if layout is None else jax.tree.leaves(layout["grads"])[0].mesh
    kwargs = dict(
        kind=kind,
        wd=float(cfg.OPTIM.WEIGHT_DECAY),
        mom=float(cfg.OPTIM.MOMENTUM),
        nesterov=bool(cfg.OPTIM.NESTEROV),
        b1=float(cfg.OPTIM.BETA1),
        b2=float(cfg.OPTIM.BETA2),
        eps=1e-8,  # optax.adamw's default — construct_optimizer passes none
        interpret=interpret,
        # the device the state rests on says in which layout
        device=jax.devices()[0] if mesh is None else mesh.devices.flat[0],
    )

    def update(params, grads, opt_state):
        return fused_optimizer_update(params, grads, opt_state, **kwargs)

    if mesh is None or mesh.size == 1:
        return update
    return per_shard_update(update, layout)


def per_shard_update(update, layout):
    """Lower a fused update PER-SHARD through shard_map over the ZeRO
    layout (ISSUE 15 — the per-shard fused weight update of
    arXiv:2004.13336, replacing the r14 whole-leaf replicated-pin that
    gathered params+grads+moments before every update).

    ``update`` is the whole-leaf callable from :func:`fused_update_for`;
    ``layout`` the ``specs.state_layout`` dict whose ``grads`` tree
    carries the per-leaf shard specs (``data`` added where divisible).
    The returned callable runs the kernel on each rank's LOCAL 1/N slice
    of params/grads/moments — no gather, no re-scatter; the update IS
    shard-local because it is elementwise per leaf (the shard-commute
    contract pinned in tests/test_pallas_kernels.py). Inputs resting in
    a different layout (stage-1 params rest replicated) are sliced by
    the shard_map in_specs — a local view, not a collective; the outer
    rest-layout constraints re-gather stage-1 params once after the
    update, exactly the declared schedule. Scalar state (counters, the
    injected learning rate) rides in replicated and is recomputed
    identically per rank."""
    mesh = jax.tree.leaves(layout["grads"])[0].mesh
    shard_specs = jax.tree.map(lambda sh: sh.spec, layout["grads"])

    def call(params, grads, opt_state):
        from jax.sharding import PartitionSpec as P

        tdef = jax.tree.structure(params)

        def is_param_shaped(node):
            try:
                return jax.tree.structure(node) == tdef
            except (TypeError, ValueError):
                return False

        def place(node):
            if is_param_shaped(node):
                return shard_specs
            return jax.tree.map(lambda _: P(), node)

        # the abstract twin of lowering.abstract_args' place_opt: moment
        # trees (param-structured) ride the shard specs, everything else
        # (counters, hyperparams) is replicated
        ospecs = jax.tree.map(place, opt_state, is_leaf=is_param_shaped)
        fn = jax.shard_map(
            update, mesh=mesh,
            in_specs=(shard_specs, shard_specs, ospecs),
            out_specs=(shard_specs, ospecs), check_vma=False,
        )
        return fn(params, grads, opt_state)

    return call


def leaf_pass_bytes(tree, kind: str = "sgd") -> int:
    """The kernel's DMA model: exact bytes one fused pass moves for a
    param tree (reads p+g+moments, writes p+moments) — what pallas_call
    transfers on TPU per its BlockSpecs, used by tools/kernel_bench.py
    as the pallas arm of the roofline A/B (XLA cost_analysis cannot see
    inside the custom call — the recorded caveat)."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        pb = leaf.size * leaf.dtype.itemsize
        if kind == "adamw":
            total += 7 * pb  # read p,g,mu,nu; write p,mu,nu
        else:
            total += 5 * pb  # read p,g,trace; write p,trace
    return total
