"""Fused optimizer update: ONE HBM pass over params+grads+moments.

The cost ledger's motivation, measured on the lowered XLA programs (the
numbers tools/kernel_bench.py re-derives into BENCH_r09.json): the optax
chain re-reads its operands per transform — ``add_decayed_weights`` →
``trace``/``scale_by_adam`` → ``scale`` each materialize an
intermediate, so the SGD-momentum update accesses ~5.4× and AdamW ~8×
the one-pass byte count. At ResNet-50 scale (25.6M params) that is
~500 MB of avoidable HBM traffic per step on a path with near-zero
arithmetic intensity — pure roofline loss. These kernels read each of
p/g/m(/v) exactly once and write p/m(/v) exactly once per leaf: the
per-shard fused weight update of arXiv:2004.13336, which is also the
fusion point ROADMAP #1's overlapped ZeRO update will reuse.

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

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Tile geometry: leaves are flattened and viewed as (rows, 128) lanes;
# one grid step updates _BLK_ROWS rows (_BLK_ROWS·128·4B·~5 tensors
# ≈ 1.3 MiB VMEM-resident — well under budget with double buffering).
_LANES = 128
_BLK_ROWS = 512


def _pad_rows(n: int) -> tuple[int, int]:
    """(rows, block_rows) for an n-element leaf: rows is the padded
    (rows, 128) view's height — a multiple of 8 sublanes, and of the
    block height when the leaf spans multiple blocks."""
    rows = -(-n // _LANES)
    rows = -(-rows // 8) * 8
    if rows > _BLK_ROWS:
        rows = -(-rows // _BLK_ROWS) * _BLK_ROWS
        return rows, _BLK_ROWS
    return rows, rows


# Attribution (HLO metadata only, no instruction changes): the pad/reshape
# of a leaf to (rows, 128) and back reads ``opt_tile`` in a trace, the
# Pallas calls alone ``opt_kernel`` — the two halves of the step's
# ``optimizer_update`` scope that the benchmark's readers tell apart
# (kernels.opt_tile_ms_per_step / kernels.opt_kernel_ms_per_step).
TILE_SCOPE = "opt_tile"
KERNEL_SCOPE = "opt_kernel"
KERNEL_NAME = "dtpu_opt_update_{kind}"  # kind: sgd | sgd_plain | adamw


def _tiled(x, rows: int):
    with jax.named_scope(TILE_SCOPE):
        flat = x.reshape(-1)
        pad = rows * _LANES - flat.shape[0]
        if pad:
            flat = jnp.pad(flat, (0, pad))
        return flat.reshape(rows, _LANES)


def _untiled(t, shape, n: int):
    with jax.named_scope(TILE_SCOPE):
        return t.reshape(-1)[:n].reshape(shape)


def _call(kind, kernel, scalars, tensors, out_dtypes, rows, blk, interpret):
    spec = pl.BlockSpec((blk, _LANES), lambda i: (i, 0))
    sspec = pl.BlockSpec(scalars.shape, lambda i: (0, 0))
    with jax.named_scope(KERNEL_SCOPE):
        return pl.pallas_call(
            kernel,
            out_shape=tuple(
                jax.ShapeDtypeStruct((rows, _LANES), d) for d in out_dtypes
            ),
            grid=(rows // blk,),
            in_specs=[sspec] + [spec] * len(tensors),
            out_specs=tuple(spec for _ in out_dtypes),
            interpret=interpret,
            # a stable kernel name: a trace reader must not depend on what
            # XLA happens to call the custom call under the current scopes
            name=KERNEL_NAME.format(kind=kind),
        )(scalars, *tensors)


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


def sgd_leaf(p, g, t, lr, *, wd, mom, nesterov, interpret):
    """Fused SGD-momentum for ONE leaf → (p_new, trace_new). ``t=None``
    is the momentum-less configuration (no trace tensor at all)."""
    n = p.size
    rows, blk = _pad_rows(n)
    sc = jnp.asarray(lr, jnp.float32).reshape(1, 1)
    if t is None:
        (po,) = _call(
            "sgd_plain", functools.partial(_sgd_plain_kernel, wd=wd),
            sc, (_tiled(p, rows), _tiled(g, rows)), (p.dtype,),
            rows, blk, interpret,
        )
        return _untiled(po, p.shape, n), None
    po, to = _call(
        "sgd",
        functools.partial(_sgd_kernel, wd=wd, mom=mom, nesterov=nesterov),
        sc, (_tiled(p, rows), _tiled(g, rows), _tiled(t, rows)),
        (p.dtype, t.dtype),
        rows, blk, interpret,
    )
    return _untiled(po, p.shape, n), _untiled(to, t.shape, n)


def adamw_leaf(p, g, mu, nu, lr, c1, c2, *, b1, b2, eps, wd, interpret):
    """Fused AdamW for ONE leaf → (p_new, mu_new, nu_new). ``c1``/``c2``
    are the 1−β₁ᵗ / 1−β₂ᵗ bias corrections (traced scalars)."""
    n = p.size
    rows, blk = _pad_rows(n)
    sc = jnp.stack([
        jnp.asarray(lr, jnp.float32),
        jnp.asarray(c1, jnp.float32),
        jnp.asarray(c2, jnp.float32),
    ]).reshape(1, 3)
    po, muo, nuo = _call(
        "adamw",
        functools.partial(_adamw_kernel, b1=b1, b2=b2, eps=eps, wd=wd),
        sc, (_tiled(p, rows), _tiled(g, rows), _tiled(mu, rows),
             _tiled(nu, rows)),
        (p.dtype, mu.dtype, nu.dtype),
        rows, blk, interpret,
    )
    return (_untiled(po, p.shape, n), _untiled(muo, mu.shape, n),
            _untiled(nuo, nu.shape, n))


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
                           interpret: bool):
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
                    interpret=interpret,
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
                    interpret=interpret,
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
                interpret=interpret,
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
    kwargs = dict(
        kind=kind,
        wd=float(cfg.OPTIM.WEIGHT_DECAY),
        mom=float(cfg.OPTIM.MOMENTUM),
        nesterov=bool(cfg.OPTIM.NESTEROV),
        b1=float(cfg.OPTIM.BETA1),
        b2=float(cfg.OPTIM.BETA2),
        eps=1e-8,  # optax.adamw's default — construct_optimizer passes none
        interpret=interpret,
    )

    def update(params, grads, opt_state):
        return fused_optimizer_update(params, grads, opt_state, **kwargs)

    if layout is None or jax.tree.leaves(layout["grads"])[0].mesh.size == 1:
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
