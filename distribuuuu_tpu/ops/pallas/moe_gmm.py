"""Grouped matmuls of the sorted experts, on tile-aligned groups.

``ops/moe.sorted_experts`` sorts the (token, slot) rows by expert and runs
the gated three-matrix expert on each group. XLA:TPU's own grouped matmul
(``jax.lax.ragged_dot``) ran that at 52-56 % of the MXU where a dense
matmul of the same work runs at 82 % (PERF.md §6, PR 29). These kernels
take the layout that makes a grouped matmul a dense one:

**Every group starts on a row-tile boundary.** Expert ``e``'s rows sit at
``sum_{j<e} max(ceil(sizes[j] / tm), 1) * tm`` in a buffer of the static
height ``(rows // tm + E) * tm`` (:func:`tile_table`); the rows between a
group's end and the next boundary are zeros going in and are never
gathered coming out. A row tile then belongs to ONE expert: no tile needs
a mask or a second weight block, a scalar-prefetched ``tile -> expert``
table picks the weight block, the block stays in VMEM while consecutive
tiles share it (its block index does not change, so Pallas does not fetch
it again), and the tiles past the last live one are skipped. Every expert
owns at least one tile, so the per-group reductions (dW) write every
expert's block, an empty expert's as zeros.

Three shapes, forward and transposes, bf16 (or float32) operands and
float32 accumulation:

* rows x W        ``[R, K] . [E, K, N] -> [R, N]``     (:func:`gmm`)
* rows x W^T      ``[R, N] . [E, K, N] -> [R, K]``     (``transpose_rhs``)
* rows^T x rows   ``[R, K], [R, N] -> [E, K, N]`` f32  (:func:`tgmm`), the
  group boundary along the contraction

and the epilogues only a kernel of our own can have (:func:`expert_ffn`,
one ``custom_vjp`` around the whole expert body): gate and up in ONE call
that reads each row tile once and writes ``silu(g) * u`` beside the two
pre-activations; the activation's backward in the epilogue of the down
projection's dX; dX of gate and up as ONE contraction; dW written float32
from the float32 accumulator, the down projection's from hidden rows it
makes again from the two pre-activations (the hidden rows are not kept for
the backward: 288 MiB at OLMoE's cell). Six calls a step where
``ragged_dot`` ran nine and XLA elementwise passes and layout copies
between them.

Every call is named ``dtpu_moe_gmm_*`` (``telemetry/schema.KERNEL_NAMES``)
and :func:`expert_ffn` is called under the ``moe_experts`` scope, which
its backward's calls keep (the transpose of a ``custom_vjp`` carries the
forward's name stack) and which is how the benchmark's readers find it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "dtpu_moe_gmm"
CALLS_A_STEP = 6  # _gate_up, _fwd; _act_bwd, _dx_gate_up, _dw_down, _dw_gate_up

# A v5e call gets 16 MiB of its 128 MiB of VMEM unless it asks for more.
# One expert's gate and up blocks at OLMoE's widths are 2 x 4 MiB, and
# Pallas double-buffers every block: the resident weights alone are that
# default, and a float32 dW block [2048, 1024] is 8 MiB. The calls ask for
# half the VMEM, and the widest column tile whose double-buffered blocks
# and float32 temporaries fit _VMEM_BUDGET of it is taken (the whole width
# at OLMoE's: one column tile reads every row tile once; capped at 512 a
# forward call measured 3.66 ms against 3.43, PERF.md section 6, PR 29).
_VMEM_LIMIT = 64 * 1024 * 1024
_VMEM_BUDGET = 40 * 1024 * 1024
ROW_TILE = 256


def row_tile(rows: int, experts: int) -> int | None:
    """The row tile ``tm`` for ``rows`` sorted rows over ``experts`` groups,
    or None where an expert averages under one tile (decode: a handful of
    rows an expert, where the tiles would be mostly padding). The pad rows
    cost ``tm / 2`` an expert on average, so ``tm / (2 * rows / experts)``
    of the work: 6 % at 2048 rows an expert. Measured at OLMoE's cell, the
    whole body forward and backward: 35.6 / 35.1 / 34.4 ms at 128 / 256 /
    512, and the buffers ``experts * tm`` rows taller each time."""
    return ROW_TILE if rows // experts >= ROW_TILE else None


def _widest(n: int, vmem_bytes) -> int:
    """The widest column tile (a 128-multiple that divides ``n``) whose
    blocks, as ``vmem_bytes(tn)`` reckons them, fit ``_VMEM_BUDGET``."""
    fits = [tn for tn in range(128, n + 1, 128)
            if n % tn == 0 and vmem_bytes(tn) <= _VMEM_BUDGET]
    return max(fits, default=128)


def tile_table(sizes, rows: int, tm: int):
    """The aligned layout of ``rows`` sorted rows in groups of ``sizes``
    [E]: ``(expert [tiles], n_live [1], starts [E])``. ``expert[t]`` is the
    group row tile ``t`` belongs to (the last group on the dead tiles past
    ``n_live``, so their weight block is the one already resident),
    ``starts[e]`` the padded row at which group ``e`` begins. The buffer is
    ``tiles * tm`` rows: ``sum max(ceil(s / tm), 1) <= rows // tm + E``."""
    E = sizes.shape[0]
    tiles = rows // tm + E
    per = jnp.maximum((sizes + tm - 1) // tm, 1).astype(jnp.int32)
    ends = jnp.cumsum(per)
    tile = jnp.arange(tiles, dtype=jnp.int32)
    expert = (tile[:, None] >= ends[None, :]).sum(-1, dtype=jnp.int32)
    return jnp.minimum(expert, E - 1), ends[-1:], (ends - per) * tm


def _dot(a, b, contract):
    """``a . b`` over ``contract`` = (dim of a, dim of b), float32 out."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _live(i, n_live):
    """The row block of grid step ``i``: a dead tile (past ``n_live``) maps
    to the last live one, so nothing is fetched or written back for it."""
    return jnp.minimum(i, n_live[0] - 1)


# column tiles outermost, row tiles walked in order inside each
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=_VMEM_LIMIT
)


# ---------------------------------------------------------------------------
# rows x W: grid (column tiles, row tiles), rows innermost so that the
# expert's weight block [K, tn] stays put while the row tiles walk through
# the group. ``body`` gets the blocks: lhs..., rhs..., extras..., outs...
# ---------------------------------------------------------------------------


def _rows_call(body, table, lhs, rhs, extras, outs: int, *, tm,
               transpose_rhs, name, interpret):
    """``outs`` results [R, N] from ``lhs``: row operands [R, K_i], each
    read a whole row tile [tm, K_i] at a time; ``rhs``: weights [E, K, N]
    read a column block of the tile's expert (``[E, N, K]`` and a row block
    under ``transpose_rhs``); ``extras``: row operands [R, N], read a
    [tm, tn] tile like the results."""
    expert, n_live = table
    rows, dtype = lhs[0].shape[0], lhs[0].dtype
    n = rhs[0].shape[1 if transpose_rhs else 2]
    depth = sum(w.shape[2 if transpose_rhs else 1] for w in rhs)
    # every block twice (Pallas double-buffers) and the float32 values of
    # the results before their cast
    tn = _widest(n, lambda tn: 2 * dtype.itemsize * (
        tm * sum(a.shape[1] for a in lhs) + depth * tn
        + (len(extras) + outs) * tm * tn
    ) + 4 * (outs + 1) * tm * tn)

    def weight(j, i, expert, n_live):
        return (expert[i], j, 0) if transpose_rhs else (expert[i], 0, j)

    def block(w):
        k = w.shape[2] if transpose_rhs else w.shape[1]
        return (None, tn, k) if transpose_rhs else (None, k, tn)

    tile = pl.BlockSpec((tm, tn), lambda j, i, e, n_live: (_live(i, n_live), j))
    in_specs = [
        pl.BlockSpec((tm, a.shape[1]), lambda j, i, e, n_live: (_live(i, n_live), 0))
        for a in lhs
    ] + [pl.BlockSpec(block(w), weight) for w in rhs] + [tile] * len(extras)

    def kernel(expert_ref, n_live_ref, *refs):
        @pl.when(pl.program_id(1) < n_live_ref[0])
        def _():
            body(*refs)

    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((rows, n), dtype)] * outs,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, rows // tm),
            in_specs=in_specs,
            out_specs=[tile] * outs,
        ),
        compiler_params=_PARAMS,
        interpret=interpret,
        name=name,
    )(expert, n_live, *lhs, *rhs, *extras)


def _matmul_body(x, w, y, *, contract):
    y[...] = _dot(x[...], w[...], contract).astype(y.dtype)


def _gate_up_body(x, w_gate, w_up, g, u, h):
    rows = x[...]
    gate = _dot(rows, w_gate[...], (1, 0))
    up = _dot(rows, w_up[...], (1, 0))
    g[...] = gate.astype(g.dtype)
    u[...] = up.astype(u.dtype)
    h[...] = (jax.nn.silu(gate) * up).astype(h.dtype)


def _act_bwd_body(dy, w_down, g, u, dg, du):
    """dh = dy . W_down^T stays float32 through the activation's backward:
    h = silu(g) u, so du = dh silu(g), dg = dh u silu'(g)."""
    dh = _dot(dy[...], w_down[...], (1, 1))
    gate = g[...].astype(jnp.float32)
    sig = jax.nn.sigmoid(gate)
    du[...] = (dh * gate * sig).astype(du.dtype)
    dg[...] = (
        dh * u[...].astype(jnp.float32) * sig * (1.0 + gate * (1.0 - sig))
    ).astype(dg.dtype)


def _dx_body(dg, du, w_gate, w_up, dx):
    acc = _dot(dg[...], w_gate[...], (1, 1)) + _dot(du[...], w_up[...], (1, 1))
    dx[...] = acc.astype(dx.dtype)


def gmm(rows, w, table, *, tm: int, transpose_rhs: bool = False,
        interpret: bool = False):
    """``rows[group e] . w[e]`` (``. w[e]^T`` under ``transpose_rhs``) for
    rows in the aligned layout of ``table`` (:func:`tile_table`). Pad rows
    give what their zeros give; rows past the live tiles are not written."""
    contract = (1, 1) if transpose_rhs else (1, 0)
    (out,) = _rows_call(
        functools.partial(_matmul_body, contract=contract), table, (rows,),
        (w,), (), 1, tm=tm, transpose_rhs=transpose_rhs,
        name=f"{NAME}_{'dx' if transpose_rhs else 'fwd'}", interpret=interpret,
    )
    return out


# ---------------------------------------------------------------------------
# rows^T x rows: grid (column tiles, row tiles), the group's row tiles the
# contraction; the expert's [K, tn] float32 block is the accumulator.
# ---------------------------------------------------------------------------


def _dw_kernel(expert_ref, n_live_ref, *refs, n_lhs, lhs_of):
    """``lhs_of`` makes the left rows from the first ``n_lhs`` blocks: the
    row tile itself, or ``silu(g) * u`` from two (:func:`_hidden`)."""
    lhs, refs = refs[:n_lhs], refs[n_lhs:]
    rhs, outs = refs[: len(refs) // 2], refs[len(refs) // 2:]
    i = pl.program_id(1)
    first = (i == 0) | (expert_ref[i] != expert_ref[jnp.maximum(i - 1, 0)])
    live = i < n_live_ref[0]  # a dead tile's expert is the last live one's

    @pl.when(live & first)
    def _():
        for out in outs:
            out[...] = jnp.zeros_like(out)

    @pl.when(live)
    def _():
        rows = lhs[0][...] if lhs_of is None else lhs_of(*lhs)
        for r, out in zip(rhs, outs):
            out[...] += _dot(rows, r[...], (0, 0))


def _hidden(g, u):
    gate = g[...].astype(jnp.float32)
    return (jax.nn.silu(gate) * u[...].astype(jnp.float32)).astype(g.dtype)


def tgmm(lhs, rhs, table, experts: int, *, tm: int, interpret: bool = False,
         name: str = f"{NAME}_dw", lhs_of=None):
    """``lhs[group e]^T . r[group e]`` for each ``r`` of the tuple ``rhs``:
    [E, K, N] float32 each, from [R, K] and [R, N] in the aligned layout.
    One call reads each ``lhs`` tile once for all of ``rhs``. Pad rows must
    be zeros in ``lhs`` or in ``rhs``."""
    expert, n_live = table
    lhs = lhs if lhs_of else (lhs,)
    rows, k = lhs[0].shape
    n = rhs[0].shape[1]
    # the row tiles and the float32 blocks [K, tn], all double-buffered
    tn = _widest(n, lambda tn: 2 * (
        lhs[0].dtype.itemsize * tm * (len(lhs) * k + len(rhs) * tn)
        + 4 * len(rhs) * k * tn
    ))

    out = pl.BlockSpec((None, k, tn), lambda j, i, e, n_live: (e[i], 0, j))
    tile = pl.BlockSpec((tm, tn), lambda j, i, e, n_live: (_live(i, n_live), j))
    return pl.pallas_call(
        functools.partial(_dw_kernel, n_lhs=len(lhs), lhs_of=lhs_of),
        out_shape=[jax.ShapeDtypeStruct((experts, k, n), jnp.float32)] * len(rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, rows // tm),
            in_specs=[pl.BlockSpec(
                (tm, k), lambda j, i, e, n_live: (_live(i, n_live), 0)
            )] * len(lhs) + [tile] * len(rhs),
            out_specs=[out] * len(rhs),
        ),
        compiler_params=_PARAMS,
        interpret=interpret,
        name=name,
    )(expert, n_live, *lhs, *rhs)


# ---------------------------------------------------------------------------
# the expert body and its backward
# ---------------------------------------------------------------------------


def _forward(rows, w_gate, w_up, w_down, table, tm, interpret):
    g, u, h = _rows_call(
        _gate_up_body, table, (rows,), (w_gate, w_up), (), 3, tm=tm,
        transpose_rhs=False, name=f"{NAME}_gate_up", interpret=interpret,
    )
    y = gmm(h, w_down, table, tm=tm, interpret=interpret)
    return y, (g, u)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def expert_ffn(rows, w_gate, w_up, w_down, expert, n_live, tm, interpret):
    """``(silu(rows W_gate[e]) * (rows W_up[e])) W_down[e]`` for rows [R, d]
    in the aligned layout ``(expert, n_live)`` of :func:`tile_table`, with
    ``w_gate``/``w_up`` [E, d, f] and ``w_down`` [E, f, d] as the
    parameters hold them: the matmuls run in ``rows.dtype``, the weights'
    gradients come back in the parameters' dtype, written float32 by the
    kernels. Call it under the ``moe_experts`` scope."""
    cast = (w.astype(rows.dtype) for w in (w_gate, w_up, w_down))
    return _forward(rows, *cast, (expert, n_live), tm, interpret)[0]


def _ffn_fwd(rows, w_gate, w_up, w_down, expert, n_live, tm, interpret):
    cast = tuple(w.astype(rows.dtype) for w in (w_gate, w_up, w_down))
    y, pre = _forward(rows, *cast, (expert, n_live), tm, interpret)
    # a residual is an array: empty ones carry the parameters' dtypes
    like = tuple(jnp.zeros((0,), w.dtype) for w in (w_gate, w_up, w_down))
    return y, (rows, cast, pre, expert, n_live, like)


def _ffn_bwd(tm, interpret, res, dy):
    rows, (w_gate, w_up, w_down), (g, u), expert, n_live, like = res
    table = (expert, n_live)
    E = w_gate.shape[0]
    dg, du = _rows_call(
        _act_bwd_body, table, (dy,), (w_down,), (g, u), 2, tm=tm,
        transpose_rhs=True, name=f"{NAME}_act_bwd", interpret=interpret,
    )
    (dx,) = _rows_call(
        _dx_body, table, (dg, du), (w_gate, w_up), (), 1, tm=tm,
        transpose_rhs=True, name=f"{NAME}_dx_gate_up", interpret=interpret,
    )
    (dw_down,) = tgmm((g, u), (dy,), table, E, tm=tm, interpret=interpret,
                      name=f"{NAME}_dw_down", lhs_of=_hidden)
    dw_gate, dw_up = tgmm(rows, (dg, du), table, E, tm=tm,
                          interpret=interpret, name=f"{NAME}_dw_gate_up")
    dws = (dw.astype(t.dtype) for dw, t in zip((dw_gate, dw_up, dw_down), like))
    return (dx, *dws, None, None)


expert_ffn.defvjp(_ffn_fwd, _ffn_bwd)
