"""Row movers of the sorted experts: only the rows that landed.

``ops/moe.sorted_experts`` keeps a sorted buffer of the static height of ALL
T * k (token, slot) rows, and where the experts are one chip's share of the
router's (``held=``) most of its row tiles are dead: no (token, slot) landed
there. The grouped matmuls (``moe_gmm.py``) skip the dead tiles. XLA's
movements into and out of the buffer did not: a gather of every row of the
buffer each way, forward and backward, with ``[T, k, d]`` passes between
them, ~205 ns a (token, slot) a step whatever the share (PERF.md section 6,
PR 42). These calls move the rows of the LIVE tiles and the slots that are
PRESENT, and nothing else.

**A row has to be contiguous to be moved alone.** Mosaic refuses a slice of
a tiled array's second-minor dim that is no multiple of the tiling (8 rows,
32-bit too; a bf16 row shares its sublanes with its neighbour besides), so
a ``[N, d]`` array cannot give up one row. The movers read rows from their
PACKED form (:func:`pack`): ``[N * s, 128]`` uint32, row ``n`` the ``s``
sublanes from ``n * s``: 4 KB contiguous, ONE vector register, at d = 2048
in bf16. A bf16 word holds columns ``j`` (low half) and ``j + d / 2`` (high
half), so that unpacking is a shift and a mask and the two halves are
contiguous column blocks, no lane shuffle. ``s`` is a multiple of the 8
sublanes: the widths taken are those with ``d * itemsize % 4096 == 0``.

Three calls, named ``dtpu_moe_rows_*`` (``telemetry/schema.KERNEL_NAMES``):

* ``_pack``: ``[N, d] -> [N * s, 128]``, a dense pass over the live row
  tiles (all of them on the token side). On the buffer's side it reads what
  a ``dtpu_moe_gmm_*`` call wrote, in the layout that call writes.
* ``_take``: token -> sorted, a grid over the buffer's row tiles. ``rows[r]
  = scale[r] * x[tok[r]]`` for the rows of the live tiles, one copy a row
  (~26 ns each: what a row costs to issue, not its 4 KB); the pad rows that
  close a live tile are zeros (``tgmm`` needs them so); a dead tile is not
  written. With ``other`` it also gives the row dots ``<x[tok[r]],
  other[r]>`` in float32.
* ``_combine``: sorted -> token, a grid over blocks of 512 tokens. ``out[t]
  = sum_slot w[t, slot] * y[row of (t, slot)]`` over the present slots,
  summed in float32. It walks the BUFFER, not the slots: a group's rows are
  in token order, so a block's rows in a group are one contiguous run, which
  comes in as a few copies of 64 rows, and each row (one register) is added,
  times its weight, into its token's accumulator. (Its first form walked
  the T * k slots with a copy a present one: the loop over the absent slots
  alone cost what XLA's gather had, 23 ns a slot. PERF.md section 6.)

:func:`take` and :func:`combine` are what ``sorted_experts`` calls: each is
the other's transpose (one ``custom_vjp`` apiece), so forward and backward
of a mixture are these three calls twice over.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distribuuuu_tpu.ops.pallas.moe_gmm import _live

NAME = "dtpu_moe_rows"
LANES = 128
TOKEN_TILE = 128  # tokens a step of the tokens' pack
TOKEN_BLOCK = 512  # tokens a step of the combine: their accumulators, 4 MB
_CHUNK = 64  # rows of the buffer a copy of the combine brings in
_WORDS = 1024  # the tile of a 1-D array of 32-bit words
_UNROLL = 8  # copies a trip of the take's loop: 35 ns a row one at a time, 26 by 8
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary",), vmem_limit_bytes=64 * 1024 * 1024
)


def sublanes_a_row(d: int, dtype) -> int | None:
    """The 128-lane sublanes of one packed row of ``d`` elements, or None
    where the movers do not take the width: a dtype that is neither float32
    nor bfloat16, or a row that is no whole number of (8, 128) tiles."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        return None
    words = d * dtype.itemsize // 4
    return words // LANES if words % (8 * LANES) == 0 and words else None


def unsupported(tokens: int, d: int, dtype) -> str:
    """Why the movers cannot run on ``tokens`` rows of ``d``, or ``""``."""
    if sublanes_a_row(d, dtype) is None:
        return (f"rows of {d} x {jnp.dtype(dtype).name}: no whole number of "
                "(8, 128) tiles of 32-bit words")
    if tokens % TOKEN_BLOCK:
        return f"{tokens} tokens: no multiple of the token block {TOKEN_BLOCK}"
    return ""


def _halves(d: int, dtype) -> tuple:
    """The first column of each float32 block a packed word holds: one for a
    32-bit dtype, two (``j`` and ``j + d / 2``) for a 16-bit one."""
    return (0,) if jnp.dtype(dtype).itemsize == 4 else (0, d // 2)


def _columns(chunk, c: int, d: int, dtype):
    """The float32 column blocks ``[(first column, [n, 128] values)]`` that
    the ``c``-th lane chunk ``[n, 128]`` uint32 of packed rows holds."""
    as_f32 = functools.partial(jax.lax.bitcast_convert_type, new_dtype=jnp.float32)
    firsts = _halves(d, dtype)
    values = ([chunk] if len(firsts) == 1
              else [chunk << 16, chunk & jnp.uint32(0xFFFF0000)])
    return [(first + c * LANES, as_f32(v)) for first, v in zip(firsts, values)]


def _words(ref, c: int, d: int):
    """The ``c``-th lane chunk ``[n, 128]`` uint32 of the packed form of the
    block ``ref`` [n, d]: :func:`_columns`' inverse."""
    as_u32 = functools.partial(jax.lax.bitcast_convert_type, new_dtype=jnp.uint32)
    lo = ref[:, c * LANES:(c + 1) * LANES]
    if ref.dtype.itemsize == 4:
        return as_u32(lo)
    hi = ref[:, d // 2 + c * LANES:d // 2 + (c + 1) * LANES]
    return (as_u32(lo.astype(jnp.float32)) >> 16) | (
        as_u32(hi.astype(jnp.float32)) & jnp.uint32(0xFFFF0000))


def _column(row):
    """``[1, n]`` float32 -> ``[n, 1]``, through a whole-tile transpose."""
    return jnp.broadcast_to(row, (LANES, row.shape[1])).T[:, :1]


def _wait(n, copy):
    """Wait for ``n`` copies of ``copy``'s size on its semaphore."""
    def one(_, carry):
        copy.wait()
        return carry

    jax.lax.fori_loop(0, n, one, 0)


# ---------------------------------------------------------------------------
# pack: [N, d] -> [N * s, 128] uint32, the live row tiles
# ---------------------------------------------------------------------------


def _pack_kernel(n_live, x, out, *, s, d):
    @pl.when(pl.program_id(0) < n_live[0])
    def _():
        n = x.shape[0]
        for c in range(s):
            out[pl.ds(c, n, stride=s), :] = _words(x, c, d)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def pack(x, n_live=None, *, tm: int, interpret: bool = False):
    """``[N, d] -> [N * s, 128]`` uint32: row ``n`` as the ``s`` sublanes
    from ``n * s``, contiguous, for a mover to copy. ``n_live`` [1]: only
    the first ``n_live`` row tiles of ``tm`` are read and written."""
    n, d = x.shape
    s = sublanes_a_row(d, x.dtype)
    if n_live is None:
        n_live = jnp.full((1,), n // tm, jnp.int32)
    return pl.pallas_call(
        functools.partial(_pack_kernel, s=s, d=d),
        out_shape=jax.ShapeDtypeStruct((n * s, LANES), jnp.uint32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // tm,),
            in_specs=[pl.BlockSpec((tm, d), lambda i, n_live: (_live(i, n_live), 0))],
            out_specs=pl.BlockSpec(
                (tm * s, LANES), lambda i, n_live: (_live(i, n_live), 0)),
        ),
        compiler_params=_PARAMS,
        interpret=interpret,
        name=f"{NAME}_pack",
    )(n_live, x)


# ---------------------------------------------------------------------------
# take: token -> sorted, the live row tiles
# ---------------------------------------------------------------------------


def _take_kernel(n_live, real_rows, tok, xw, *refs, s, d, scaled, dotted):
    scale = refs[0] if scaled else None
    other = refs[scaled] if dotted else None
    rows = refs[scaled + dotted]
    dots = refs[scaled + dotted + 1] if dotted else None
    buf, sem = refs[-2:]
    tm = rows.shape[0]
    i = pl.program_id(0)

    def copy(t, r):
        return pltpu.make_async_copy(
            xw.at[pl.ds(pl.multiple_of(t * s, s), s)],
            buf.at[pl.ds(pl.multiple_of(r * s, s), s)], sem)

    @pl.when(i < n_live[0])
    def _():
        # the pad rows close the tile: the real rows are the first n
        n = real_rows[i]

        def start(first, count):  # no branch: a kernel's lowering is set-up
            for r in range(count):
                copy(tok[0, first + r], first + r).start()

        def trip(t, carry):
            start(t * _UNROLL, _UNROLL)
            return carry

        def one(r, carry):
            start(r, 1)
            return carry

        jax.lax.fori_loop(0, n // _UNROLL, trip, 0)
        jax.lax.fori_loop(n // _UNROLL * _UNROLL, n, one, 0)
        _wait(n, copy(0, 0))
        real = jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0) < n
        factor = _column(scale[...]) if scaled else None
        dot = jnp.zeros((tm, 1), jnp.float32)
        for c in range(s):
            chunk = buf[pl.ds(c, tm, stride=s), :]
            for first, values in _columns(chunk, c, d, rows.dtype):
                values = jnp.where(real, values, 0.0)
                if dotted:
                    dot += jnp.sum(
                        values * other[:, first:first + LANES].astype(jnp.float32),
                        axis=1, keepdims=True)
                if scaled:
                    values = values * factor
                rows[:, first:first + LANES] = values.astype(rows.dtype)
        if dotted:
            dots[...] = jnp.broadcast_to(dot, (tm, LANES)).T[:1]


@functools.partial(
    jax.jit, static_argnames=("tokens", "d", "dtype", "interpret"))
def _take(xw, tok, n_live, *, tokens: int, d: int, dtype, scale=None,
          other=None, interpret: bool = False):
    """``rows[r] = scale[r] * x[tok[r]]`` [tiles * tm, d] ``dtype`` from the
    packed ``xw`` (:func:`pack` of ``x`` [tokens, d]) for the rows of the
    first ``n_live`` tiles; ``tok`` [tiles, tm] int32, ``tokens`` (or more)
    on a pad row, which reads zeros and must not be followed by a real row
    in its tile. ``scale`` [tiles, tm] float32 or None. With ``other``
    [tiles * tm, d] also ``<x[tok[r]], other[r]>`` [tiles, tm] float32
    (without the scale). Rows, and dots, of a dead tile are not written."""
    tiles, tm = tok.shape
    s = sublanes_a_row(d, dtype)
    scaled, dotted = scale is not None, other is not None
    real_rows = jnp.sum(tok < tokens, axis=1, dtype=jnp.int32)

    def by_tile(i, n_live, real_rows):
        return (_live(i, n_live), 0, 0)

    vector = pl.BlockSpec((None, 1, tm), by_tile)
    tile = pl.BlockSpec((tm, d), lambda i, n_live, real_rows: (_live(i, n_live), 0))
    out = pl.pallas_call(
        functools.partial(_take_kernel, s=s, d=d, scaled=scaled, dotted=dotted),
        out_shape=[jax.ShapeDtypeStruct((tiles * tm, d), dtype)] + [
            jax.ShapeDtypeStruct((tiles, 1, tm), jnp.float32)] * dotted,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles,),
            in_specs=[
                pl.BlockSpec((None, 1, tm), by_tile, memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ] + [vector] * scaled + [tile] * dotted,
            out_specs=[tile] + [vector] * dotted,
            scratch_shapes=[pltpu.VMEM((tm * s, LANES), jnp.uint32),
                            pltpu.SemaphoreType.DMA(())],
        ),
        compiler_params=_PARAMS,
        interpret=interpret,
        name=f"{NAME}_take",
    )(n_live, real_rows, tok.reshape(tiles, 1, tm), xw,
      *([scale.reshape(tiles, 1, tm)] if scaled else []), *([other] * dotted))
    return (out[0], out[1].reshape(tiles, tm)) if dotted else out[0]


# ---------------------------------------------------------------------------
# combine: sorted -> token, the present slots
# ---------------------------------------------------------------------------


def block_bounds(flat, starts, experts: int, top_k: int):
    """Where each token block's rows sit in each group of the buffer:
    ``[(blocks + 1) * E]`` int32, entry ``j * E + e`` the buffer row at which
    the rows of group ``e`` whose token is in block ``j`` (of ``TOKEN_BLOCK``)
    start. A group's rows are in token order (the sort is stable), so a
    block's are one run, which ends where the next block's starts. ``flat``
    [T * k]: the group of each (token, slot), ``experts`` or more if absent;
    ``starts`` [E]: ``moe_gmm.tile_table``'s."""
    hot = flat.reshape(-1, TOKEN_BLOCK * top_k, 1) == jnp.arange(
        experts, dtype=flat.dtype)
    before = jnp.cumsum(hot.sum(axis=1, dtype=jnp.int32), axis=0)
    before = jnp.concatenate([jnp.zeros_like(before[:1]), before])
    return (starts.astype(jnp.int32) + before).reshape(-1)


def _combine_kernel(bounds, src, w, yw, out, acc, ybuf, sbuf, sem, *,
                    experts, top_k, rows, s, d):
    tb = out.shape[0]
    chunk, window = ybuf.shape[1] // s, sbuf.shape[0] // experts
    j = pl.program_id(0)

    def run(e):
        lo = bounds[j * experts + e]
        return lo, bounds[(j + 1) * experts + e] - lo

    def indices(e, lo):  # the (token, slot) of the run's rows, into SMEM
        first = lo // _WORDS * _WORDS  # src is padded: the window fits
        return first, pltpu.make_async_copy(
            src.at[pl.ds(pl.multiple_of(first, _WORDS), window)],
            sbuf.at[pl.ds(pl.multiple_of(e * window, _WORDS), window)],
            sem.at[0, e])

    def packed(e, at):  # `chunk` packed rows of the buffer from row `at`
        at = jnp.minimum(at, rows - chunk)
        return at, pltpu.make_async_copy(
            yw.at[pl.ds(pl.multiple_of(at * s, s), chunk * s)], ybuf.at[e],
            sem.at[1, e])

    def ask(e, carry):  # every group's run: its indices, its first rows
        lo, n = run(e)

        @pl.when(n > 0)
        def _():
            indices(e, lo)[1].start()
            packed(e, lo)[1].start()

        return carry

    def add(e, carry):  # the run's rows, each into its token's accumulator
        lo, n = run(e)

        @pl.when(n > 0)
        def _():
            first, copy = indices(e, lo)
            copy.wait()

            def some(c, carry):
                at, copy = packed(e, lo + c * chunk)

                @pl.when(c > 0)
                def _():
                    copy.start()

                copy.wait()

                def one(r, carry):  # row r of the buffer, to its token
                    pair = sbuf[e * window + r - first]
                    row = ybuf[e, pl.ds(pl.multiple_of((r - at) * s, s), s), :]
                    weight = w[0, pair - j * tb * top_k]
                    token = pl.multiple_of((pair // top_k - j * tb) * s, s)
                    for h, (_, values) in enumerate(_columns(row, 0, d, out.dtype)):
                        acc[h, pl.ds(token, s), :] += weight * values
                    return carry

                begin = lo + c * chunk
                jax.lax.fori_loop(begin, jnp.minimum(begin + chunk, lo + n), one, 0)
                return carry

            jax.lax.fori_loop(0, (n + chunk - 1) // chunk, some, 0)

        return carry

    # loops, not Python's: a kernel is traced and lowered at every call site
    # of every program that holds it, and a group's body eight times over
    # cost a warm run 20 s of set-up (PERF.md section 6, PR 42)
    jax.lax.fori_loop(0, experts, ask, 0)
    acc[...] = jnp.zeros_like(acc)
    jax.lax.fori_loop(0, experts, add, 0)
    for c in range(s):
        for h, first in enumerate(_halves(d, out.dtype)):
            out[:, first + c * LANES:first + (c + 1) * LANES] = acc[
                h, pl.ds(c, tb, stride=s), :].astype(out.dtype)


@functools.partial(
    jax.jit, static_argnames=("experts", "d", "dtype", "interpret"))
def _combine(yw, src, w, bounds, *, experts: int, d: int, dtype,
             interpret: bool = False):
    """``out[t] = sum_slot w[t, slot] * y[row of (t, slot)]`` [T, d] ``dtype``
    over the PRESENT slots, summed in float32, from the packed ``yw``
    (:func:`pack` of ``y`` [rows, d]). It walks the buffer, not the slots: a
    step takes a block of ``TOKEN_BLOCK`` tokens and, group by group, the
    run of the buffer's rows that are theirs (``bounds``:
    :func:`block_bounds`), copies the run in (contiguous: a few copies a
    group, not one a row) and adds each row, times its weight, into its
    token's accumulator; ``src`` [rows] int32 says whose each row is
    (``token * k + slot``). ``w`` [T, k] float32."""
    tokens, k = w.shape
    s = sublanes_a_row(d, dtype)
    rows = yw.shape[0] // s
    tb = TOKEN_BLOCK
    # a run's indices come in as whole tiles of a 1-D array: 1024 words
    window = -(-(tb + _WORDS) // _WORDS) * _WORDS
    src = src.reshape(-1)
    src = jnp.pad(src, (0, -(-rows // _WORDS) * _WORDS + window - rows))
    return pl.pallas_call(
        functools.partial(_combine_kernel, experts=experts, top_k=k,
                          rows=rows, s=s, d=d),
        out_shape=jax.ShapeDtypeStruct((tokens, d), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(tokens // tb,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((None, 1, tb * k), lambda j, bounds: (j, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tb, d), lambda j, bounds: (j, 0)),
            scratch_shapes=[
                pltpu.VMEM((len(_halves(d, dtype)), tb * s, LANES), jnp.float32),
                pltpu.VMEM((experts, _CHUNK * s, LANES), jnp.uint32),
                pltpu.SMEM((experts * window,), jnp.int32),
                pltpu.SemaphoreType.DMA((2, experts)),
            ],
        ),
        compiler_params=_PARAMS,
        interpret=interpret,
        name=f"{NAME}_combine",
    )(bounds, src, w.reshape(tokens // tb, 1, tb * k), yw)


# ---------------------------------------------------------------------------
# the pair sorted_experts calls: each is the other's transpose
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def take(x, src, dst, bounds, scale, n_live, interpret: bool = False):
    """``x[src // k]`` [tiles * tm, d] for the rows of the live tiles, zeros
    on their pad rows (``src`` [tiles, tm] = T * k), nothing written on the
    dead tiles. ``dst`` [T, k] is where each (token, slot) went (past the
    buffer: nowhere) and ``bounds`` :func:`block_bounds`': by them the
    backward sums a token's cotangent rows. (``scale`` is :func:`combine`'s:
    the two take one set of tables.)"""
    tokens, d = x.shape
    return _take(pack(x, tm=TOKEN_TILE, interpret=interpret),
                 src // dst.shape[1], n_live, tokens=tokens, d=d,
                 dtype=x.dtype, interpret=interpret)


def _take_fwd(x, src, dst, bounds, scale, n_live, interpret):
    return (take(x, src, dst, bounds, scale, n_live, interpret),
            (src, dst, bounds, n_live))


def _experts(bounds, tokens: int) -> int:
    return bounds.shape[0] // (tokens // TOKEN_BLOCK + 1)


def _take_bwd(interpret, res, g):
    src, dst, bounds, n_live = res
    present = (dst < g.shape[0]).astype(jnp.float32)
    dx = _combine(pack(g, n_live, tm=src.shape[1], interpret=interpret), src,
                  present, bounds, experts=_experts(bounds, dst.shape[0]),
                  d=g.shape[1], dtype=g.dtype, interpret=interpret)
    return dx, None, None, None, None, None


take.defvjp(_take_fwd, _take_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def combine(y, w, src, dst, bounds, scale, n_live, interpret: bool = False):
    """``sum_slot w[t, slot] * y[dst[t, slot]]`` [T, d] over the present
    slots (``dst`` inside the buffer), in float32 before the cast. ``src``
    [tiles, tm], ``dst`` and ``bounds`` are :func:`take`'s; ``scale``
    [tiles, tm] float32 is ``w`` as the buffer's rows hold it (0 on a pad
    row), which the backward scales a token's cotangent by, row by row."""
    return _combine(pack(y, n_live, tm=src.shape[1], interpret=interpret), src,
                    w, bounds, experts=_experts(bounds, w.shape[0]),
                    d=y.shape[1], dtype=y.dtype, interpret=interpret)


def _combine_fwd(y, w, src, dst, bounds, scale, n_live, interpret):
    return (combine(y, w, src, dst, bounds, scale, n_live, interpret),
            (y, src, dst, scale, n_live))


def _combine_bwd(interpret, res, g):
    """``dy[r] = w[r] * g[token of r]`` and ``dw = <g[token], y[r]>``, the
    dots gathered to their (token, slot): one gather of T * k floats."""
    y, src, dst, scale, n_live = res
    tokens, k = dst.shape
    dy, dots = _take(
        pack(g, tm=TOKEN_TILE, interpret=interpret), src // k, n_live,
        tokens=tokens, d=g.shape[1], dtype=y.dtype, scale=scale, other=y,
        interpret=interpret)
    dw = jnp.where(dst < y.shape[0],
                   dots.reshape(-1)[jnp.minimum(dst, y.shape[0] - 1)], 0.0)
    return dy, dw, None, None, None, None, None


combine.defvjp(_combine_fwd, _combine_bwd)
