"""A q or k projection's way to the flash kernels as ONE call each way: the
per-head RMSNorm, the rotary embedding and the heads-major layout read the
projection's output once and write the kernels' input once
(``ops/head_prologue.py`` chooses the path; ``models/lfm2_moe.HeadNorm`` has
the ``jax.numpy`` lines, which are the reference).

XLA compiled the ``jax.numpy`` form (reshape, transpose, float32 norm, a
slice and a concatenate at lane 64 of a 128-lane head, a cast) to separate
float32 passes: 9 % of the HBM's bandwidth on the bytes a perfect fusion
moves in SDAR's and Trinity-Mini's cells (PERF.md section 6, PR 51). Two
calls, named ``dtpu_head_prologue_*`` (``telemetry/schema.KERNEL_NAMES``):

* ``_fwd``: ``t [N, S, n D], scale [D] (, cos, sin [S, D]) -> y [N, n, S, D]``.
* ``_bwd``: ``dy [N, n, S, D], t, scale (, cos, sin) -> dt [N, S, n D],
  dscale [D]`` float32: the normalised ``x`` and its ``rsqrt`` made again in
  VMEM from ``t`` (nothing else is a residual), the rotation transposed, and
  the scale's gradient summed a row block in the call.

What keeps the bytes at one pass, and the pass at the HBM's pace:

* **``t`` goes in as the projection wrote it** (``blk`` rows the whole ``n D``
  wide, contiguous in HBM) **and ``y`` comes out heads-major**: the out block
  is ``[n, blk, D]`` of ``[N, n, S, D]``, so the transpose is the DMA's
  stride and no copy. The backward reads ``dy`` the same way and writes
  ``dt`` whole.
* **rotate-half is a permutation matmul on the idle MXU**, not a slice and a
  concatenate at lane ``D / 2``: ``roll(x, D / 2) = x P`` with ``P`` a 0/1
  matrix is EXACT in one pass for bfloat16 (float32 operands take the
  full-precision passes), and the sign of ``rotate_half([a, b]) = [-b, a]``
  is folded into the sine table (``-sin`` on the first half's lanes). The
  roll is taken on the projection's output AS IT CAME, ``roll(x inv w) =
  roll(x) inv roll(w)`` to the bit, so it does not wait for the norm. A lane
  roll on the XLU (``pltpu.roll``) beside the norm's lane reduce ran the
  forward call at 17 % of the HBM's bandwidth where either alone reaches 75
  (PR 51's chip runs): the v5e's cross-lane unit is the bottleneck, so both
  cross-lane steps of the forward go to the MXU.
* **the sum of squares' lane sum is a matmul with ones**: the square of a
  bfloat16 is 16 significant bits, two bfloat16 parts exactly, and the MXU
  sums each part over the lanes INTO every lane (no broadcast after it);
  float32 sums of exact terms, ``jnp.mean``'s arithmetic in another order.
  The backward's second reduce (``mean(g xhat)``, float32 products) stays on
  the XLU, alone there. Other dtypes reduce on the XLU.
* the tables are float32 ``[S, D]`` indexed by ROW (SDAR's rows carry the
  positions ``0..S/2 - 1`` twice), made once by XLA; the grid walks the
  sequences of a row block innermost, so a table block is fetched once a row
  block; a row chunk's tables are loaded once and every head of the chunk
  reads them from registers. float32 arithmetic (a v5e's VPU has no
  bfloat16), one rounding on the store, as ``RMSNorm`` and ``rotary`` round.
* ``dscale``: 8 sublanes of float32 partial sums a grid step (``[steps * 8,
  D]``), added outside by one XLA reduction. No ``[N, n, S, D]`` product
  exists in HBM.

The row block follows the shape (:func:`row_block`: the tallest of 512, 256,
... that divides ``S`` and whose double-buffered blocks fit the budget) and the
calls ask for the VMEM their blocks need, no more.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "dtpu_head_prologue"
LANES = 128
SUBLANES = 8  # rows of a float32 register: what a partial sum of dscale holds
_ROW_BLOCKS = (512, 256, 128, 64, 32, 16, 8)
_ROW_CHUNKS = (128, 64, 32, 16, 8)
# A v5e call gets 16 MiB of its 128 MiB of VMEM unless it asks for more; the
# backward's double-buffered blocks at 512 rows of 32 x 128 in bf16 are 25
_VMEM_BUDGET = 40 * 1024 * 1024
_VMEM_SLACK = 4 * 1024 * 1024  # Mosaic's own scratch beside the blocks


def packed_rows(dtype) -> int:
    """Rows of one packed sublane tile: the least a block may hold."""
    return SUBLANES * 4 // jnp.dtype(dtype).itemsize


def _block_bytes(blk: int, n: int, D: int, dtype, rotary: bool, backward: bool) -> int:
    """The double-buffered blocks of one call, in bytes."""
    wide = (3 if backward else 2) * blk * n * D * jnp.dtype(dtype).itemsize
    tables = 2 * blk * D * 4 if rotary else 0
    return 2 * (wide + tables + SUBLANES * D * 4 * (1 + backward))


def row_block(S: int, n: int, D: int, dtype, rotary: bool = True) -> int | None:
    """The tallest row block that divides ``S``, holds whole packed tiles and
    fits the budget with the backward's blocks, or None."""
    for blk in _ROW_BLOCKS:
        if (S % blk == 0 and blk % packed_rows(dtype) == 0
                and _block_bytes(blk, n, D, dtype, rotary, True) <= _VMEM_BUDGET):
            return blk
    return None


def unsupported(S: int, n: int, D: int, dtype, rotary: bool = True) -> str:
    """Why the calls cannot run on ``S`` rows of ``n`` heads of ``D``, or
    ``""``."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.bfloat16, jnp.float32):  # what the MXU's steps take
        return f"{dtype.name}: neither bfloat16 nor float32"
    if D % LANES:
        return f"a head of {D}: no multiple of the {LANES} lanes"
    if row_block(S, n, D, dtype, rotary) is None:
        return (f"{S} rows: no multiple of a row block {_ROW_BLOCKS} of whole "
                f"{packed_rows(dtype)}-row tiles that fits the VMEM's budget at "
                f"{n} heads of {D}")
    return ""


def row_chunk(blk: int, dtype) -> int:
    """Rows of a chunk: what the float32 values of one trip of the inner loop
    cover, a head at a time. The tallest that divides the block: a trip pays
    the whole latency of its chain (reduce, rsqrt, store) once, and k's four
    heads a trip run 1.4 x faster at 128 rows than at 32 (PR 51's chip
    runs; q's thirty-two read the same at either)."""
    return next(tr for tr in _ROW_CHUNKS
                if blk % tr == 0 and tr % packed_rows(dtype) == 0)


def _f32(x):
    return x.astype(jnp.float32)


def _dot(a, b):
    """``a b`` on the MXU with float32 sums; float32 operands at full
    precision (bfloat16 ones are exact as they are)."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32, precision=(
        jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None))


def _normalised(raw, eps):
    """``(x rsqrt(mean(x^2) + eps), the rsqrt)`` in float32 over the lanes
    of a head ``raw [tr, D]``. The square of a bfloat16 has 16 significant
    bits, so it is two bfloat16 parts EXACTLY, and the MXU sums each part's
    lanes into every lane (float32 sums of exact terms: ``jnp.mean``'s
    arithmetic, in another order); other dtypes reduce on the XLU."""
    x = _f32(raw)
    sq, D = x * x, raw.shape[-1]
    if raw.dtype == jnp.bfloat16:
        ones = jnp.ones((D, D), jnp.bfloat16)
        hi = sq.astype(jnp.bfloat16)
        total = _dot(hi, ones) + _dot((sq - _f32(hi)).astype(jnp.bfloat16), ones)
    else:
        total = jnp.sum(sq, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(total / D + eps)
    return x * inv, inv


def _fwd_kernel(t, scale, *rest, n, D, eps, tr, rotary):
    *tables, y = rest
    w, w_turned = scale[0:1, :], scale[1:2, :]

    def chunk(r, carry):
        rows = pl.ds(pl.multiple_of(r * tr, tr), tr)
        if rotary:
            cos, sin, half_turn = tables[0][rows, :], tables[1][rows, :], tables[2][...]
        for h in range(n):
            raw = t[rows, h * D:(h + 1) * D]
            xhat, inv = _normalised(raw, eps)
            v = xhat * w
            if rotary:  # roll(x inv w) = roll(x) inv roll(w), to the bit
                v = v * cos + _dot(raw, half_turn) * inv * w_turned * sin
            y[h, rows, :] = v.astype(y.dtype)
        return carry

    jax.lax.fori_loop(0, t.shape[0] // tr, chunk, 0)


def _bwd_kernel(dy, t, scale, *rest, n, D, eps, tr, rotary):
    *tables, dt, dw = rest
    w = scale[0:1, :]

    def chunk(r, sums):
        rows = pl.ds(pl.multiple_of(r * tr, tr), tr)
        if rotary:
            cos, sin, half_turn = tables[0][rows, :], tables[1][rows, :], tables[2][...]
        for h in range(n):
            lanes = slice(h * D, (h + 1) * D)
            xhat, inv = _normalised(t[rows, lanes], eps)
            raw = dy[h, rows, :]
            g = _f32(raw)
            if rotary:  # the transpose: roll(g sin) = roll(g) roll(sin) = -roll(g) sin
                g = g * cos - _dot(raw, half_turn) * sin
            own = g * xhat
            # 8 sublanes of partial sums: whole registers added
            sums = sums + sum(own[i:i + SUBLANES] for i in range(0, tr, SUBLANES))
            g = g * w
            dt[rows, lanes] = (inv * (g - xhat * jnp.mean(
                g * xhat, axis=-1, keepdims=True))).astype(dt.dtype)
        return sums

    dw[...] = jax.lax.fori_loop(
        0, t.shape[0] // tr, chunk, jnp.zeros((SUBLANES, D), jnp.float32))


def _plan(t, n: int, rotary: bool, block):
    S, D = t.shape[-2], t.shape[-1] // n
    blk = block or row_block(S, n, D, t.dtype, rotary)
    x = t.reshape(-1, S, n * D)
    N = x.shape[0]
    # the sequences of a row block innermost: its tables are fetched once
    specs = dict(
        rows=pl.BlockSpec((None, blk, n * D), lambda s, b: (b, s, 0)),
        heads=pl.BlockSpec((None, n, blk, D), lambda s, b: (b, 0, s, 0)),
        scale=pl.BlockSpec((2, D), lambda s, b: (0, 0)),
        turn=pl.BlockSpec((D, D), lambda s, b: (0, 0)),
        table=pl.BlockSpec((blk, D), lambda s, b: (s, 0)),
        sums=pl.BlockSpec((SUBLANES, D), lambda s, b: (s * N + b, 0)),
    )
    return S, D, blk, x, (S // blk, N), specs


def _params(blk, n, D, dtype, rotary, backward):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * 2,
        vmem_limit_bytes=_block_bytes(blk, n, D, dtype, rotary, backward) + _VMEM_SLACK)


def _scale_rows(scale):
    """``[scale; scale with its halves swapped]`` float32 ``[2, D]``."""
    w = _f32(scale).reshape(1, -1)
    return jnp.concatenate([w, jnp.roll(w, w.shape[1] // 2, axis=1)])


def _half_turn(D, dtype):
    """``x @ this`` is ``x`` with its halves swapped: a permutation, exact."""
    return jnp.roll(jnp.eye(D, dtype=dtype), D // 2, axis=1)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "block", "interpret"))
def forward(t, scale, cos=None, sin=None, *, heads: int, eps: float,
            block: int | None = None, interpret: bool = False):
    """``[..., n, S, D]`` in ``t``'s dtype from ``t [..., S, n D]``: every
    head of ``D`` normalised by its own root mean square times ``scale [D]``,
    then, where the float32 tables ``cos`` and sign-folded ``sin`` ``[S, D]``
    come with the call, rotated by them; ``block``: the row block
    (:func:`row_block` unless given: the tests)."""
    rotary = cos is not None
    S, D, blk, x, grid, specs = _plan(t, heads, rotary, block)
    tables = (_f32(cos), _f32(sin), _half_turn(D, t.dtype)) if rotary else ()
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, n=heads, D=D, eps=eps, rotary=rotary,
                          tr=row_chunk(blk, t.dtype)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], heads, S, D), t.dtype),
        grid=grid,
        in_specs=[specs["rows"], specs["scale"]]
        + ([specs["table"]] * 2 + [specs["turn"]] if rotary else []),
        out_specs=specs["heads"],
        compiler_params=_params(blk, heads, D, t.dtype, rotary, False),
        interpret=interpret,
        name=f"{NAME}_fwd",
    )(x, _scale_rows(scale), *tables)
    return y.reshape(*t.shape[:-2], heads, S, D)


@functools.partial(jax.jit, static_argnames=("heads", "eps", "block", "interpret"))
def backward(t, scale, dy, cos=None, sin=None, *, heads: int, eps: float,
             block: int | None = None, interpret: bool = False):
    """``(dt [..., S, n D] in t's dtype, dscale [D] float32)`` for the
    cotangent ``dy [..., n, S, D]`` of :func:`forward`."""
    rotary = cos is not None
    S, D, blk, x, grid, specs = _plan(t, heads, rotary, block)
    tables = (_f32(cos), _f32(sin), _half_turn(D, t.dtype)) if rotary else ()
    steps = grid[0] * grid[1]
    dt, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, n=heads, D=D, eps=eps, rotary=rotary,
                          tr=row_chunk(blk, t.dtype)),
        out_shape=[jax.ShapeDtypeStruct(x.shape, t.dtype),
                   jax.ShapeDtypeStruct((steps * SUBLANES, D), jnp.float32)],
        grid=grid,
        in_specs=[specs["heads"], specs["rows"], specs["scale"]]
        + ([specs["table"]] * 2 + [specs["turn"]] if rotary else []),
        out_specs=[specs["rows"], specs["sums"]],
        compiler_params=_params(blk, heads, D, t.dtype, rotary, True),
        interpret=interpret,
        name=f"{NAME}_bwd",
    )(dy.reshape(-1, heads, S, D), x, _scale_rows(scale), *tables)
    return dt.reshape(t.shape), dw.sum(axis=0)
