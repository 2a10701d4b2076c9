"""The gated short convolution of LFM2's ``conv`` layers as ONE call each
way: gate -> filter -> gate reads its inputs once and writes its outputs
once (``ops/short_conv.py`` has the arithmetic and is the reference).

XLA compiled the ``jax.numpy`` form to six loop fusions, two of which only
WROTE copies of B, C, u and dy shifted by one and by two positions (sublane
shifts it does not fuse into their consumers): 23.8 % of the HBM's bandwidth
on the bytes a perfect fusion moves (PERF.md section 6, PR 44). Two calls,
named ``dtpu_short_conv_*`` (``telemetry/schema.KERNEL_NAMES``):

* ``_fwd``: ``bcu [N, S, 3H], w [H, L] -> y [N, S, H]``.
* ``_bwd``: ``bcu, w, dy -> dbcu [N, S, 3H], dw [H, L]`` float32: the
  convolution again for dC, ``dg`` from ``dy * C`` read AHEAD, ``dB = dg *
  u``, ``du = dg * B``, and the filter's gradient summed in the call.

What keeps the bytes at one pass:

* **``bcu`` goes in whole and ``dbcu`` comes out whole.** A grid step takes
  a block of ``ts`` positions the whole ``3H`` wide (contiguous in HBM) and
  walks it in (``tr`` rows, ``tl`` lanes) chunks small enough for the
  float32 values to stay in registers; B, C and u are lane slices of the one
  block. No split in front of the call and no concatenate behind it.
* **The halo is a second small block on the neighbouring rows**: the last
  packed sublane tile (16 rows of a 16-bit dtype, 8 of float32) of the
  previous block of B and of u, the first of the next block of C and of dy,
  clamped at the sequence's ends and zeroed there. A tap reaches at most one
  float32 register (8 rows) into it, so ``L <= 9``. Inside a block a chunk
  reads its neighbours' rows from the block itself. The shifts are
  ``pltpu.roll`` on the sublanes of (halo rows + chunk), never a shifted copy.
* **``dw`` accumulates in the call**: a float32 block ``[L * 8, H]`` (8
  sublanes of partial sums a tap) that stays in VMEM over the whole grid,
  zeroed at its first step; the 8 sublanes are summed outside, ``L * 8 * H``
  floats. No ``[N, S, H]`` product exists in HBM.
* float32 arithmetic (a v5e's VPU has no bfloat16), one rounding on the
  store.

The sequence block follows the shape (:func:`seq_block`: the tallest of 512,
256, ... that divides ``S`` and whose double-buffered blocks fit the budget)
and the calls ask for the VMEM their blocks need, no more.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NAME = "dtpu_short_conv"
LANES = 128
REACH = 8  # rows of the halo a tap may read: one float32 register
_SEQ_BLOCKS = (512, 256, 128, 64, 32, 16, 8)
_ROW_CHUNK = 32
_LANE_CHUNK = 256
# A v5e call gets 16 MiB of its 128 MiB of VMEM unless it asks for more; the
# backward's double-buffered blocks at 512 x 2048 channels in bf16 are 28
_VMEM_BUDGET = 40 * 1024 * 1024
_VMEM_SLACK = 4 * 1024 * 1024  # Mosaic's own scratch beside the blocks


def halo_rows(dtype) -> int:
    """Rows of one packed sublane tile: the least a block may hold."""
    return REACH * 4 // jnp.dtype(dtype).itemsize


def _block_bytes(ts: int, H: int, taps: int, dtype, backward: bool) -> int:
    """The double-buffered blocks of one call, in bytes."""
    itemsize = jnp.dtype(dtype).itemsize
    widths = 3 * H + 3 * H + H if backward else 3 * H + H
    halos = (4 if backward else 2) * halo_rows(dtype) * H
    filters = (-(-taps // REACH) * REACH + taps * REACH * backward) * H * 4
    return 2 * ((ts * widths + halos) * itemsize + filters)


def seq_block(S: int, H: int, taps: int, dtype) -> int | None:
    """The tallest sequence block that divides ``S``, holds whole packed
    tiles and fits the budget with the backward's blocks, or None."""
    for ts in _SEQ_BLOCKS:
        if (S % ts == 0 and ts % halo_rows(dtype) == 0
                and _block_bytes(ts, H, taps, dtype, True) <= _VMEM_BUDGET):
            return ts
    return None


def unsupported(S: int, H: int, taps: int, dtype) -> str:
    """Why the calls cannot run on ``S`` positions of ``H`` channels under a
    filter of ``taps``, or ``""``."""
    dtype = jnp.dtype(dtype)
    if not (jnp.issubdtype(dtype, jnp.floating) and dtype.itemsize in (2, 4)):
        return f"{dtype.name}: neither a 16- nor a 32-bit float"
    if H % LANES:
        return f"{H} channels: no multiple of the {LANES} lanes"
    if taps - 1 > REACH:
        return (f"a filter of {taps} taps reaches {taps - 1} rows back: past "
                f"the {REACH} of the halo tile")
    if seq_block(S, H, taps, dtype) is None:
        return (f"{S} positions: no multiple of a sequence block "
                f"{_SEQ_BLOCKS} of whole {halo_rows(dtype)}-row tiles")
    return ""


def chunks(ts: int, H: int, dtype) -> tuple[int, int]:
    """(rows, lanes) of a chunk: what the float32 values of one trip of the
    inner loop cover."""
    hr = halo_rows(dtype)
    tr = _ROW_CHUNK if ts % _ROW_CHUNK == 0 and _ROW_CHUNK % hr == 0 else hr
    return tr, _LANE_CHUNK if H % _LANE_CHUNK == 0 else LANES


def _f32(x):
    return x.astype(jnp.float32)


def _neighbour(own, halo, at_edge, rows):
    """8 float32 rows beside a chunk: ``own`` (the packed tile of the block
    itself that holds them) or, for the block's outermost chunk, ``halo``."""
    return jnp.where(at_edge, _f32(halo)[rows], _f32(own)[rows])


def _behind(ext, back: int):
    """``ext`` = [8 rows before the chunk; the chunk]: the chunk read
    ``back`` rows back."""
    return (pltpu.roll(ext, back, 0) if back else ext)[REACH:]


def _ahead(ext, ahead: int):
    """``ext`` = [the chunk; 8 rows after it]: the chunk read ``ahead`` rows
    ahead."""
    rows = ext.shape[0]
    return (pltpu.roll(ext, rows - ahead, 0) if ahead else ext)[:rows - REACH]


def _filtered(shifted, w):
    """``sum_j w[j] * shifted(L - 1 - j)``: tap ``j`` reads ``L - 1 - j``
    rows away (summed in ``ops/short_conv.py``'s order: float32 inputs give
    its bits)."""
    taps = len(w)
    return sum(w[j] * shifted(taps - 1 - j) for j in range(taps))


def _lane_chunks(H: int, tl: int):
    """``(lanes, B's, C's, u's)`` slices a lane chunk: the chunk's lanes of a
    block ``H`` wide, and of the three lane blocks of ``bcu``."""
    for col in range(0, H, tl):
        yield tuple(slice(at + col, at + col + tl) for at in (0, 0, H, 2 * H))


def _g_ext(bcu, b_prev, u_prev, lanes, in_b, in_u, r, r0, tr, first_block):
    """``g = B * u`` of the chunk at rows ``r0`` under the 8 rows before it
    (zeros left of the sequence), float32; and B, u."""
    hr = b_prev.shape[0]
    before = pl.ds(pl.multiple_of(jnp.maximum(r0 - hr, 0), hr), hr)
    last8 = slice(hr - REACH, hr)
    tail = (_neighbour(bcu[before, in_b], b_prev[:, lanes], r == 0, last8)
            * _neighbour(bcu[before, in_u], u_prev[:, lanes], r == 0, last8))
    tail = jnp.where(jnp.logical_and(first_block, r == 0), 0.0, tail)
    b, u = _f32(bcu[pl.ds(r0, tr), in_b]), _f32(bcu[pl.ds(r0, tr), in_u])
    return jnp.concatenate([tail, b * u]), b, u


def _fwd_kernel(bcu, b_prev, u_prev, w, y, *, H, taps, tr, tl):
    first_block = pl.program_id(1) == 0
    for lanes, in_b, in_c, in_u in _lane_chunks(H, tl):
        wt = [w[j:j + 1, lanes] for j in range(taps)]

        def chunk(r, carry, lanes=lanes, in_b=in_b, in_c=in_c, in_u=in_u, wt=wt):
            r0 = pl.multiple_of(r * tr, tr)
            g, _, _ = _g_ext(bcu, b_prev, u_prev, lanes, in_b, in_u, r, r0, tr,
                             first_block)
            conv = _filtered(lambda k: _behind(g, k), wt)
            y[pl.ds(r0, tr), lanes] = (
                _f32(bcu[pl.ds(r0, tr), in_c]) * conv).astype(y.dtype)
            return carry

        jax.lax.fori_loop(0, y.shape[0] // tr, chunk, 0)


def _bwd_kernel(bcu, b_prev, u_prev, c_next, dy, dy_next, w, dbcu, dw, *,
                H, taps, tr, tl):
    ts, hr = dy.shape[0], c_next.shape[0]
    first_block = pl.program_id(1) == 0
    last_block = pl.program_id(1) == pl.num_programs(1) - 1
    chunks = ts // tr

    @pl.when(jnp.logical_and(pl.program_id(0) == 0, first_block))
    def _():
        dw[...] = jnp.zeros_like(dw)

    for lanes, in_b, in_c, in_u in _lane_chunks(H, tl):
        wt = [w[j:j + 1, lanes] for j in range(taps)]

        def chunk(r, sums, lanes=lanes, in_b=in_b, in_c=in_c, in_u=in_u, wt=wt):
            r0 = pl.multiple_of(r * tr, tr)
            rows = pl.ds(r0, tr)
            g, b, u = _g_ext(bcu, b_prev, u_prev, lanes, in_b, in_u, r, r0, tr,
                             first_block)
            d = _f32(dy[rows, lanes])
            own = d * _f32(bcu[rows, in_c])
            # dy * C of the 8 rows after the chunk, zeros right of the sequence
            after = pl.ds(pl.multiple_of(jnp.minimum(r0 + tr, ts - hr), hr), hr)
            at_end, first8 = r == chunks - 1, slice(0, REACH)
            head = (_neighbour(dy[after, lanes], dy_next[:, lanes], at_end, first8)
                    * _neighbour(bcu[after, in_c], c_next[:, lanes], at_end, first8))
            head = jnp.where(jnp.logical_and(last_block, at_end), 0.0, head)
            e = jnp.concatenate([own, head])
            shifted = [_behind(g, k) for k in range(taps)]
            dg = _filtered(lambda k: _ahead(e, k), wt)
            dbcu[rows, in_b] = (dg * u).astype(dbcu.dtype)
            dbcu[rows, in_c] = (
                d * _filtered(shifted.__getitem__, wt)).astype(dbcu.dtype)
            dbcu[rows, in_u] = (dg * b).astype(dbcu.dtype)
            # 8 sublanes of partial sums a tap: whole registers added
            return tuple(
                s + sum((own * shifted[k])[i:i + REACH] for i in range(0, tr, REACH))
                for k, s in enumerate(sums))

        sums = jax.lax.fori_loop(
            0, chunks, chunk, (jnp.zeros((REACH, tl), jnp.float32),) * taps)
        for k, s in enumerate(sums):  # tap j reads k = L - 1 - j rows back
            j = taps - 1 - k
            dw[j * REACH:(j + 1) * REACH, lanes] += s


def _params(ts, H, taps, dtype, backward):
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * 2 if backward else ("parallel",) * 2,
        vmem_limit_bytes=_block_bytes(ts, H, taps, dtype, backward) + _VMEM_SLACK)


def _specs(ts: int, S: int, H: int, hr: int):
    """``block(width)``: ``ts`` positions of an array ``width`` channels
    wide; ``before(part)`` / ``after(part)``: the halo tile before / after it
    in lane block ``part`` of ``bcu`` (B, C, u; 0 of ``dy``), clamped at the
    sequence's ends; ``whole(rows)``: a ``[rows, H]`` array that stays put."""
    per, last = ts // hr, S // hr - 1

    def block(width):
        return pl.BlockSpec((None, ts, width), lambda n, s: (n, s, 0))

    def before(part):
        return pl.BlockSpec(
            (None, hr, H), lambda n, s: (n, jnp.maximum(s * per - 1, 0), part))

    def after(part):
        return pl.BlockSpec(
            (None, hr, H), lambda n, s: (n, jnp.minimum((s + 1) * per, last), part))

    def whole(rows):
        return pl.BlockSpec((rows, H), lambda n, s: (0, 0))

    return block, before, after, whole


def _filter_rows(w):
    """``w [H, L] -> [L padded to whole registers, H]`` float32."""
    return jnp.pad(_f32(w).T, ((0, -w.shape[1] % REACH), (0, 0)))


def _plan(bcu, w, block):
    S, (H, taps) = bcu.shape[-2], w.shape
    ts = block or seq_block(S, H, taps, bcu.dtype)
    return S, H, taps, ts, chunks(ts, H, bcu.dtype), _specs(
        ts, S, H, halo_rows(bcu.dtype))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def forward(bcu, w, *, block: int | None = None, interpret: bool = False):
    """``y = C * sum_j w_j (B * u)_{t-(L-1)+j}`` ``[..., S, H]`` in ``bcu``'s
    dtype from ``bcu [..., S, 3H]`` and ``w [H, L]``; ``block``: the
    sequence block (:func:`seq_block` unless given: the tests)."""
    S, H, taps, ts, (tr, tl), (blocks, before, _, whole) = _plan(bcu, w, block)
    x, wt = bcu.reshape(-1, S, 3 * H), _filter_rows(w)
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, H=H, taps=taps, tr=tr, tl=tl),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], S, H), bcu.dtype),
        grid=(x.shape[0], S // ts),
        in_specs=[blocks(3 * H), before(0), before(2), whole(wt.shape[0])],
        out_specs=blocks(H),
        compiler_params=_params(ts, H, taps, bcu.dtype, False),
        interpret=interpret,
        name=f"{NAME}_fwd",
    )(x, x, x, wt)
    return y.reshape(*bcu.shape[:-1], H)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def backward(bcu, w, dy, *, block: int | None = None, interpret: bool = False):
    """``(dbcu [..., S, 3H] in bcu's dtype, dw [H, L] float32)`` for the
    cotangent ``dy [..., S, H]`` of :func:`forward`."""
    S, H, taps, ts, (tr, tl), (blocks, before, after, whole) = _plan(bcu, w, block)
    x, d, wt = bcu.reshape(-1, S, 3 * H), dy.reshape(-1, S, H), _filter_rows(w)
    dbcu, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, H=H, taps=taps, tr=tr, tl=tl),
        out_shape=[jax.ShapeDtypeStruct(x.shape, bcu.dtype),
                   jax.ShapeDtypeStruct((taps * REACH, H), jnp.float32)],
        grid=(x.shape[0], S // ts),
        in_specs=[blocks(3 * H), before(0), before(2), after(1),
                  blocks(H), after(0), whole(wt.shape[0])],
        out_specs=[blocks(3 * H), whole(taps * REACH)],
        compiler_params=_params(ts, H, taps, bcu.dtype, True),
        interpret=interpret,
        name=f"{NAME}_bwd",
    )(x, x, x, x, d, d, wt)
    return dbcu.reshape(bcu.shape), dw.reshape(taps, REACH, H).sum(axis=1).T
