"""Mamba-2's chunked scan as ONE call each way (``ops/ssd.py`` has the
mathematics, chooses the path, and its ``jax.numpy`` body is the reference).

XLA compiled that body so that, a Mamba-2 layer, the ``[chunks, H, L, L]``
float32 masked-decay matrices (twice), the chunks' ``[chunks, H, P, N]``
float32 states through six levels of ``associative_scan`` and float32 copies
between fusions all went through HBM, forward, again and transposed: 5.9 % of
the HBM's bandwidth on the bytes ONE pass must move (PERF.md section 6, PR
53). Two calls, named ``dtpu_ssd_*`` (``telemetry/schema.KERNEL_NAMES``),
over a grid of (sequence, chunk) with the chunk axis sequential:

* ``_fwd``: ``x [B, S, H P], dt^T [B, H, S], a [H, 1], b, c [B, S, G N], d``
  (a lane a column) ``-> y [B, S, H P]`` float32, the last state ``[B, N, H
  P]`` float32 and, where the backward will want them, the state ENTERING
  every chunk ``[B, chunks, N, H P]`` float32.
* ``_bwd``: those, ``dy`` and the last state's cotangent ``-> dx, ddt^T, db,
  dc`` and, summed over the chunks in the call, ``[B, H, L]`` partial sums of
  ``da`` and ``[B, 8, H P]`` of ``dd``; the chunks in REVERSE, the state's
  cotangent carried as the state is forward.

What never reaches HBM, and how the rest keeps the units busy:

* **The state lives in VMEM across the chunk axis**, transposed and lane
  dense: ``S^T [N, H P]`` float32 (the forward's is its ``last`` output block,
  which stays put while a sequence's chunks pass; the backward's cotangent a
  scratch). Every head's part of a product with it is then ONE matmul a lane
  tile with no transpose: ``C S^T``, ``B^T (x w)`` (a transposed-LHS
  contraction), ``B dS^T``.
* **The ``[L, L]`` pieces are built a head at a time in registers**: the
  group's ``C B^T`` once, and a head ``exp`` of the MASKED difference of the
  running sums (never the mask of an overflowed exp), times the scores and
  the step, rounded to the operands' dtype where ``ops/ssd.py`` rounds, into
  the MXU. The backward builds them again.
* **Heads of 64 share a 128-lane tile**: a head's matmul runs on the whole
  tile (the MXU is 128 wide whatever the head) and a lane select keeps each
  head's half, so no operand is ever shifted across lanes.
* **Per-head scalars live in both forms**: a ROW ``[H, L]`` (positions on the
  lanes: what a column of an ``[L, L]`` piece is scaled by, cheap to
  broadcast down the sublanes) and, through ONE 128 x 128 transpose a chunk
  of all of them stacked, a COLUMN ``[L, .]`` (what a row is scaled by: a
  lane broadcast). ``dt`` comes in transposed (XLA's transpose of ``[S, H]``
  floats) and ``ddt`` goes out so.
* **The running sum of ``dt A`` is a product with a triangle of ones**, and
  its gradient's reverse running sum another: three bfloat16 parts add up to
  a float32 exactly and a 0/1 weight is exact in bfloat16, so three one-pass
  products of 16 rows give float32's own rounding (the full-precision float32
  form latches six float32 weight tiles for them). The MXU is otherwise idle
  there.
* **The backward's sums over a head's width** (``sum_p dy (e C S)``, ``sum_p
  x d(x w)``, ``sum dS S``: what the decays' gradients need) **are lane sums
  of the tile, a head's ``[L, 1]`` onto lane ``h`` of one gathering tile,
  transposed to row form once a chunk**; a column sum of an ``[L, L]`` piece
  is vector adds. (As float32 products with a 0/1 matrix on the MXU they
  latched 3,100 weight registers a chunk for 16 rows each.) What the running
  sum is owed as the LATER index of a decay is a row sum of the SAME float32
  products whose column sum it cancels against (as the earlier index):
  ``sum_p dy (M x)`` in its place, with ``M`` rounded for the MXU, left ``da``
  60 x the body's distance from float64.
* ``db`` and ``dc`` sum a group's heads INSIDE the step (all of them are in
  the block); ``da`` and ``dd`` accumulate in blocks that stay put.

float32 everywhere but the MXU's operands (a v5e's VPU has no bfloat16),
which are ``x``'s dtype as ``ops/ssd.py``'s are; float32 operands take the
full-precision passes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distribuuuu_tpu.ops.pallas.moe_gmm import _dot  # a · b over (dim, dim), f32

NAME = "dtpu_ssd"
LANES = 128
SUBLANES = 8  # rows of a float32 register: what a partial sum of dd holds
# A v5e call gets 16 MiB of its 128 MiB of VMEM unless it asks for more; a
# quarter of it bounds the blocks (the cell's are 5 MiB; the whole mixer's 128
# heads, 39, would also unroll 128 head bodies into one call)
_VMEM_BUDGET = 32 * 1024 * 1024
_VMEM_SLACK = 8 * 1024 * 1024  # Mosaic's own scratch: the [L, L] pieces it spills


def _block_bytes(H: int, P: int, G: int, N: int, L: int, dtype, backward: bool) -> int:
    """The double-buffered blocks and the scratch of one call, in bytes."""
    wide, item = H * P, jnp.dtype(dtype).itemsize
    rows = L * (wide * item + 2 * G * N * item) + H * L * 4  # x, b, c, dt^T
    state = N * wide * 4
    if backward:
        blocks = 2 * rows + L * wide * 4 + 2 * state + (H * L + SUBLANES * wide) * 4
        return 2 * blocks + state + H * L * 4
    return 2 * (rows + L * wide * 4 + 2 * state)


def unsupported(chunk: int, heads: int, groups: int, state: int, width: int,
                dtype) -> str:
    """Why the calls cannot run chunks of ``chunk`` positions of ``heads``
    heads ``width`` wide on ``groups`` groups of a ``state``, or ``""``."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.bfloat16, jnp.float32):  # what the MXU's steps take
        return f"{dtype.name}: neither bfloat16 nor float32"
    if chunk != LANES:
        return f"chunks of {chunk}: not the {LANES} lanes a row of decays fills"
    if state % LANES:
        return f"a state of {state}: no multiple of the {LANES} lanes"
    if LANES % width:
        return f"heads {width} wide: no divisor of the {LANES} lanes"
    if (heads // groups * width) % LANES:
        return (f"{heads // groups} heads of {width} a group: no whole "
                f"{LANES}-lane tiles")
    if heads % SUBLANES or heads > LANES:
        return (f"{heads} heads: no multiple of the {SUBLANES} sublanes up to the "
                f"{LANES} lanes (a head a lane where their sums are gathered)")
    if _block_bytes(heads, width, groups, state, chunk, dtype, True) > _VMEM_BUDGET:
        return (f"{heads} heads of {width} on a state of {state}: the blocks "
                "of a chunk pass the VMEM's budget")
    return ""


def _f32(t):
    return t.astype(jnp.float32)


def _dot32(a, b, contract):
    """A float32 product at full precision: the MXU's multi-pass form (the
    tests' float32 scans)."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        preferred_element_type=jnp.float32, precision=jax.lax.Precision.HIGHEST)


def _ones_product(rows, ones):
    """``rows [H, L]`` float32 times a 0/1 matrix ``[L, L]``, to float32's
    own rounding in ONE pass a part: three bfloat16 parts add up to a
    float32 exactly and a 0/1 weight is exact in bfloat16, where the
    full-precision float32 form latches six float32 weight tiles for 16 rows."""
    bf16, total, rest = jnp.bfloat16, None, rows
    ones = ones.astype(bf16)
    for _ in range(3):
        part = rest.astype(bf16)
        rest = rest - _f32(part)
        term = _dot(part, ones, (1, 0))
        total = term if total is None else total + term
    return total


def _mxu(a, b, contract):
    """``a . b`` with operands in the scan's dtype and float32 sums."""
    return _dot32(a, b, contract) if a.dtype == jnp.float32 else _dot(a, b, contract)


def _columns(rows):
    """``[H, L]`` float32 row forms -> ``[L, 128 k]``: quantity ``q``'s head
    ``h`` is column ``q H + h``. Stacked, padded to whole 128-row slabs, and
    a slab a transpose."""
    L = rows[0].shape[1]
    held = sum(r.shape[0] for r in rows)
    pad = -held % LANES
    stacked = jnp.concatenate(
        list(rows) + ([jnp.zeros((pad, L), jnp.float32)] if pad else []), axis=0)
    slabs = [stacked[i:i + LANES].T for i in range(0, held + pad, LANES)]
    return slabs[0] if len(slabs) == 1 else jnp.concatenate(slabs, axis=1)


class _Chunk:
    """What both kernels make of a chunk's ``dt^T [H, L]`` and ``a [H, 1]``:
    the per-head scalars in row form, their columns, and the masks."""

    def __init__(self, dt_rows, a, H, P):
        L = dt_rows.shape[1]
        self.H, self.P, self.L = H, P, L
        self.per_tile = LANES // P  # heads a 128-lane tile holds
        row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
        self.later = row >= col  # [l, s]: position l sees s
        self.dt = dt_rows
        # the running sum of dt A inside the chunk: a product with ones on
        # and above the diagonal
        self.cum = _ones_product(dt_rows * a, row <= col)
        self.to_last = jnp.exp(self.cum[:, L - 1:L] - self.cum)  # to the chunk's end
        self.to_end = self.to_last * dt_rows
        self.cols = _columns([jnp.exp(self.cum), self.cum, self.to_end])
        self.lane = jax.lax.broadcasted_iota(jnp.int32, (L, LANES), 1)

    def heads_of(self, tile):
        """The heads whose lanes 128-lane tile ``tile`` of ``[., H P]``
        holds."""
        return [tile * self.per_tile + i for i in range(self.per_tile)]

    def by_head(self, parts):
        """One ``[L, 128]`` tile from a part a head: each head's lanes from
        its own part."""
        out = parts[0]
        for i in range(1, len(parts)):
            out = jnp.where(self.lane >= i * self.P, parts[i], out)
        return out

    def own_lanes(self, i, tile_value):
        """``tile_value`` with every lane that is not the tile's ``i``-th
        head's zeroed."""
        if self.per_tile == 1:
            return tile_value
        lane = self.lane[:tile_value.shape[0]]
        own = (lane >= i * self.P) & (lane < (i + 1) * self.P)
        return jnp.where(own, tile_value, 0.0)

    def spread(self, quantity, heads):
        """``[L, 128]``: column form of row quantity ``quantity`` (0 the exp
        of the running sum, 1 the running sum, 2 the decay to the end times
        the step), a head's value on each of its lanes."""
        first = quantity * self.H
        return self.by_head([
            jnp.broadcast_to(self.cols[:, first + h:first + h + 1], (self.L, LANES))
            for h in heads])

    def gathered(self, into, i, h, products, plus=0.0):
        """``into [., 128]`` with lane ``h`` holding the sum of ``products
        [., 128]`` over the lanes of the tile's ``i``-th head (``plus`` a
        column of the same height)."""
        total = jnp.sum(self.own_lanes(i, products), axis=1, keepdims=True) + plus
        return jnp.where(self.lane[:into.shape[0]] == h, total, into)

    def decay(self, h):
        """``exp(cum_l - cum_s)`` for ``s <= l``, 0 above: the exp of the
        masked difference."""
        diff = self.cols[:, self.H + h:self.H + h + 1] - self.cum[h:h + 1, :]
        return jnp.exp(jnp.where(self.later, diff, -jnp.inf))


def _tiles(H, P, G):
    """``(group, tile, lanes)`` of every 128-lane tile of ``[., H P]``."""
    a_group = H // G * P // LANES
    return [(t // a_group, t, slice(t * LANES, (t + 1) * LANES))
            for t in range(H * P // LANES)]


def _fwd_kernel(x, dt_rows, a, b, c, skip, y, state, *entering, H, P, G, N):
    dtype = x.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    if entering:
        entering[0][...] = state[...]
    k = _Chunk(dt_rows[...], a[...], H, P)
    scores = {}
    for g, t, lanes in _tiles(H, P, G):
        b_g, c_g = b[:, g * N:(g + 1) * N], c[:, g * N:(g + 1) * N]
        if g not in scores:  # once for the heads that share it
            scores[g] = _mxu(c_g, b_g, (1, 1))
        heads = k.heads_of(t)
        x_t = x[:, lanes]
        intra = k.by_head([
            _mxu((scores[g] * k.decay(h) * k.dt[h:h + 1, :]).astype(dtype), x_t, (1, 0))
            for h in heads])
        from_start, to_end = k.spread(0, heads), k.spread(2, heads)
        held = state[:, lanes]
        carried = from_start * _mxu(c_g, held.astype(dtype), (1, 0))
        x32 = _f32(x_t)
        y[:, lanes] = intra + carried + skip[:, lanes] * x32
        state[:, lanes] = from_start[k.L - 1:k.L, :] * held + _mxu(
            b_g, (x32 * to_end).astype(dtype), (0, 0))


def _rows8(t):
    """``[8 m, W] -> [8, W]``: whole registers added."""
    return sum(t[i:i + SUBLANES] for i in range(0, t.shape[0], SUBLANES))


def _bwd_kernel(x, dt_rows, a, b, c, skip, entering, dy, dlast,
                dx, ddt_rows, db, dc, da, dd, dstate, direct, *, H, P, G, N):
    dtype = x.dtype

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate[...] = dlast[...]
        da[...] = jnp.zeros_like(da)
        dd[...] = jnp.zeros_like(dd)

    k = _Chunk(dt_rows[...], a[...], H, P)
    L = k.L
    scores, dscores, db_g, dc_g = {}, {}, {}, {}
    # sums over a head's lanes, head h's on lane h: what the running sum is
    # owed a position as the later index of a decay and through the entering
    # state's part of y; sum_p x d(x to_end); sum_{n, p} dS S in 8 partial rows
    d_from_start = d_to_end = jnp.zeros((L, LANES), jnp.float32)
    d_last = jnp.zeros((SUBLANES, LANES), jnp.float32)
    for g, t, lanes in _tiles(H, P, G):
        b_g, c_g = b[:, g * N:(g + 1) * N], c[:, g * N:(g + 1) * N]
        if g not in scores:
            scores[g] = _mxu(c_g, b_g, (1, 1))
            dscores[g] = jnp.zeros((L, L), jnp.float32)
            db_g[g] = dc_g[g] = jnp.zeros((L, N), jnp.float32)
        heads = k.heads_of(t)
        x_t, dy_t = x[:, lanes], dy[:, lanes]
        x32, dy_b = _f32(x_t), dy_t.astype(dtype)
        from_start, to_end = k.spread(0, heads), k.spread(2, heads)
        held, dheld = entering[:, lanes], dstate[:, lanes]
        held_b, dheld_b = held.astype(dtype), dheld.astype(dtype)
        by_dy = dy_t * (from_start * _mxu(c_g, held_b, (1, 0)))
        dweighted = _mxu(b_g, dheld_b, (1, 0))  # the cotangent of x to_end
        by_x = x32 * dweighted
        by_state = _rows8(dheld * held)
        dx_intra = []
        for i, h in enumerate(heads):
            decay = k.decay(h)
            stepped = decay * k.dt[h:h + 1, :]
            mixed = (scores[g] * stepped).astype(dtype)
            dmixed = _mxu(k.own_lanes(i, dy_t).astype(dtype), x_t, (1, 1))  # [l, s]
            dx_intra.append(_mxu(mixed, dy_b, (0, 0)))
            dscores[g] = dscores[g] + dmixed * stepped
            # what the step alone is owed, a column sum; and what the running
            # sum is owed as the LATER index of a decay, a row sum of the same
            # float32 products (as the earlier index it is owed the column
            # sum times the step: the two cancel on the diagonal exactly)
            ddecay = dmixed * scores[g] * decay
            direct[h:h + 1, :] = jnp.sum(ddecay, axis=0, keepdims=True)
            d_from_start = k.gathered(d_from_start, i, h, by_dy, plus=jnp.sum(
                ddecay * k.dt[h:h + 1, :], axis=1, keepdims=True))
            d_to_end = k.gathered(d_to_end, i, h, by_x)
            d_last = k.gathered(d_last, i, h, by_state)
        dcarried = (dy_t * from_start).astype(dtype)
        dc_g[g] = dc_g[g] + _mxu(dcarried, held_b, (1, 1))
        db_g[g] = db_g[g] + _mxu((x32 * to_end).astype(dtype), dheld_b, (1, 1))
        dx[:, lanes] = (k.by_head(dx_intra) + dweighted * to_end
                        + skip[:, lanes] * dy_t).astype(dx.dtype)
        dd[:, lanes] += _rows8(dy_t * x32)
        dstate[:, lanes] = from_start[L - 1:L, :] * dheld + _mxu(c_g, dcarried, (0, 0))
    for g in scores:
        dscores_b = dscores[g].astype(dtype)
        at = slice(g * N, (g + 1) * N)
        dc[:, at] = (dc_g[g] + _mxu(dscores_b, b[:, at], (1, 0))).astype(dc.dtype)
        db[:, at] = (db_g[g] + _mxu(dscores_b, c[:, at], (0, 0))).astype(db.dtype)

    # at the chunk's last position the running sum is also owed what the
    # state's decay is (lanes 0..H-1 of the columns hold its exp, a head a lane)
    last_row = jax.lax.broadcasted_iota(jnp.int32, (L, LANES), 0) == L - 1
    d_from_start = d_from_start + jnp.where(
        last_row, k.cols[L - 1:L, :LANES] * jnp.sum(d_last, axis=0, keepdims=True), 0.0)
    d_from_start, d_to_end = d_from_start.T[:H], d_to_end.T[:H]  # row form [H, L]
    owed = direct[...]
    dstep = owed + d_to_end * k.to_last
    # the running sum's cotangent a position: as the later index of a decay,
    # as the earlier one, and at the last position every decay to the end
    dcum = d_from_start - owed * k.dt - d_to_end * k.to_end
    at_last = jax.lax.broadcasted_iota(jnp.int32, (H, L), 1) == L - 1
    dcum = dcum + jnp.where(at_last, jnp.sum(
        d_to_end * k.to_end, axis=1, keepdims=True), 0.0)
    # the reverse running sum: a product with ones on and below the diagonal
    dstepped = _ones_product(dcum, k.later)
    ddt_rows[...] = dstep + a[...] * dstepped
    da[...] += k.dt * dstepped


def _specs(H, P, G, N, L, chunks, reverse):
    """Block specs by name; ``reverse``: the chunks walked last to first."""
    wide = H * P

    def at(z):
        return chunks - 1 - z if reverse else z

    return dict(
        rows=pl.BlockSpec((None, L, wide), lambda n, z: (n, at(z), 0)),
        steps=pl.BlockSpec((None, H, L), lambda n, z: (n, 0, at(z))),
        groups=pl.BlockSpec((None, L, G * N), lambda n, z: (n, at(z), 0)),
        rate=pl.BlockSpec((H, 1), lambda n, z: (0, 0)),
        skip=pl.BlockSpec((1, wide), lambda n, z: (0, 0)),
        state=pl.BlockSpec((None, N, wide), lambda n, z: (n, 0, 0)),
        entering=pl.BlockSpec((None, None, N, wide), lambda n, z: (n, at(z), 0, 0)),
        sums=pl.BlockSpec((None, H, L), lambda n, z: (n, 0, 0)),
        sums8=pl.BlockSpec((None, SUBLANES, wide), lambda n, z: (n, 0, 0)),
    )


def _params(H, P, G, N, L, dtype, backward):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_block_bytes(H, P, G, N, L, dtype, backward) + _VMEM_SLACK)


def _flat(x, dt, a, b, c, d):
    """The calls' operands from ``ops/ssd.ssd``'s (already padded to whole
    chunks): heads and groups folded into the lanes, ``dt`` transposed, ``a``
    a column and ``d`` a lane a column."""
    B, S, H, P = x.shape
    G, N = b.shape[-2:]
    grouped = (t.astype(x.dtype).reshape(B, S, G * N) for t in (b, c))
    return (x.reshape(B, S, H * P), _f32(dt).transpose(0, 2, 1), _f32(a).reshape(H, 1),
            *grouped, jnp.repeat(_f32(d), P).reshape(1, H * P))


@functools.partial(jax.jit, static_argnames=("chunk", "keep", "interpret"))
def forward(x, dt, a, b, c, d, *, chunk: int, keep: bool = False,
            interpret: bool = False):
    """``(y [B, S, H, P] float32, last state [B, H, P, N] float32, the state
    entering every chunk [B, chunks, N, H P] float32 or None)`` for ``S`` a
    multiple of ``chunk``; ``keep``: whether the backward's residual is
    written."""
    B, S, H, P = x.shape
    G, N = b.shape[-2:]
    chunks = S // chunk
    specs = _specs(H, P, G, N, chunk, chunks, False)
    out_shape = [jax.ShapeDtypeStruct((B, S, H * P), jnp.float32),
                 jax.ShapeDtypeStruct((B, N, H * P), jnp.float32)]
    out_specs = [specs["rows"], specs["state"]]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct((B, chunks, N, H * P), jnp.float32))
        out_specs.append(specs["entering"])
    y, last, *entering = pl.pallas_call(
        functools.partial(_fwd_kernel, H=H, P=P, G=G, N=N),
        out_shape=out_shape,
        grid=(B, chunks),
        in_specs=[specs["rows"], specs["steps"], specs["rate"], specs["groups"],
                  specs["groups"], specs["skip"]],
        out_specs=out_specs,
        compiler_params=_params(H, P, G, N, chunk, x.dtype, False),
        interpret=interpret,
        name=f"{NAME}_fwd",
    )(*_flat(x, dt, a, b, c, d))
    return (y.reshape(B, S, H, P), last.reshape(B, N, H, P).transpose(0, 2, 3, 1),
            entering[0] if keep else None)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def backward(x, dt, a, b, c, d, entering, dy, dlast, *, chunk: int,
             interpret: bool = False):
    """``(dx, ddt, da, db, dc, dd)`` for the cotangents ``dy [B, S, H, P]``
    and ``dlast [B, H, P, N]`` of :func:`forward`'s first two results:
    ``dx``, ``db`` and ``dc`` in ``x``'s dtype, the rest float32."""
    B, S, H, P = x.shape
    G, N = b.shape[-2:]
    chunks = S // chunk
    wide = H * P
    specs = _specs(H, P, G, N, chunk, chunks, True)
    f32 = jnp.float32
    dx, ddt_rows, db, dc, da, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, H=H, P=P, G=G, N=N),
        out_shape=[jax.ShapeDtypeStruct((B, S, wide), x.dtype),
                   jax.ShapeDtypeStruct((B, H, S), f32),
                   jax.ShapeDtypeStruct((B, S, G * N), x.dtype),
                   jax.ShapeDtypeStruct((B, S, G * N), x.dtype),
                   jax.ShapeDtypeStruct((B, H, chunk), f32),
                   jax.ShapeDtypeStruct((B, SUBLANES, wide), f32)],
        grid=(B, chunks),
        in_specs=[specs["rows"], specs["steps"], specs["rate"], specs["groups"],
                  specs["groups"], specs["skip"], specs["entering"], specs["rows"],
                  specs["state"]],
        out_specs=[specs["rows"], specs["steps"], specs["groups"], specs["groups"],
                   specs["sums"], specs["sums8"]],
        scratch_shapes=[pltpu.VMEM((N, wide), f32), pltpu.VMEM((H, chunk), f32)],
        compiler_params=_params(H, P, G, N, chunk, x.dtype, True),
        interpret=interpret,
        name=f"{NAME}_bwd",
    )(*_flat(x, dt, a, b, c, d), entering, _f32(dy).reshape(B, S, wide),
      _f32(dlast).transpose(0, 3, 1, 2).reshape(B, N, wide))
    return (dx.reshape(B, S, H, P), ddt_rows.transpose(0, 2, 1), da.sum(axis=(0, 2)),
            db.reshape(B, S, G, N), dc.reshape(B, S, G, N),
            dd.sum(axis=(0, 1)).reshape(H, P).sum(axis=1))
