"""The row movers of the held mixtures (``ops/pallas/moe_rows.py``), run by
the interpreter: ``take`` against ``x[tok]`` and ``combine`` against the
masked ``[T, k, d]`` sum, at the buffer's extremes, with NaN wherever
nothing may read; the gradient of ``sorted_experts(held=)`` through them
against a dense masked loop; what ``held=None`` traces; what
``kernel.select`` / ``kernel.fallback`` say; and that nothing names a knob."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distribuuuu_tpu.ops import moe as moe_ops
from distribuuuu_tpu.ops import pallas as tier
from distribuuuu_tpu.ops.pallas import moe_gmm, moe_rows

TM = 128  # the bare calls' row tile: the buffer is a multiple of the lanes
DTYPES = {"float32": (jnp.float32, 1024), "bfloat16": (jnp.bfloat16, 2048)}

# rows an expert holds, of T * k = 1024: the buffer's two extremes, ragged
# groups whose pad rows close live tiles (one longer than a tile and than a
# chunk of the combine), and an expert with no row
GROUPS = {
    "no_row_on_a_held_expert": [0, 0, 0, 0],
    "every_row_on_them": [256, 256, 256, 256],
    "ragged_groups": [5, 300, 129, 70],
    "an_expert_with_no_row": [40, 0, 7, 17],
}


def _layout(sizes, tokens=512, k=2, seed=0):
    """A routing with ``sizes[e]`` (token, slot) choices on held expert ``e``
    of 4 of 8, the rest on the other four, as ``moe_ops._sorted_layout``
    lays it out: every group a shifted copy of its sorted run, checked here
    against the gather that says the same."""
    rng = np.random.default_rng(seed)
    flat = np.concatenate([np.full(n, e) for e, n in enumerate(sizes)]
                          + [rng.integers(4, 8, tokens * k - sum(sizes))])
    indices = jnp.asarray(rng.permutation(flat).reshape(tokens, k), jnp.int32)
    weights = jax.random.uniform(jax.random.key(seed), (tokens, k))
    lay = moe_ops._sorted_layout(indices, 4, 0, 8, TM, weights)
    assert lay.tm == TM and lay.src.shape[0] == (tokens * k // TM + 4) * TM
    # a shifted copy a group is the gather it replaced
    src = np.asarray(lay.src)
    real = src < tokens * k
    np.testing.assert_array_equal(
        np.asarray(indices).reshape(-1)[src[real]],
        np.repeat(np.arange(4), sizes))
    np.testing.assert_array_equal(np.asarray(lay.dst)[src[real]], np.nonzero(real)[0])
    np.testing.assert_array_equal(
        np.asarray(lay.scale), np.where(real, np.asarray(weights).reshape(-1)[
            np.minimum(src, tokens * k - 1)], 0))
    return indices, lay, weights


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("groups", list(GROUPS))
def test_take_is_the_gather_of_the_live_tiles(groups, dtype):
    """``rows[r] = scale[r] * x[tok[r]]`` and the row dots, on the live
    tiles; pad rows zeros; the dead tiles of every input NaN."""
    dtype, d = DTYPES[dtype]
    indices, lay, _ = _layout(GROUPS[groups])
    (T, k), height, live = indices.shape, lay.src.shape[0], int(lay.n_live[0]) * TM
    keys = jax.random.split(jax.random.key(1), 3)
    x = jax.random.normal(keys[0], (T, d), dtype)
    dead = (jnp.arange(height) >= live)
    other = jnp.where(
        dead[:, None], jnp.nan, jax.random.normal(keys[1], (height, d), dtype))
    scale = jnp.where(dead, jnp.nan, jax.random.uniform(keys[2], (height,)))
    tok = lay.src.reshape(-1, TM) // k
    real = np.asarray(lay.src < T * k)
    assert real[:live].sum() == sum(GROUPS[groups]) and not real[live:].any()
    packed = moe_rows.pack(x, tm=moe_rows.TOKEN_TILE, interpret=True)
    rows_of = np.asarray(x, np.float32)[np.minimum(tok.reshape(-1), T - 1)]
    want = np.where(real[:, None], rows_of, 0)

    plain = moe_rows._take(
        packed, tok, lay.n_live, tokens=T, d=d, dtype=dtype, interpret=True)
    assert plain.dtype == dtype and plain.shape == (height, d)
    np.testing.assert_array_equal(np.asarray(plain, np.float32)[:live], want[:live])

    rows, dots = moe_rows._take(
        packed, tok, lay.n_live, tokens=T, d=d, dtype=dtype,
        scale=scale.reshape(-1, TM), other=other, interpret=True)
    scaled = (want[:live] * np.asarray(scale)[:live, None])
    np.testing.assert_array_equal(
        np.asarray(rows, np.float32)[:live],
        np.asarray(jnp.asarray(scaled).astype(dtype), np.float32))
    want_dots = (want[:live] * np.asarray(other, np.float32)[:live]).sum(-1)
    np.testing.assert_allclose(dots.reshape(-1)[:live], want_dots, rtol=1e-5, atol=1e-4)
    assert np.isfinite(np.asarray(rows, np.float32)[:live]).all()


@pytest.mark.parametrize("dtype", list(DTYPES))
@pytest.mark.parametrize("groups", list(GROUPS))
def test_combine_is_the_masked_sum_over_the_present_slots(groups, dtype):
    """``out[t] = sum_slot w[t, slot] * y[dst[t, slot]]`` in float32 over
    the present slots; the buffer's dead tiles and pad rows NaN, an absent
    slot's weight NaN going in (the call zeroes it), the result finite."""
    dtype, d = DTYPES[dtype]
    indices, lay, weights = _layout(GROUPS[groups], seed=1)
    (T, k), height = indices.shape, lay.src.shape[0]
    keys = jax.random.split(jax.random.key(2), 2)
    real = lay.src < T * k
    y = jnp.where(
        real[:, None], jax.random.normal(keys[0], (height, d), dtype), jnp.nan)
    present = np.asarray(lay.present).reshape(T, k)
    w = jnp.where(present, weights, jnp.nan)
    dst = lay.dst.reshape(T, k)
    got = moe_rows.combine(y, w, *moe_ops._mover_tables(lay, k), True)
    assert got.dtype == dtype and got.shape == (T, d)
    rows = np.asarray(y, np.float32)[np.minimum(dst, height - 1)]
    want = np.where(present[..., None], rows * np.asarray(w)[..., None], 0).sum(1)
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-2 if dtype == jnp.bfloat16 else 1e-6,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-6)
    if not sum(GROUPS[groups]):
        assert not np.abs(got).max()


def test_pack_reads_and_writes_the_live_tiles_alone():
    """A packed row is the row's 32-bit words in order, a bf16 word column
    ``j`` under column ``j + d / 2``; past ``n_live`` nothing is written."""
    x = jax.random.normal(jax.random.key(0), (64, 2048), jnp.bfloat16)
    TM = 16
    words = np.asarray(moe_rows.pack(x, tm=TM, interpret=True)).reshape(64, 1024)
    bits = np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint16)).astype(np.uint32)
    np.testing.assert_array_equal(words, bits[:, :1024] | (bits[:, 1024:] << 16))
    some = np.asarray(moe_rows.pack(
        x, jnp.asarray([2], jnp.int32), tm=TM, interpret=True)).reshape(64, 1024)
    np.testing.assert_array_equal(some[:2 * TM], words[:2 * TM])
    f32 = jax.random.normal(jax.random.key(1), (32, 1024), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(moe_rows.pack(f32, tm=TM, interpret=True)).reshape(32, 1024),
        np.asarray(jax.lax.bitcast_convert_type(f32, jnp.uint32)))
    assert moe_rows.sublanes_a_row(2048, jnp.bfloat16) == 8
    assert moe_rows.sublanes_a_row(2048, jnp.float32) == 16
    assert moe_rows.sublanes_a_row(128, jnp.float32) is None
    assert moe_rows.sublanes_a_row(2048, jnp.float16) is None
    assert "token block" in moe_rows.unsupported(100, 2048, jnp.bfloat16)
    assert moe_rows.unsupported(8192, 2048, jnp.bfloat16) == ""


def _held(T=1024, k=2, d=1024, f=128, held=2, total=8, first=2, where=(0, 8)):
    keys = jax.random.split(jax.random.key(3), 6)
    params = {
        "w_gate": 0.05 * jax.random.normal(keys[0], (held, d, f)),
        "w_up": 0.05 * jax.random.normal(keys[1], (held, d, f)),
        "w_down": 0.05 * jax.random.normal(keys[2], (held, f, d)),
    }
    x = jax.random.normal(keys[3], (T, d))
    weights = jax.random.uniform(keys[4], (T, k))
    indices = jax.random.randint(keys[5], (T, k), *where, jnp.int32)
    return params, x, weights, indices, (first, total)


def _dense_part(params, x, weights, indices, first):
    """``tests/test_glm_moe.py``'s: a loop over the held experts, each on
    every token, masked."""
    out = jnp.zeros_like(x)
    for j in range(params["w_gate"].shape[0]):
        y = (jax.nn.silu(x @ params["w_gate"][j]) * (x @ params["w_up"][j])
             ) @ params["w_down"][j]
        out = out + y * jnp.where(indices == first + j, weights, 0).sum(-1)[:, None]
    return out


def _walk(jaxpr):
    """Every equation at the XLA level: not inside a kernel."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _walk(sub)


@pytest.mark.parametrize("where", ["none", "all", "mixed"])
def test_gradient_of_a_held_share_through_the_movers_equals_a_dense_loop(where):
    """x, the routing weights and the three expert tensors, through take,
    the six grouped matmuls and combine and back, at a row tile of 256."""
    lo, hi = {"none": (4, 8), "all": (2, 4), "mixed": (0, 8)}[where]
    params, x, weights, indices, held = _held(where=(lo, hi))
    d = x.shape[1]

    def part(params, x, weights):
        return moe_ops.sorted_experts(
            params, x, weights, indices, held=held, interpret=True)

    def total_of(fn):
        return lambda *a: jnp.sum(fn(*a) * jnp.cos(jnp.arange(d)))

    dense = lambda p, x, w: _dense_part(p, x, w, indices, held[0])  # noqa: E731
    np.testing.assert_allclose(
        jax.jit(part)(params, x, weights), dense(params, x, weights), atol=2e-5)
    grads = jax.jit(jax.grad(total_of(part), argnums=(0, 1, 2)))(params, x, weights)
    wants = jax.grad(total_of(dense), argnums=(0, 1, 2))(params, x, weights)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(wants), strict=True):
        assert a.dtype == b.dtype and bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(a, b, atol=2e-6 * max(float(jnp.abs(b).max()), 1.0))
    if where == "none":
        assert not any(float(jnp.abs(g).max()) for g in jax.tree.leaves(grads))


def test_a_held_share_moves_rows_through_the_movers_alone():
    """Forward and backward of a held share: the six grouped matmuls, two
    takes, two combines, a pack before each, and no gather, product or sum
    over a ``[T * k, d]`` or ``[T, k, d]`` array outside them; no loop at
    the XLA level."""
    params, x, weights, indices, held = _held()
    (T, k), d = indices.shape, x.shape[1]

    def loss(params, x, weights):
        return moe_ops.sorted_experts(
            params, x, weights, indices, held=held, interpret=True).sum()

    eqns = list(_walk(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(
        params, x, weights).jaxpr))
    names = sorted(e.params["name"] for e in eqns if e.primitive.name == "pallas_call")
    movers = [n for n in names if n.startswith(moe_rows.NAME)]
    assert movers == ["dtpu_moe_rows_combine"] * 2 + ["dtpu_moe_rows_pack"] * 4 + [
        "dtpu_moe_rows_take"] * 2
    assert len([n for n in names if n.startswith(moe_gmm.NAME)]) == moe_gmm.CALLS_A_STEP
    assert not [e for e in eqns if e.primitive.name in ("while", "cond", "scan")]
    height = (T * k // moe_gmm.ROW_TILE + held[1] // 4) * moe_gmm.ROW_TILE
    for eqn in eqns:
        if eqn.primitive.name in ("pallas_call", "custom_vjp_call", "pjit", "jit",
                                  "custom_vjp_call_jaxpr"):
            continue  # a kernel, or a wrapper around one: its inside is walked
        for var in eqn.outvars:
            shape = tuple(getattr(var.aval, "shape", ()))
            assert shape not in ((height, d), (T * k, d), (T, k, d)), (
                eqn.primitive.name, shape)


def test_with_every_expert_held_the_program_is_the_one_it_was():
    """``held=None`` (OLMoE's cell) traces no mover: the gathers, the
    product and the sum as before, under the kernels too."""
    params, x, weights, indices, _ = _held(held=8, first=0)
    for interpret in (None, True):
        text = str(jax.make_jaxpr(lambda: moe_ops.sorted_experts(
            params, x, weights, indices, interpret=interpret))())
        assert moe_rows.NAME not in text
        assert text.count("gather") >= 2
        same = str(jax.make_jaxpr(lambda: moe_ops.sorted_experts(
            params, x, weights, indices, held=(0, 8), interpret=interpret))())
        assert same == text


def _records(path, kind):
    from distribuuuu_tpu.telemetry import schema

    records = [json.loads(line) for line in open(path)]
    for record in records:
        if record.get("kind", "").startswith("kernel."):
            schema.validate_record(record)
    return [r for r in records if r.get("kind") == kind and r["op"] == "moe_rows"]


def test_select_and_fallback_say_which_arm_moved_the_rows_and_why(tmp_path):
    from distribuuuu_tpu.telemetry import spans

    params, x, weights, indices, held = _held()
    every = {n: jnp.tile(w, (4, 1, 1)) for n, w in params.items()}

    def trace(params, x, held, interpret):
        n = x.shape[0]
        jax.eval_shape(lambda: moe_ops.sorted_experts(
            params, x, jnp.resize(weights, (n, 2)), jnp.resize(indices, (n, 2)),
            held=held, interpret=interpret))

    tier.reset_selection()
    path = spans.setup_telemetry(str(tmp_path), rank=0)
    try:
        trace(params, x, held, None)           # the CPU: no tile table
        trace(params, x, held, True)           # forced, a share: the movers
        trace(every, x, None, True)            # forced, every expert held
        trace(*_held(d=128)[:2], held, True)   # forced, a width with no tile
        trace(*_held(T=1100)[:2], held, True)  # forced, tokens off the tile
    finally:
        spans.close_telemetry()
        tier.reset_selection()
    selected = _records(path, "kernel.select")
    assert [(r["impl"], r["requested"]) for r in selected] == [
        ("xla", "auto"), ("pallas", "pallas"), ("xla", "pallas")]
    T, k = indices.shape
    assert selected[1]["tm"] == moe_gmm.ROW_TILE and selected[1]["rows_bound"] == T * k
    assert (selected[1]["experts_held"], selected[1]["experts_total"]) == (2, 8)
    assert "tm" not in selected[0] and "tm" not in selected[2]
    reasons = [r["reason"] for r in _records(path, "kernel.fallback")]
    assert len(reasons) == 4
    assert "no tile table" in reasons[0]
    assert "every expert is held" in reasons[1]
    assert "(8, 128) tiles" in reasons[2]
    assert "token block" in reasons[3]


def test_the_movers_have_no_knob():
    from distribuuuu_tpu.config import cfg

    assert "moe_rows" in tier.KNOBLESS and "moe_rows" not in tier.KNOBS
    assert not [key for key in cfg.KERNELS if "ROWS" in key or "MOVER" in key]
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for name in ("ops/moe.py", "ops/pallas/moe_rows.py"):
        text = open(os.path.join(here, "distribuuuu_tpu", name)).read()
        assert "environ" not in text and "cfg." not in text, name
