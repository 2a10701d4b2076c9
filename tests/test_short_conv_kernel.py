"""LFM2's gated short convolution as two Pallas calls
(``ops/pallas/short_conv.py``), run by the interpreter at tiny shapes whose
halo crosses sequence blocks at both ends: forward and backward against the
``jax.numpy`` path (``ops/short_conv.forward_xla`` / ``backward_xla``), the
edges of the sequence and of every block named; the gradient through the
``custom_vjp``; that ``bcu`` goes in whole and ``dbcu`` comes out whole; what
``kernel.select`` / ``kernel.fallback`` say; and that nothing names a knob."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distribuuuu_tpu.ops import pallas as tier
from distribuuuu_tpu.ops import short_conv as op
from distribuuuu_tpu.ops.pallas import short_conv as kernel

H = 256
# dtype -> (S, sequence block): three blocks of one 32-row chunk; four blocks
# of two 8-row chunks (a chunk inside a block reads its neighbour's rows from
# the block, the outermost ones from the halo tiles)
SHAPES = {"bfloat16": (96, 32), "float32": (64, 16)}
# bfloat16: one unit in the last place (both paths round the same float32
# once); float32: the sums' own rounding, where the compiler orders them
CLOSE = {"bfloat16": dict(rtol=2.0 ** -7, atol=1e-30),
         "float32": dict(rtol=1e-5, atol=2e-6)}


def _inputs(dtype, taps, batch, seq, channels=H):
    keys = jax.random.split(jax.random.key(taps * 10 + batch), 3)
    bcu = jax.random.normal(keys[0], (batch, seq, 3 * channels)).astype(dtype)
    w = jax.random.normal(keys[1], (channels, taps)) * 0.5
    dy = jax.random.normal(keys[2], (batch, seq, channels)).astype(dtype)
    return bcu, w, dy


def _edges(seq: int, block: int, taps: int) -> list:
    """The first and last L - 1 positions of the sequence and of every block."""
    reach = taps - 1
    rows = set()
    for start in range(0, seq, block):
        rows.update(range(start, start + reach))
        rows.update(range(start + block - reach, start + block))
    return sorted(rows)


@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("dtype", list(SHAPES))
def test_the_two_calls_are_the_jax_numpy_path(dtype, taps, batch):
    seq, block = SHAPES[dtype]
    bcu, w, dy = _inputs(dtype, taps, batch, seq)
    assert seq // block >= 3  # a first, a middle and a last block
    want_y = op.forward_xla(bcu, w)
    want_dbcu, want_dw = op.backward_xla(bcu, w, dy)
    y = kernel.forward(bcu, w, block=block, interpret=True)
    dbcu, dw = kernel.backward(bcu, w, dy, block=block, interpret=True)
    assert y.dtype == bcu.dtype and dbcu.dtype == bcu.dtype and dw.dtype == jnp.float32
    assert y.shape == want_y.shape and dbcu.shape == bcu.shape and dw.shape == w.shape
    f32 = lambda x: np.asarray(x, np.float32)
    close = CLOSE[dtype]
    edges = _edges(seq, block, taps)
    # where a tap reads across the sequence's ends (zeros) or a block's (the halo)
    np.testing.assert_allclose(
        f32(y)[:, edges], f32(want_y)[:, edges], **close,
        err_msg=f"y at the edges of the sequence and of the blocks: rows {edges}")
    np.testing.assert_allclose(
        f32(dbcu)[:, edges], f32(want_dbcu)[:, edges], **close,
        err_msg=f"dbcu at the edges of the sequence and of the blocks: rows {edges}")
    np.testing.assert_allclose(f32(y), f32(want_y), **close)
    for name, got, want in zip(
            "BCu", np.split(f32(dbcu), 3, -1), np.split(f32(want_dbcu), 3, -1)):
        np.testing.assert_allclose(got, want, **close, err_msg=f"d{name}")
    np.testing.assert_allclose(dw, want_dw, rtol=1e-5, atol=1e-5 * np.abs(want_dw).max())


def test_a_chunk_inside_a_block_and_a_filter_of_nine_taps():
    """bfloat16 blocks of two 32-row chunks (rows 16..31 and 32..47 of a
    block are the neighbours' tiles, not the halo's), and the longest filter
    the halo tile covers: 8 rows of reach, float32 blocks of 8 rows, so every
    row of a block reads the whole tile before it."""
    bcu, w, dy = _inputs("bfloat16", 3, 1, 128)
    assert kernel.chunks(64, H, jnp.bfloat16) == (32, 256)
    y = kernel.forward(bcu, w, block=64, interpret=True)
    dbcu, dw = kernel.backward(bcu, w, dy, block=64, interpret=True)
    want_dbcu, want_dw = op.backward_xla(bcu, w, dy)
    close = CLOSE["bfloat16"]
    np.testing.assert_allclose(*(np.asarray(t, np.float32) for t in (
        y, op.forward_xla(bcu, w))), **close)
    np.testing.assert_allclose(*(np.asarray(t, np.float32) for t in (
        dbcu, want_dbcu)), **close)
    np.testing.assert_allclose(dw, want_dw, rtol=1e-5, atol=1e-5 * np.abs(want_dw).max())
    bcu, w, dy = _inputs("float32", 9, 1, 24, channels=128)
    assert not kernel.unsupported(24, 128, 9, jnp.float32)
    np.testing.assert_allclose(
        kernel.forward(bcu, w, block=8, interpret=True), op.forward_xla(bcu, w),
        rtol=1e-5, atol=1e-5)
    for got, want in zip(kernel.backward(bcu, w, dy, block=8, interpret=True),
                         op.backward_xla(bcu, w, dy)):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_the_gradient_through_the_custom_vjp_is_the_jax_numpy_paths():
    """``gated_short_conv(interpret=True)`` under ``jax.grad``: both calls,
    the block chosen from the shape, leading dims of any rank, the filter's
    gradient in the filter's dtype."""
    bcu, w, dy = _inputs("bfloat16", 3, 2, 64)
    bcu, dy = bcu.reshape(2, 1, 64, 3 * H), dy.reshape(2, 1, 64, H)
    weights = dy.astype(jnp.float32)

    def loss(bcu, w, interpret):
        return (op.gated_short_conv(bcu, w, interpret).astype(jnp.float32) * weights).sum()

    got = jax.grad(loss, argnums=(0, 1))(bcu, w, True)
    want = jax.grad(loss, argnums=(0, 1))(bcu, w, None)
    assert got[0].dtype == jnp.bfloat16 and got[1].dtype == w.dtype
    np.testing.assert_allclose(*(np.asarray(t[0], np.float32) for t in (got, want)),
                               **CLOSE["bfloat16"])
    np.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-5 * np.abs(want[1]).max())


def test_bcu_goes_in_whole_and_dbcu_comes_out_whole():
    """Forward and backward trace to ONE Pallas call each, by name; outside
    them nothing splits ``bcu``, nothing concatenates ``dbcu`` and no value of
    ``[N, S, H]`` or wider exists but the calls' own operands and results."""
    bcu, w, dy = _inputs("bfloat16", 3, 2, 64)
    jaxpr = jax.make_jaxpr(lambda bcu, w: jax.vjp(
        lambda bcu, w: op.gated_short_conv(bcu, w, True), bcu, w)[1](dy))(bcu, w)
    names, others = [], []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
                continue
            inner = [v for v in eqn.params.values() if hasattr(v, "eqns")
                     or hasattr(v, "jaxpr")]
            for sub in inner:
                walk(getattr(sub, "jaxpr", sub))
            if not inner:
                others.append(eqn)

    walk(jaxpr.jaxpr)
    assert names == [f"{kernel.NAME}_fwd", f"{kernel.NAME}_bwd"]
    big = [str(e) for e in others
           if any(np.prod(v.aval.shape) >= 2 * 64 * H for v in e.outvars)
           and e.primitive.name != "reshape"]
    assert not big, big
    assert not [e for e in others if e.primitive.name in ("split", "concatenate", "slice")]


def _records(path, kind):
    from distribuuuu_tpu.telemetry import schema

    records = [json.loads(line) for line in open(path)]
    for record in records:
        if record.get("kind", "").startswith("kernel."):
            schema.validate_record(record)
    return [r for r in records if r.get("kind") == kind and r["op"] == "short_conv"]


def test_select_and_fallback_say_which_path_ran_and_why(tmp_path, monkeypatch):
    from distribuuuu_tpu.telemetry import spans

    def trace(dtype="bfloat16", taps=3, seq=64, channels=H, interpret=True):
        bcu, w, _ = _inputs(dtype, taps, 2, seq, channels)
        jax.eval_shape(lambda: op.gated_short_conv(bcu, w, interpret))

    tier.reset_selection()
    path = spans.setup_telemetry(str(tmp_path), rank=0)
    try:
        trace(interpret=None)      # the CPU: the interpreter is the tests' path
        trace()                    # forced: the kernel
        trace(channels=200)        # forced, channels off the lanes
        trace(taps=10)             # forced, a filter past the halo tile
        trace(seq=60)              # forced, no sequence block divides it
        # as on a TPU host of several chips, outside any shard_map
        monkeypatch.setattr(tier, "interpret_mode", lambda: False)
        assert jax.device_count() > 1
        trace(interpret=None)
    finally:
        spans.close_telemetry()
        tier.reset_selection()
    selected = _records(path, "kernel.select")
    assert [(r["impl"], r["requested"]) for r in selected] == [
        ("xla", "auto"), ("pallas", "pallas"), ("xla", "pallas")]
    assert {k: selected[1][k] for k in (
        "seq_block", "row_chunk", "lane_chunk", "taps", "channels", "tokens")} == {
        "seq_block": 64, "row_chunk": 32, "lane_chunk": 256, "taps": 3,
        "channels": H, "tokens": 2 * 64}
    assert "seq_block" not in selected[0] and "seq_block" not in selected[2]
    reasons = [r["reason"] for r in _records(path, "kernel.fallback")]
    assert len(reasons) == 5
    assert "platform cpu" in reasons[0]
    assert "200 channels: no multiple of the 128 lanes" in reasons[1]
    assert "10 taps reaches 9 rows back" in reasons[2]
    assert "60 positions: no multiple of a sequence block" in reasons[3]
    assert "may span several devices" in reasons[4]


def test_the_block_follows_the_shape_and_the_vmem_asked_for_follows_the_block():
    # LFM2's cell: 512 positions of 3 x 2048 channels in bf16; float32 halves it
    assert kernel.seq_block(8192, 2048, 3, jnp.bfloat16) == 512
    assert kernel.seq_block(8192, 2048, 3, jnp.float32) == 256
    assert kernel.seq_block(96, 256, 3, jnp.bfloat16) == 32
    assert kernel.seq_block(24, 128, 3, jnp.bfloat16) is None  # no whole 16-row tile
    assert kernel.seq_block(24, 128, 3, jnp.float32) == 8
    for backward in (False, True):
        params = kernel._params(512, 2048, 3, jnp.bfloat16, backward)
        blocks = kernel._block_bytes(512, 2048, 3, jnp.bfloat16, backward)
        assert params.vmem_limit_bytes == blocks + kernel._VMEM_SLACK
        assert blocks <= kernel._VMEM_BUDGET < 128 * 2 ** 20
    assert kernel._block_bytes(512, 2048, 3, jnp.bfloat16, True) > 2 * 512 * 7 * 2048 * 2


def test_the_short_convolution_has_no_knob():
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.telemetry import schema

    assert "short_conv" in tier.KNOBLESS and "short_conv" not in tier.KNOBS
    assert "short_conv" in tier._NO_SHARD_MAP
    assert kernel.NAME in schema.KERNEL_NAMES
    assert not [key for key in cfg.KERNELS if "CONV_GATE" in key or "SHORT" in key]
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for name in ("ops/short_conv.py", "ops/pallas/short_conv.py"):
        text = open(os.path.join(here, "distribuuuu_tpu", name)).read()
        assert "environ" not in text and "cfg." not in text, name
