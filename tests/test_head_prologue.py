"""A q or k projection's way to the flash kernels as two Pallas calls
(``ops/pallas/head_prologue.py``), run by the interpreter at small shapes
with heads of 128: forward and the gradients of ``t`` and ``scale`` against
the ``jax.numpy`` lines (``models/lfm2_moe.HeadNorm.xla`` under autodiff) at
4 and 32 heads, with and without rotary, at positions that repeat (SDAR's
rows), in bfloat16 and float32; the tables against ``models/olmoe.rotary``;
``Attention`` through either path; what ``kernel.select`` / ``kernel.fallback``
say; what a recomputed block runs again; and that nothing names a knob."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from decoder_contract import forward_matmuls, walk
from distribuuuu_tpu.models import lfm2_moe, olmoe, ouro, share
from distribuuuu_tpu.ops import head_prologue as op
from distribuuuu_tpu.ops import pallas as tier
from distribuuuu_tpu.ops.pallas import head_prologue as kernel

D, EPS, THETA = 128, 1e-6, 1e6
# 192 rows: three row blocks of 64, one 64-row chunk each, a sequence
S = 192
# bfloat16: one unit in the last place (both paths round the same float32
# once; a sum that cancels may land a unit apart); float32: the sums' own
# rounding, where the compiler orders them
CLOSE = {"bfloat16": dict(rtol=2.0 ** -7, atol=2.0 ** -9),
         "float32": dict(rtol=2e-5, atol=2e-5)}


def _inputs(dtype, heads, batch=2, seq=S, head_dim=D):
    keys = jax.random.split(jax.random.key(heads), 3)
    t = (2.0 * jax.random.normal(keys[0], (batch, seq, heads * head_dim))).astype(dtype)
    scale = 3.0 + 0.5 * jax.random.normal(keys[1], (head_dim,))
    dy = jax.random.normal(keys[2], (batch, heads, seq, head_dim)).astype(dtype)
    # a noised and a clean copy of a sequence: every position twice
    positions = jnp.tile(jnp.arange(seq // 2, dtype=jnp.int32), 2)
    return t, scale, dy, positions


def _f32(x):
    return np.asarray(x, np.float32)


@pytest.mark.parametrize("rotary", [True, False], ids=["rotary", "no_rotary"])
@pytest.mark.parametrize("heads", [4, 32])
@pytest.mark.parametrize("dtype", list(CLOSE))
def test_the_two_calls_are_the_jax_numpy_lines(dtype, heads, rotary):
    t, scale, dy, positions = _inputs(dtype, heads)
    theta = THETA if rotary else None
    assert kernel.row_block(S, heads, D, dtype, rotary) == 64
    assert kernel.row_chunk(64, dtype) == 64
    want, vjp = jax.vjp(
        lambda t, scale: lfm2_moe.HeadNorm.xla(t, scale, positions, heads, EPS, theta),
        t, scale)
    got, vjp_kernel = jax.vjp(
        lambda t, scale: op.head_prologue(
            t, scale, positions, heads=heads, eps=EPS, theta=theta, interpret=True),
        t, scale)
    assert got.shape == want.shape == (2, heads, S, D) and got.dtype == t.dtype
    close = CLOSE[dtype]
    np.testing.assert_allclose(_f32(got), _f32(want), **close)
    (dt, dscale), (want_dt, want_dscale) = vjp_kernel(dy), vjp(dy)
    assert dt.shape == t.shape and dt.dtype == t.dtype
    assert dscale.shape == (D,) and dscale.dtype == scale.dtype
    np.testing.assert_allclose(_f32(dt), _f32(want_dt), **close)
    np.testing.assert_allclose(
        dscale, want_dscale, rtol=1e-4, atol=1e-5 * np.abs(want_dscale).max())


def test_a_block_of_one_packed_tile_of_two_chunks_and_leading_dims_of_any_rank():
    """The least row block a dtype allows (16 rows of bfloat16, 8 of float32:
    one chunk a block), a block of two 128-row chunks, and ``t [2, 3, S, n
    D]``."""
    assert kernel.row_chunk(512, "bfloat16") == kernel.row_chunk(256, "float32") == 128
    for dtype, block, seq in (("bfloat16", 16, 48), ("float32", 8, 48), ("bfloat16", 256, 512)):
        t, scale, dy, positions = _inputs(dtype, 4, batch=6, seq=seq)
        tables = op.rotary_tables(positions, D, THETA)
        assert kernel.row_chunk(block, dtype) == min(block, 128)
        want, vjp = jax.vjp(lambda t, scale: lfm2_moe.HeadNorm.xla(
            t, scale, positions, 4, EPS, THETA), t, scale)
        shaped = t.reshape(2, 3, seq, 4 * D)
        got = kernel.forward(shaped, scale, *tables, heads=4, eps=EPS, block=block,
                             interpret=True)
        dt, dscale = kernel.backward(
            shaped, scale, dy.reshape(2, 3, 4, seq, D), *tables, heads=4, eps=EPS,
            block=block, interpret=True)
        assert got.shape == (2, 3, 4, seq, D) and dt.shape == shaped.shape
        np.testing.assert_allclose(_f32(got).reshape(want.shape), _f32(want), **CLOSE[dtype])
        np.testing.assert_allclose(_f32(dt).reshape(t.shape), _f32(vjp(dy)[0]), **CLOSE[dtype])
        want_dscale = vjp(dy)[1]
        np.testing.assert_allclose(
            dscale, want_dscale, rtol=1e-4, atol=1e-5 * np.abs(want_dscale).max())


def test_the_tables_are_rotarys_angles_with_the_sign_in_the_sine():
    """``x cos + roll(x, D / 2) sin±`` is ``rotary(x)`` to the bit: the same
    products, the minus sign moved from ``rotate_half`` into the table."""
    positions = jnp.tile(jnp.arange(24, dtype=jnp.int32), 2)
    x = jax.random.normal(jax.random.key(0), (2, 3, 48, D))
    cos, sin = op.rotary_tables(positions, D, THETA)
    assert cos.shape == sin.shape == (48, D) and cos.dtype == sin.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(x * cos + jnp.roll(x, D // 2, axis=-1) * sin),
        np.asarray(olmoe.rotary(x, positions, THETA)))


def _attention(dim=256, **kw):
    return lfm2_moe.Attention(
        dim=dim, num_heads=2, kv_heads=1, eps=EPS, rope_theta=THETA,
        dtype=jnp.float32, head_dim=D, **kw)


@pytest.mark.parametrize("rotary", [True, False], ids=["rotary", "no_rotary"])
def test_attention_takes_either_path_to_the_same_numbers(monkeypatch, rotary):
    """``Attention`` at heads of 128 with the kernel path forced (the
    interpreter, as the platform says here) against the ``jax.numpy`` lines
    it runs on the CPU otherwise: the same parameter tree, output and
    gradients, ``q_norm/scale`` and ``k_norm/scale`` among them."""
    model = _attention(rotary=rotary)
    x = jax.random.normal(jax.random.key(1), (2, 32, 256))
    positions = jnp.arange(32, dtype=jnp.int32)
    params = model.init(jax.random.key(2), x, positions)
    assert params["params"]["q_norm"]["scale"].shape == (D,)
    assert set(params["params"]) == {
        "q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm"}
    params = jax.tree.map(lambda p: p + 0.1 * jnp.cos(jnp.arange(p.size).reshape(p.shape)),
                          params)

    def loss(params, x):
        return (model.apply(params, x, positions) ** 2).sum()

    want = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    ran = []
    monkeypatch.setattr(op, "kernel_runs", lambda *a, **kw: ran.append(a[1:]) or True)
    got = jax.value_and_grad(loss, argnums=(0, 1))(params, x)
    assert ran == [(2, rotary), (1, rotary)]
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4 * float(jnp.abs(w).max()))


def _records(path, kind):
    from distribuuuu_tpu.telemetry import schema

    records = [json.loads(line) for line in open(path)]
    for record in records:
        if record.get("kind", "").startswith("kernel."):
            schema.validate_record(record)
    return [r for r in records if r.get("kind") == kind and r["op"] == "head_prologue"]


def test_select_and_fallback_say_which_path_ran_and_why(tmp_path, monkeypatch):
    from distribuuuu_tpu.telemetry import spans

    def trace(dtype="bfloat16", heads=4, seq=S, head_dim=D, interpret=True):
        t = jax.ShapeDtypeStruct((2, seq, heads * head_dim), jnp.dtype(dtype))
        return op.kernel_runs(t, heads, True, interpret)

    tier.reset_selection()
    path = spans.setup_telemetry(str(tmp_path), rank=0)
    try:
        assert not trace(interpret=None)  # the CPU: the interpreter is the tests' path
        assert trace()                    # forced: the kernel at heads of 128
        assert not trace(head_dim=64)     # forced, LFM2's heads: half the lanes
        assert not trace(seq=60)          # forced, no row block divides it
        assert not trace(dtype="int8")
        # as on a TPU host of several chips, outside any shard_map
        monkeypatch.setattr(tier, "interpret_mode", lambda: False)
        assert jax.device_count() > 1
        assert not trace(interpret=None)
    finally:
        spans.close_telemetry()
        tier.reset_selection()
    selected = _records(path, "kernel.select")
    assert [(r["impl"], r["requested"]) for r in selected] == [
        ("xla", "auto"), ("pallas", "pallas"), ("xla", "pallas")]
    assert {k: selected[1][k] for k in (
        "rows", "heads", "head_dim", "rotary", "row_block", "row_chunk")} == {
        "rows": 2 * S, "heads": 4, "head_dim": D, "rotary": True, "row_block": 64,
        "row_chunk": 64}
    assert "row_block" not in selected[0] and "row_block" not in selected[2]
    reasons = [r["reason"] for r in _records(path, "kernel.fallback")]
    assert len(reasons) == 5
    assert "platform cpu" in reasons[0]
    assert "a head of 64: no multiple of the 128 lanes" in reasons[1]
    assert "60 rows: no multiple of a row block" in reasons[2]
    assert "int8: neither bfloat16 nor float32" in reasons[3]
    assert "may span several devices" in reasons[4]


def _recomputed_block_gradient(monkeypatch, prologue: bool):
    """The gradient's jaxpr of ONE recomputed ``models/share.Block`` whose
    mixer is ``Attention`` at 2 heads on 1 of 128, 384 wide, over 1024 tokens, traced as
    for one chip (the flash kernels, and with ``prologue`` the two calls)."""
    monkeypatch.setattr(tier, "interpret_mode", lambda: False)
    monkeypatch.setattr(tier, "compiled_across_devices", lambda: False)
    if not prologue:
        monkeypatch.setattr(op, "kernel_runs", lambda *a, **kw: False)
    block = ouro.recomputed(share.Block)(
        functools.partial(_attention, 384, attn_impl="flash"), "attn", None, 64, 384, EPS,
        jnp.float32, ("operator_norm", "ffn_norm"))
    x = jax.ShapeDtypeStruct((1, 1024, 384), jnp.float32)
    positions = jnp.arange(1024, dtype=jnp.int32)
    params = jax.eval_shape(block.init, jax.random.key(0), x, positions)

    def loss(params, x):
        return (block.apply(params, x, positions)[0] ** 2).sum()

    return jax.make_jaxpr(jax.grad(loss))(params, x).jaxpr


def _calls(jaxpr) -> list:
    return [eqn.params["name"] for eqn in walk(jaxpr) if eqn.primitive.name == "pallas_call"]


@pytest.mark.parametrize("prologue", [True, False], ids=["kernel", "jax_numpy"])
def test_a_recomputed_block_runs_the_projections_again_and_never_the_forward_call(
        monkeypatch, prologue):
    """What a share block recomputes (``models/ouro.recomputed``) with the op
    in it: ``q_proj`` and ``k_proj`` run again, as often as with the
    ``jax.numpy`` lines (the backward call reads their output as the per-head
    norm's backward did), ``v_proj`` does not, and the second forward holds
    neither ``dtpu_flash_fwd`` nor ``dtpu_head_prologue_fwd``: their one
    reader's outputs are kept."""
    jaxpr = _recomputed_block_gradient(monkeypatch, prologue)
    q, k_or_v = (384, 2 * D), (384, D)
    # forward and again: q, k twice each; v once (k and v share a shape)
    assert forward_matmuls(jaxpr, {q}) == 2
    assert forward_matmuls(jaxpr, {k_or_v}) == 3
    calls = _calls(jaxpr)
    assert calls.count("dtpu_flash_fwd") == 1 and calls.count("dtpu_flash_bwd") == 1
    assert calls.count(f"{kernel.NAME}_fwd") == calls.count(f"{kernel.NAME}_bwd") == (
        2 if prologue else 0)
    again = [eqn for eqn in walk(jaxpr) if eqn.primitive.name in ("remat2", "checkpoint")
             and "dtpu_flash_bwd" in _calls(eqn.params["jaxpr"])]
    assert len(again) == 1  # the backward's: the second forward and the transpose
    again = again[0].params["jaxpr"]
    assert forward_matmuls(again, {q}) == 1 and forward_matmuls(again, {k_or_v}) == 1
    assert sorted(set(_calls(again))) == sorted(
        {"dtpu_flash_bwd", "dtpu_flash_dq", "dtpu_flash_dkdv"}
        | ({f"{kernel.NAME}_bwd"} if prologue else set()))


def test_the_block_follows_the_shape_and_the_vmem_asked_for_follows_the_block():
    # SDAR's and Trinity-Mini's q and k in bf16; float32 halves q's block
    for S_, heads in ((16384, 32), (16384, 4), (8192, 32), (8192, 4)):
        assert kernel.row_block(S_, heads, D, jnp.bfloat16) == 512
    assert kernel.row_block(8192, 32, D, jnp.float32) == 256
    assert kernel.row_block(24, 4, D, jnp.bfloat16) is None  # no whole 16-row tile
    assert kernel.row_block(24, 4, D, jnp.float32) == 8
    for backward in (False, True):
        params = kernel._params(512, 32, D, jnp.bfloat16, True, backward)
        blocks = kernel._block_bytes(512, 32, D, jnp.bfloat16, True, backward)
        assert params.vmem_limit_bytes == blocks + kernel._VMEM_SLACK
        assert blocks <= kernel._VMEM_BUDGET < 128 * 2 ** 20
    assert kernel._block_bytes(512, 32, D, jnp.bfloat16, True, True) > 2 * 3 * 512 * 4096 * 2


def test_the_head_prologue_has_no_knob():
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.telemetry import schema

    assert "head_prologue" in tier.KNOBLESS and "head_prologue" not in tier.KNOBS
    assert "head_prologue" in tier._NO_SHARD_MAP
    assert kernel.NAME in schema.KERNEL_NAMES
    assert schema.DEVICE_SCOPES["attn_prologue"] == "kernels"
    assert not [key for key in cfg.KERNELS if "PROLOGUE" in key or "HEAD" in key]
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for name in ("ops/head_prologue.py", "ops/pallas/head_prologue.py"):
        text = open(os.path.join(here, "distribuuuu_tpu", name)).read()
        assert "environ" not in text and "cfg." not in text, name
