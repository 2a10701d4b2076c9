"""The sorted experts' Pallas grouped matmuls (``ops/pallas/moe_gmm.py``),
run by the interpreter, against ``jax.lax.ragged_dot`` on the same sorted
rows and against a dense loop over the experts; what ``sorted_experts``
traces when the kernel arm runs; and what ``kernel.select`` /
``kernel.fallback`` say about which arm ran."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distribuuuu_tpu.ops import moe as moe_ops
from distribuuuu_tpu.ops import pallas as tier
from distribuuuu_tpu.ops.pallas import moe_gmm

TM = 16  # a bf16 sublane tile: the smallest row tile Mosaic would take

GROUPS = {
    "uneven": [5, 33, 17, 9],
    "an_empty_expert": [40, 0, 7, 17],
    "all_on_one_expert": [0, 0, 64, 0],
    "multiples_of_the_tile": [16, 32, 16, 48],
    "one_row_over_the_tile": [17, 33, 1, 49],
}


def _aligned(sizes, tm):
    """``(table, at)``: the tile table and, for each sorted row, the row it
    takes in the aligned buffer, laid out here with numpy, independently
    of ``ops/moe.sorted_experts``."""
    sizes = np.asarray(sizes)
    expert, n_live, starts = moe_gmm.tile_table(
        jnp.asarray(sizes, jnp.int32), int(sizes.sum()), tm)
    at = np.concatenate([
        int(starts[e]) + np.arange(n) for e, n in enumerate(sizes)
    ]).astype(np.int32)
    assert len(expert) * tm >= at.max() + 1 and int(n_live[0]) <= len(expert)
    return (expert, n_live), at


def _pad(rows, at, height):
    return jnp.zeros((height, rows.shape[1]), rows.dtype).at[at].set(rows)


@pytest.mark.parametrize("dtype,tolerance", [
    (jnp.float32, 1e-5), (jnp.bfloat16, 2e-2),
], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("groups", list(GROUPS))
@pytest.mark.parametrize("shape", ["rows_x_w", "rows_x_w_transposed", "rows_t_x_rows"])
def test_grouped_matmul_equals_ragged_dot(shape, groups, dtype, tolerance):
    sizes = GROUPS[groups]
    E, R, K, N = len(sizes), sum(sizes), 128, 256
    table, at = _aligned(sizes, TM)
    height = len(table[0]) * TM
    k = jax.random.split(jax.random.key(len(groups)), 3)
    group_sizes = jnp.asarray(sizes, jnp.int32)
    if shape == "rows_t_x_rows":
        lhs = jax.random.normal(k[0], (R, K), dtype)
        rhs = jax.random.normal(k[1], (R, N), dtype)
        (got,) = moe_gmm.tgmm(
            _pad(lhs, at, height), (_pad(rhs, at, height),), table, E,
            tm=TM, interpret=True)
        want = jax.lax.ragged_dot_general(
            lhs, rhs, group_sizes,
            jax.lax.RaggedDotDimensionNumbers(
                dot_dimension_numbers=(((0,), (0,)), ((), ())),
                lhs_ragged_dimensions=[0], rhs_group_dimensions=[]),
            preferred_element_type=jnp.float32)
        assert got.dtype == jnp.float32 and got.shape == (E, K, N)
    else:
        transposed = shape == "rows_x_w_transposed"
        rows = jax.random.normal(k[0], (R, N if transposed else K), dtype)
        w = jax.random.normal(k[1], (E, K, N), dtype)
        got = moe_gmm.gmm(_pad(rows, at, height), w, table, tm=TM,
                          transpose_rhs=transposed, interpret=True)[at]
        want = jax.lax.ragged_dot(
            rows, w.swapaxes(1, 2) if transposed else w, group_sizes)
        assert got.dtype == dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(got - want).max() <= tolerance * np.abs(want).max()


def _routed(T=640, d=128, f=256, E=4, top=2, dtype=jnp.float32):
    k = jax.random.split(jax.random.key(5), 6)
    params = {
        "w_gate": jax.random.normal(k[1], (E, d, f)) * 0.1,
        "w_up": jax.random.normal(k[2], (E, d, f)) * 0.1,
        "w_down": jax.random.normal(k[3], (E, f, d)) * 0.1,
    }
    x = jax.random.normal(k[4], (T, d), dtype)
    probs = jax.nn.softmax(jax.random.normal(k[0], (T, E)) * 2)
    weights, indices = moe_ops.top_k_as_is(probs, top)
    return params, x, weights, indices


def test_gradient_through_the_kernel_arm_equals_a_dense_loop():
    """``w_gate``, ``w_up``, ``w_down``, ``x`` and ``weights``, through the
    aligned layout, the six calls and the gathers back."""
    params, x, weights, indices = _routed()
    E = params["w_gate"].shape[0]
    cotangent = jax.random.normal(jax.random.key(9), x.shape)

    def dense(params, x, weights):
        return sum(
            ((jax.nn.silu(x @ params["w_gate"][e]) * (x @ params["w_up"][e]))
             @ params["w_down"][e]) * (weights * (indices == e)).sum(-1)[:, None]
            for e in range(E)
        )

    def kernel(params, x, weights):
        return moe_ops.sorted_experts(params, x, weights, indices, interpret=True)

    want, want_vjp = jax.vjp(dense, params, x, weights)
    got, got_vjp = jax.vjp(kernel, params, x, weights)
    np.testing.assert_allclose(got, want, atol=1e-5)
    for g, w in zip(jax.tree.leaves(got_vjp(cotangent)),
                    jax.tree.leaves(want_vjp(cotangent))):
        assert g.dtype == w.dtype
        np.testing.assert_allclose(g, w, atol=1e-4 * float(jnp.abs(w).max()))


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def test_kernel_arm_takes_top_k_rows_a_token_not_one_an_expert():
    """The twin of ``test_olmoe``'s jaxpr test for the kernel arm: forward
    and backward are six Pallas calls on ``T * k`` rows plus at most a tile
    an expert, no ``ragged_dot`` is left, and no value anywhere has a
    tokens x experts x width shape."""
    params, x, weights, indices = _routed()
    (T, d), (E, _, f), k = x.shape, params["w_gate"].shape, indices.shape[1]
    params["router"] = jnp.zeros((d, E))

    def loss(params, x):
        out, _ = moe_ops.moe_ffn_sorted(
            params, x[None], top_k=k, interpret=True)
        return out.sum()

    eqns = list(_walk(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x).jaxpr))
    assert not [e for e in eqns if "ragged_dot" in e.primitive.name]
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == moe_gmm.CALLS_A_STEP
    names = sorted(e.params["name"] for e in calls)
    assert all(n.startswith(moe_gmm.NAME) for n in names) and len(set(names)) == 6
    tm = moe_gmm.row_tile(T * k, E)
    height = (T * k // tm + E) * tm
    for eqn in calls:
        row_operands = {v.aval.shape[0] for v in eqn.invars if v.aval.ndim == 2}
        assert row_operands == {height}, (eqn.params["name"], row_operands)
    for eqn in eqns:
        for var in eqn.outvars:
            shape = tuple(getattr(var.aval, "shape", ()))
            assert not (len(shape) >= 3 and set(shape[-3:-1]) == {T, E}
                        and shape[-1] in (d, f)), (eqn.primitive.name, shape)


def _records(path, kind):
    from distribuuuu_tpu.telemetry import schema

    records = [json.loads(line) for line in open(path)]
    for record in records:
        if record.get("kind", "").startswith("kernel."):
            schema.validate_record(record)
    return [r for r in records if r.get("kind") == kind]


def test_select_and_fallback_say_which_arm_ran_and_why(tmp_path):
    from distribuuuu_tpu.telemetry import spans

    params, x, weights, indices = _routed()
    tier.reset_selection()
    path = spans.setup_telemetry(str(tmp_path), rank=0)
    try:
        # the CPU: XLA's ragged_dot, because of the platform
        jax.eval_shape(lambda: moe_ops.sorted_experts(params, x, weights, indices))
        # forced: the kernel (interpreted), with the tiles it chose
        jax.eval_shape(lambda: moe_ops.sorted_experts(
            params, x, weights, indices, interpret=True))
        # forced on a shape without a tile: 2 rows an expert
        jax.eval_shape(lambda: moe_ops.sorted_experts(
            params, x[:4], weights[:4], indices[:4], interpret=True))
    finally:
        spans.close_telemetry()
        tier.reset_selection()
    selected = [r for r in _records(path, "kernel.select") if r["op"] == "moe_gmm"]
    fell = [r for r in _records(path, "kernel.fallback") if r["op"] == "moe_gmm"]
    assert [(r["impl"], r["requested"]) for r in selected] == [
        ("xla", "auto"), ("pallas", "pallas"), ("xla", "pallas")]
    T, k = indices.shape
    assert selected[1]["tm"] == moe_gmm.ROW_TILE
    assert (selected[1]["tk"], selected[1]["tn"]) == (128, 256)
    assert selected[1]["pad_row_share"] == round(4 * moe_gmm.ROW_TILE / (T * k), 4)
    assert selected[1]["calls_a_step"] == 6
    assert "tm" not in selected[0]
    assert ["platform cpu" in r["reason"] for r in fell] == [True, False]
    assert "2 rows an expert" in fell[1]["reason"]


def test_the_kernel_has_no_knob():
    from distribuuuu_tpu.config import cfg

    assert "moe_gmm" in tier.KNOBLESS and "moe_gmm" not in tier.KNOBS
    assert not [key for key in cfg.KERNELS if "MOE" in key or "GMM" in key]
    with pytest.raises(ValueError, match="unknown kernel op"):
        tier.select("no_such_kernel")
