"""The kernel tier's exactness, selection, and composition pins (ISSUE 13).

Every kernel in ops/pallas/ runs here in interpret mode (the tier-1 CPU
story — same pallas_call the TPU lowers) against its XLA reference:

* fused optimizer update — BIT-exact jit-vs-jit for SGD-momentum (fp32
  and the bf16-momentum configuration) and AdamW, including the optax
  state structure and counters;
* fused conv epilogue — pinned tolerance (the fused path keeps the fp32
  accumulator into the affine; the reference rounds to the compute
  dtype first), with the param tree pinned compute-path-independent;
* fused decode attention — pinned tolerance vs the dense softmax, and
  logit-equivalence through the real GPTDecoder on a real GPT param
  tree, plus token-identical end-to-end generation;
* ZeRO shard-compatibility — updating a shard ≡ slicing the unsharded
  update (the elementwise-commute proof the partition layer's layouts
  rely on), plus the fused update running under the real ZeRO-1 lowering;
* selection discipline — KERNELS.* validation refusals with their
  arithmetic, kernel.select/kernel.fallback telemetry, warn-once
  fallback that stays correct, and the trajectory pin
  (KERNELS.*=pallas training ≡ xla within pinned tolerance);
* the bench-index pin — BENCH_r09's kernel_* series must never clobber
  the resnet50 img/s regression reference (the PR 8 lesson).
"""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import distribuuuu_tpu.config as config
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu.ops import pallas as tier
from distribuuuu_tpu.ops.pallas import conv_epilogue as ce
from distribuuuu_tpu.ops.pallas import decode_attn as da
from distribuuuu_tpu.ops.pallas import opt_update as ou

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))


@pytest.fixture(autouse=True)
def _fresh_tier():
    tier.reset_selection()
    yield
    tier.reset_selection()


def _tree_bit_equal(a, b):
    return all(jax.tree.leaves(jax.tree.map(
        lambda x, y: bool((x == y).all()), a, b
    )))


def _params(rng, dtype=jnp.float32):
    # deliberately awkward shapes: lane-unaligned, tiny, multi-block
    return {
        "w": jnp.asarray(rng.standard_normal((37, 13)), dtype),
        "b": jnp.asarray(rng.standard_normal((5,)), dtype),
        "big": jnp.asarray(rng.standard_normal((700_000,)), dtype),
    }


# ------------------------------------------------------ fused opt update


@pytest.mark.parametrize("mom_dtype", ["float32", "bfloat16"])
def test_fused_sgd_bit_exact_vs_optax(mom_dtype):
    from distribuuuu_tpu.utils.optim import construct_optimizer

    cfg.defrost()
    cfg.OPTIM.MOMENTUM_DTYPE = mom_dtype
    rng = np.random.default_rng(0)
    params = _params(rng)
    grads = jax.tree.map(lambda x: x * 0.1, params)
    opt = construct_optimizer()
    st = opt.init(params)

    @jax.jit
    def ref(p, g, s):
        u, s2 = opt.update(g, s, p)
        return optax.apply_updates(p, u), s2

    @jax.jit
    def fused(p, g, s):
        return ou.fused_optimizer_update(
            p, g, s, kind="sgd", wd=float(cfg.OPTIM.WEIGHT_DECAY),
            mom=float(cfg.OPTIM.MOMENTUM),
            nesterov=bool(cfg.OPTIM.NESTEROV), b1=0.9, b2=0.999,
            eps=1e-8, interpret=True,
        )

    p1, s1 = params, st
    p2, s2 = params, st
    for _ in range(2):  # two steps: the trace feeds back
        p1, s1 = ref(p1, grads, s1)
        p2, s2 = fused(p2, grads, s2)
    assert _tree_bit_equal(p1, p2)
    assert _tree_bit_equal(s1.inner_state[1][0].trace,
                           s2.inner_state[1][0].trace)
    if mom_dtype == "bfloat16":
        assert s2.inner_state[1][0].trace["w"].dtype == jnp.bfloat16
    assert int(s1.count) == int(s2.count)
    assert (jax.tree_util.tree_structure(s1)
            == jax.tree_util.tree_structure(s2))


def test_fused_adamw_bit_exact_vs_optax():
    from distribuuuu_tpu.utils.optim import construct_optimizer

    cfg.defrost()
    cfg.OPTIM.OPTIMIZER = "adamw"
    rng = np.random.default_rng(1)
    params = _params(rng)
    grads = jax.tree.map(lambda x: x * 0.03, params)
    opt = construct_optimizer()
    st = opt.init(params)

    @jax.jit
    def ref(p, g, s):
        u, s2 = opt.update(g, s, p)
        return optax.apply_updates(p, u), s2

    @jax.jit
    def fused(p, g, s):
        return ou.fused_optimizer_update(
            p, g, s, kind="adamw", wd=float(cfg.OPTIM.WEIGHT_DECAY),
            mom=0.9, nesterov=True, b1=float(cfg.OPTIM.BETA1),
            b2=float(cfg.OPTIM.BETA2), eps=1e-8, interpret=True,
        )

    p1, s1 = params, st
    p2, s2 = params, st
    for _ in range(3):  # bias correction moves with the count
        p1, s1 = ref(p1, grads, s1)
        p2, s2 = fused(p2, grads, s2)
    assert _tree_bit_equal(p1, p2)
    adam1, _ = ou._find_state(s1.inner_state, "mu")
    adam2, _ = ou._find_state(s2.inner_state, "mu")
    assert _tree_bit_equal(adam1.mu, adam2.mu)
    assert _tree_bit_equal(adam1.nu, adam2.nu)
    assert int(adam1.count) == int(adam2.count) == 3
    assert (jax.tree_util.tree_structure(s1)
            == jax.tree_util.tree_structure(s2))


def test_fused_sgd_without_momentum():
    cfg.defrost()
    cfg.OPTIM.MOMENTUM = 0.0
    from distribuuuu_tpu.utils.optim import construct_optimizer

    rng = np.random.default_rng(2)
    params = {"w": jnp.asarray(rng.standard_normal((9, 11)), jnp.float32)}
    grads = jax.tree.map(lambda x: x * 0.1, params)
    opt = construct_optimizer()
    st = opt.init(params)

    @jax.jit
    def ref(p, g, s):
        u, s2 = opt.update(g, s, p)
        return optax.apply_updates(p, u), s2

    @jax.jit
    def fused(p, g, s):
        return ou.fused_optimizer_update(
            p, g, s, kind="sgd", wd=float(cfg.OPTIM.WEIGHT_DECAY),
            mom=0.0, nesterov=True, b1=0.9, b2=0.999, eps=1e-8,
            interpret=True,
        )

    p1, _ = ref(params, grads, st)
    p2, _ = fused(params, grads, st)
    assert _tree_bit_equal(p1, p2)


# Leaf shapes ResNet-50 and RegNetY-16GF really have, one per way
# ``opt_update._plan`` blocks a leaf: one [second-minor, minor] slab a block,
# a last dimension under one lane tile, a plain 2-D leaf in row blocks, rows
# that no sublane tile divides over a ragged last block, a last dimension
# that is no lane multiple with two slabs a block, a stem whose second-minor
# dimension is 3, a 1-D leaf, and the largest conv (36 blocks).
MODEL_LEAVES = [
    (3, 3, 256, 256), (1, 1, 64, 256), (2048, 1000), (1, 1, 308, 1232),
    (3, 3, 112, 224), (7, 7, 3, 64), (64,), (3, 3, 512, 512),
]
UPDATE_KINDS = {  # config overrides, fused kind
    "sgd_f32": ({}, "sgd"),
    "sgd_bf16_trace": ({"MOMENTUM_DTYPE": "bfloat16"}, "sgd"),
    "sgd_plain": ({"MOMENTUM": 0.0}, "sgd"),
    "adamw": ({"OPTIMIZER": "adamw"}, "adamw"),
}


@pytest.mark.parametrize("shape", MODEL_LEAVES, ids=str)
@pytest.mark.parametrize("update", list(UPDATE_KINDS))
def test_fused_update_bit_exact_on_model_leaf_shapes(update, shape):
    """Every way a leaf goes to the kernel (viewed in place, blocked with
    a ragged end, slabs, one block) gives optax's bits: parameters
    and every moment, two steps, so the moments feed back."""
    from distribuuuu_tpu.utils.optim import construct_optimizer

    overrides, kind = UPDATE_KINDS[update]
    cfg.defrost()
    for key, value in overrides.items():
        cfg.OPTIM[key] = value
    rng = np.random.default_rng(4)
    params = {"leaf": jnp.asarray(rng.standard_normal(shape), jnp.float32)}
    grads = jax.tree.map(lambda x: x * 0.1, params)
    opt = construct_optimizer()
    state = opt.init(params)
    _, view, block, _ = ou._plan(shape, [jnp.float32])
    if shape == (1, 1, 308, 1232):
        assert view[1] % block[1], "the ragged last block is the point"

    @jax.jit
    def ref(p, g, s):
        u, s2 = opt.update(g, s, p)
        return optax.apply_updates(p, u), s2

    @jax.jit
    def fused(p, g, s):
        return ou.fused_optimizer_update(
            p, g, s, kind=kind, wd=float(cfg.OPTIM.WEIGHT_DECAY),
            mom=float(cfg.OPTIM.MOMENTUM),
            nesterov=bool(cfg.OPTIM.NESTEROV), b1=float(cfg.OPTIM.BETA1),
            b2=float(cfg.OPTIM.BETA2), eps=1e-8, interpret=True,
        )

    p1, s1 = params, state
    p2, s2 = params, state
    for _ in range(2):
        p1, s1 = ref(p1, grads, s1)
        p2, s2 = fused(p2, grads, s2)
    assert _tree_bit_equal(p1, p2)
    assert _tree_bit_equal(s1, s2)
    assert (jax.tree_util.tree_structure(s1)
            == jax.tree_util.tree_structure(s2))


@pytest.mark.parametrize("shape,order", [
    ((2048, 1000), (1, 0)),            # the TPU rests the classifier so
    ((1, 1, 256, 64), (0, 1, 3, 2)),   # ... and this conv
    ((1, 1, 308, 1232), (0, 2, 1, 3)),  # ... and RegNetY's SE convs
])
def test_fused_sgd_bit_exact_in_a_resting_order(monkeypatch, shape, order):
    """The kernel sees a leaf in the dimension order the device rests it
    in; the CPU rests everything row-major, so the TPU's orders are put in
    by hand here. Elementwise, so any order gives optax's bits."""
    monkeypatch.setattr(ou, "_resting_order", lambda *a: order)
    _, view, _, copied = ou._plan(shape, [jnp.float32])
    assert view[1:] == (shape[order[-2]], shape[order[-1]]) and not copied
    rng = np.random.default_rng(5)
    p, g, t = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
               for _ in range(3))
    kw = dict(wd=5e-5, mom=0.9, nesterov=True)
    got = jax.jit(lambda p, g, t: ou.sgd_leaf(
        p, g, t, jnp.float32(0.1), interpret=True, **kw))(p, g, t)
    monkeypatch.undo()
    want = jax.jit(lambda p, g, t: ou.sgd_leaf(
        p, g, t, jnp.float32(0.1), interpret=True, **kw))(p, g, t)
    assert got[0].shape == shape and _tree_bit_equal(got, want)


def test_zero_sharded_update_equals_unsharded_then_shard():
    """The partition layer's shard-compat contract: the fused update is
    elementwise per leaf, so updating a ZeRO shard must equal slicing
    the unsharded update — exactly, per shard, for params AND moments."""
    rng = np.random.default_rng(3)
    n, shards = 4096, 8
    p = jnp.asarray(rng.standard_normal(n), jnp.float32)
    g = jnp.asarray(rng.standard_normal(n), jnp.float32)
    t = jnp.asarray(rng.standard_normal(n), jnp.float32)
    lr = jnp.float32(0.1)
    kw = dict(wd=5e-5, mom=0.9, nesterov=True, interpret=True)
    full_p, full_t = jax.jit(
        lambda p, g, t: ou.sgd_leaf(p, g, t, lr, **kw)
    )(p, g, t)
    per = n // shards
    for i in range(shards):
        sl = slice(i * per, (i + 1) * per)
        sp, st_ = jax.jit(
            lambda p, g, t: ou.sgd_leaf(p, g, t, lr, **kw)
        )(p[sl], g[sl], t[sl])
        assert bool((sp == full_p[sl]).all())
        assert bool((st_ == full_t[sl]).all())


@pytest.mark.slow  # 39s: real ZeRO-1+3 lowerings; tier-1 budget (ISSUE 18)
def test_fused_update_under_real_zero_lowering():
    """KERNELS.OPT_UPDATE=pallas composed with the partition layer's
    ZeRO-1 layout on the 8-device mesh: the trajectory must match the
    XLA reference path's within the pinned tolerance. Since r16 the
    fused update lowers PER-SHARD through shard_map
    (opt_update.per_shard_update) — both arms consume the same
    reduce-scattered grads; tests/test_zero_overlap.py adds the ZeRO-3
    twin and pins the census stays gather-once."""
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.parallel import mesh as mesh_lib, sharding
    from distribuuuu_tpu.parallel.partition import topology as topo_lib

    def run_two_steps():
        mesh = mesh_lib.build_mesh()
        topo = topo_lib.from_cfg(cfg)
        model = trainer.build_model_from_cfg(topo)
        from distribuuuu_tpu.parallel.partition import lowering
        from distribuuuu_tpu.utils.optim import construct_optimizer

        lowered = lowering.lower(
            model, construct_optimizer(), topk=2, mesh=mesh,
            topology=topo, im_size=16,
        )
        state = lowered.init_state(jax.random.key(0), 16)
        rng = np.random.default_rng(0)
        batch = sharding.shard_batch(mesh, {
            "image": rng.standard_normal((8, 16, 16, 3)).astype(np.float32),
            "label": rng.integers(0, 4, (8,)).astype(np.int32),
            "mask": np.ones((8,), np.float32),
        })
        for _ in range(2):
            state, metrics = lowered.train_step(state, batch)
        return jax.device_get(state.params), jax.device_get(metrics)

    cfg.defrost()
    cfg.MODEL.ARCH = "resnet18"
    cfg.MODEL.NUM_CLASSES = 4
    cfg.MESH.ZERO = 1
    # both arms consume the same reduce-scattered grads (per-shard
    # lowering), but XLA fuses the in-step optax chain with different
    # FMA contraction than the shard_map'd kernel region — ulp-level
    # drift that a reference-recipe LR of 0.1 amplifies chaotically
    # through BN+relu within two steps; the pin is layout composition,
    # not chaos
    cfg.OPTIM.BASE_LR = 0.001
    ref_params, ref_metrics = run_two_steps()
    cfg.defrost()
    cfg.KERNELS.OPT_UPDATE = "pallas"
    pal_params, pal_metrics = run_two_steps()
    diffs = jax.tree.leaves(jax.tree.map(
        lambda a, b: float(np.abs(np.asarray(a, np.float32)
                                  - np.asarray(b, np.float32)).max()),
        ref_params, pal_params,
    ))
    assert max(diffs) <= 5e-6, max(diffs)
    assert np.isclose(float(ref_metrics["loss"]), float(pal_metrics["loss"]),
                      rtol=1e-5)


@pytest.mark.slow  # 29s: two full toy train runs; tier-1 budget (ISSUE 18)
def test_trajectory_pin_pallas_vs_xla_training():
    """The tier's headline contract: a KERNELS.OPT_UPDATE=pallas training
    run tracks the xla reference within the pinned tolerance (the only
    drift source is XLA fusing the in-step reference chain with
    different FMA contraction than the standalone jit — ~1 ulp/step)."""
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.parallel.partition import lowering
    from distribuuuu_tpu.utils.optim import construct_optimizer

    def run(n_steps=3):
        model = trainer.build_model_from_cfg()
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((4, 24, 24, 3)), jnp.float32)
        y = jnp.asarray(rng.integers(0, 8, (4,)), jnp.int32)
        v = model.init(jax.random.key(0), x, train=True)
        state = lowering.TrainState(
            params=v["params"], batch_stats=v.get("batch_stats", {}),
            opt_state=construct_optimizer().init(v["params"]),
            step=jnp.int32(0), key=jax.random.key(1),
        )
        step = lowering.make_train_step(
            model, construct_optimizer(), topk=2
        )
        for _ in range(n_steps):
            state, _ = step(state, {"image": x, "label": y})
        return jax.device_get(state.params)

    cfg.defrost()
    cfg.MODEL.ARCH = "resnet18"
    cfg.MODEL.NUM_CLASSES = 8
    ref = run()
    cfg.defrost()
    cfg.KERNELS.OPT_UPDATE = "pallas"
    pal = run()
    diffs = jax.tree.leaves(jax.tree.map(
        lambda a, b: float(np.abs(np.asarray(a, np.float32)
                                  - np.asarray(b, np.float32)).max()),
        ref, pal,
    ))
    assert max(diffs) <= 5e-6, max(diffs)


# ------------------------------------------------------- conv epilogue


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv_epilogue_tolerance(dtype):
    rng = np.random.default_rng(4)
    B, H, W, cin, cout = 2, 5, 5, 48, 96
    x = jnp.asarray(rng.standard_normal((B, H, W, cin)), dtype)
    k = jnp.asarray(rng.standard_normal((1, 1, cin, cout)) * 0.1,
                    jnp.float32)
    mean = jnp.asarray(rng.standard_normal(cout) * 0.2, jnp.float32)
    var = jnp.asarray(rng.random(cout) + 0.3, jnp.float32)
    scale = jnp.asarray(rng.standard_normal(cout) * 0.3 + 1.0, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(cout) * 0.2, jnp.float32)
    inv = jax.lax.rsqrt(var + 1e-5) * scale
    a, c = inv, bias - mean * inv

    @jax.jit
    def ref(x):
        o = jax.lax.conv_general_dilated(
            x, k.astype(dtype), (1, 1), [(0, 0), (0, 0)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        y = (o.astype(jnp.float32) - mean) * inv + bias
        return jnp.maximum(y, 0.0).astype(dtype)

    @jax.jit
    def fused(x):
        return ce.conv1x1_bn_act(x, k.astype(dtype), a, c, "relu",
                                 interpret=True)

    r, f = ref(x), fused(x)
    tol = 1e-5 if dtype == jnp.float32 else 0.0625  # pinned per dtype
    d = float(jnp.abs(r.astype(jnp.float32) - f.astype(jnp.float32)).max())
    assert d <= tol, d


def test_conv_epilogue_through_convbn_and_param_tree():
    import flax.linen as nn

    from distribuuuu_tpu.models.layers import ConvBN

    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((2, 8, 8, 32)), jnp.float32)
    m = ConvBN(64, (1, 1), 1, act=nn.relu)
    v = m.init(jax.random.key(0), x, train=False)
    # non-default BN stats so the affine folding is actually exercised
    v = {
        "params": v["params"],
        "batch_stats": jax.tree.map(
            lambda s: s + jnp.asarray(
                rng.random(s.shape) * 0.3, s.dtype
            ),
            v["batch_stats"],
        ),
    }
    ref = jax.jit(lambda v, x: m.apply(v, x, train=False))(v, x)
    cfg.defrost()
    cfg.KERNELS.CONV_EPILOGUE = "pallas"
    v2 = m.init(jax.random.key(0), x, train=False)
    assert (jax.tree_util.tree_structure(v2)
            == jax.tree_util.tree_structure(v))  # compute-path-independent
    fused = jax.jit(lambda v, x: m.apply(v, x, train=False))(v, x)
    d = float(jnp.abs(ref.astype(jnp.float32)
                      - fused.astype(jnp.float32)).max())
    assert d <= 0.0625, d


def test_conv_epilogue_efficientnet_eval_and_fallback_warns_once():
    """EfficientNet eval under forced pallas: the pointwise chains fuse,
    every non-qualifying site (3×3 stem, depthwise) falls back with ONE
    warning per distinct reason — never one per call site — and the
    logits stay within tolerance."""
    from distribuuuu_tpu.models.efficientnet import EfficientNet

    m = EfficientNet(blocks=((1, 16, 1, 1, 3), (6, 24, 1, 2, 3)),
                     stem_ch=8, head_ch=64, num_classes=4)
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((2, 32, 32, 3)), jnp.float32)
    v = m.init(jax.random.key(0), x, train=False)
    ref = jax.jit(lambda v, x: m.apply(v, x, train=False))(v, x)
    cfg.defrost()
    cfg.KERNELS.CONV_EPILOGUE = "pallas"
    v2 = m.init(jax.random.key(0), x, train=False)
    assert (jax.tree_util.tree_structure(v2)
            == jax.tree_util.tree_structure(v))
    fused = jax.jit(lambda v, x: m.apply(v, x, train=False))(v, x)
    d = float(jnp.abs(ref.astype(jnp.float32)
                      - fused.astype(jnp.float32)).max())
    assert d <= 0.25, d  # bf16 logits through a different rounding path
    # the warn-once registry holds one entry per (op, reason) — the 3×3
    # stem and the grouped depthwise are distinct reasons; dozens of
    # call sites, but never dozens of warnings (the repo logger does not
    # propagate, so the dedup set IS the observable)
    fallback_reasons = {r for (op, r) in tier._warned
                        if op == "conv_epilogue"}
    assert 1 <= len(fallback_reasons) <= 3


def test_conv_epilogue_training_never_fuses():
    """The fused path is eval-only: a train=True forward under forced
    pallas must keep real batch-stat BN (stats update, raw conv out)."""
    import flax.linen as nn

    from distribuuuu_tpu.models.layers import ConvBN

    cfg.defrost()
    cfg.KERNELS.CONV_EPILOGUE = "pallas"
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((4, 4, 4, 16)), jnp.float32)
    m = ConvBN(32, (1, 1), 1, act=nn.relu)
    v = m.init(jax.random.key(0), x, train=True)
    y, mutated = m.apply(v, x, train=True, mutable=["batch_stats"])
    # stats moved off their init: the batch path ran, not the affine
    var = jax.tree.leaves(mutated["batch_stats"])
    assert any(
        float(jnp.abs(s.astype(jnp.float32)
                      - jnp.asarray(i, jnp.float32)).max()) > 0
        for s, i in zip(var, [0.0, 1.0])
    )


# -------------------------------------------------------- decode attn


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attn_tolerance(dtype):
    rng = np.random.default_rng(8)
    B, H, C, D = 3, 2, 256, 32
    q = jnp.asarray(rng.standard_normal((B, H, D)), dtype)
    ck = jnp.asarray(rng.standard_normal((B, H, C, D)), dtype)
    cv = jnp.asarray(rng.standard_normal((B, H, C, D)), dtype)
    lens = jnp.asarray([0, 100, C - 1], jnp.int32)  # fresh/mid/full rows
    sc = D ** -0.5

    @jax.jit
    def dense(q, ck, cv):
        s = jnp.einsum("bhd,bhcd->bhc", q.astype(jnp.float32),
                       ck.astype(jnp.float32)) * sc
        vis = jnp.arange(C)[None, None, :] <= lens[:, None, None]
        s = jnp.where(vis, s, jnp.float32(-1e30))
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhc,bhcd->bhd", w, cv.astype(jnp.float32))

    @jax.jit
    def fused(q, ck, cv):
        return da.decode_attention(q, ck, cv, lens, scale=sc,
                                   interpret=True)

    d = float(jnp.abs(dense(q, ck, cv) - fused(q, ck, cv)).max())
    assert d <= 1e-5, d  # fp32 online-softmax summation order


def test_decode_attn_matches_cached_attention_on_gpt_params():
    """Logit-equivalence through the REAL decoder: GPTDecoder applied to
    a real GPT param tree, xla vs forced-pallas decode step."""
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.lm import generate as gen

    cfg.defrost()
    cfg.MODEL.ARCH = "gpt_nano"
    cfg.MODEL.NUM_CLASSES = 320
    cfg.LM.SEQ_LEN = 64
    model = trainer.build_model_from_cfg()
    variables = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False
    )
    dec = gen.decoder_for(model)
    B, C = 2, 64
    hh, dh = model.num_heads, model.dim // model.num_heads
    rng = np.random.default_rng(9)
    cache = {
        "k": jnp.asarray(
            rng.standard_normal((model.depth, B, hh, C, dh)) * 0.3,
            model.dtype),
        "v": jnp.asarray(
            rng.standard_normal((model.depth, B, hh, C, dh)) * 0.3,
            model.dtype),
    }
    lens = jnp.asarray([4, 40], jnp.int32)
    toks = jnp.asarray([[7], [200]], jnp.int32)
    run = jax.jit(lambda v, t, l, c: dec.apply(v, t, l, c))
    lo_ref, cache_ref = run(variables, toks, lens, cache)
    cfg.defrost()
    cfg.KERNELS.DECODE_ATTN = "pallas"
    cfg.KERNELS.DECODE_BLOCK = 32
    lo_pal, cache_pal = run(variables, toks, lens, cache)
    d = float(jnp.abs(lo_ref.astype(jnp.float32)
                      - lo_pal.astype(jnp.float32)).max())
    assert d <= 0.05, d  # bf16 activations through the block softmax
    assert _tree_bit_equal(cache_ref, cache_pal)  # cache write untouched


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_generate_engine_tokens_identical(impl, tmp_path):
    """End-to-end: greedy generation must produce the SAME tokens with
    the fused decode kernel as with the dense reference."""
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.lm.generate import GenerateEngine

    cfg.defrost()
    cfg.MODEL.ARCH = "gpt_nano"
    cfg.MODEL.NUM_CLASSES = 320
    cfg.LM.SEQ_LEN = 64
    cfg.KERNELS.DECODE_ATTN = impl
    cfg.KERNELS.DECODE_BLOCK = 64
    model = trainer.build_model_from_cfg()
    variables = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False
    )
    eng = GenerateEngine(
        model, variables, max_new_tokens=6, prompt_len=8,
        batch_tiles=[2], cache_tiles=[64], eos_id=-1,
    )
    with eng:
        toks = eng.submit([1, 2, 3, 4]).result(timeout=60)
    assert len(toks) == 6
    # stash per-impl results on the module for the cross-impl compare
    key = "_gen_tokens"
    store = globals().setdefault(key, {})
    store[impl] = toks
    if len(store) == 2:
        assert store["xla"] == store["pallas"], store


# --------------------------------------------- selection + validation


def test_kernels_cfg_refusals():
    cfg.defrost()
    cfg.KERNELS.OPT_UPDATE = "mosaic"
    with pytest.raises(ValueError, match=r"auto.*pallas.*xla"):
        tier.validate_kernels_cfg()
    cfg.KERNELS.OPT_UPDATE = "auto"
    cfg.KERNELS.DECODE_BLOCK = 100
    with pytest.raises(ValueError, match=r"100 % 8 = 4"):
        tier.validate_kernels_cfg()


def test_engine_refuses_unaligned_cache_tiles():
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.lm.generate import GenerateEngine

    cfg.defrost()
    cfg.MODEL.ARCH = "gpt_nano"
    cfg.MODEL.NUM_CLASSES = 320
    cfg.LM.SEQ_LEN = 256
    cfg.KERNELS.DECODE_ATTN = "pallas"
    cfg.KERNELS.DECODE_BLOCK = 128
    model = trainer.build_model_from_cfg()
    with pytest.raises(ValueError) as e:
        GenerateEngine(
            model, {"params": {}}, max_new_tokens=8, prompt_len=8,
            batch_tiles=[1], cache_tiles=[192],
        )
    # both numbers and the remainder arithmetic must be in the message
    assert "192" in str(e.value) and "128" in str(e.value)
    assert "192 % 128 = 64" in str(e.value)


def test_select_emits_telemetry_and_fallback(tmp_path):
    from distribuuuu_tpu.telemetry import schema, spans

    path = spans.setup_telemetry(str(tmp_path), rank=0)
    try:
        cfg.defrost()
        cfg.KERNELS.OPT_UPDATE = "pallas"
        cfg.KERNELS.CONV_EPILOGUE = "pallas"  # forced ⇒ fallback is loud
        assert tier.select("opt_update", supported=True) == "pallas"
        assert tier.select("opt_update", supported=True) == "pallas"  # dedup
        assert tier.select(
            "conv_epilogue", supported=False, reason="kernel (3, 3)"
        ) == "xla"
    finally:
        spans.close_telemetry()
    recs = [json.loads(ln) for ln in open(path)]
    for r in recs:
        if r.get("kind", "").startswith("kernel."):
            schema.validate_record(r)
    sel = [r for r in recs if r.get("kind") == "kernel.select"]
    fb = [r for r in recs if r.get("kind") == "kernel.fallback"]
    assert [s["op"] for s in sel].count("opt_update") == 1  # emitted once
    assert sel[0]["impl"] == "pallas" and sel[0]["requested"] == "pallas"
    assert fb and fb[0]["op"] == "conv_epilogue"
    assert "kernel (3, 3)" in fb[0]["reason"]


def test_auto_stays_on_xla_off_tpu():
    """`auto` must never pick interpret-mode pallas on the CPU backend —
    the tier-1 suite runs the reference paths unless a test forces."""
    assert tier.select("opt_update", supported=True) == "xla"
    from distribuuuu_tpu.ops.pallas.opt_update import fused_update_for

    assert fused_update_for() is None


def test_run_report_kernels_section(tmp_path):
    import run_report

    tdir = tmp_path / "telemetry"
    os.makedirs(tdir)
    recs = [
        {"kind": "clock", "rank": 0, "t": 0.0, "unix": 0.0, "mono": 0.0},
        {"kind": "kernel.select", "rank": 0, "t": 1.0, "op": "opt_update",
         "impl": "pallas", "requested": "auto"},
        {"kind": "kernel.select", "rank": 0, "t": 1.0, "op": "flash_attn",
         "impl": "pallas", "requested": "auto", "L": 4096, "d": 128,
         "causal": True, "blk_q": 512, "blk_k": 512, "tiles_visited": 36,
         "tiles_crossed": 8, "tiles_masked": 36, "bwd_matmuls_a_tile": 5},
        {"kind": "kernel.fallback", "rank": 0, "t": 1.0,
         "op": "conv_epilogue", "requested": "pallas",
         "reason": "kernel (3, 3) is not pointwise (1, 1)"},
        {"kind": "span", "rank": 0, "t": 1.0, "v": 1, "name": "step",
         "t0": 0.0, "dur": 0.01, "track": "pipeline", "phase": "train"},
    ]
    with open(tdir / "rank00000.jsonl", "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    rep = run_report.build_report(str(tmp_path))
    kern = rep["kernels"]
    assert kern["selected"]["opt_update"] == {
        "impl": "pallas", "requested": "auto"}
    # a knobless op's record carries what it chose
    flash = kern["selected"]["flash_attn"]
    assert (flash["impl"], flash["blk_q"], flash["blk_k"]) == ("pallas", 512, 512)
    assert (flash["tiles_visited"], flash["tiles_crossed"]) == (36, 8)
    assert flash["bwd_matmuls_a_tile"] == 5 and "rank" not in flash
    assert kern["fallbacks"][0]["op"] == "conv_epilogue"


def test_run_report_prints_a_shares_experts_and_its_plan(tmp_path, capsys):
    """A chip's share of an expert-parallel layer in the ``kernels``
    section: ``moe_gmm``'s record with the experts held of those routed over
    and the rows its buffer is sized for, and the ``share.plan`` record."""
    import run_report

    tdir = tmp_path / "telemetry"
    os.makedirs(tdir)
    recs = [
        {"kind": "clock", "rank": 0, "t": 0.0, "unix": 0.0, "mono": 0.0},
        {"kind": "kernel.select", "rank": 0, "t": 1.0, "op": "moe_gmm",
         "impl": "pallas", "requested": "auto", "tm": 256, "tk": 2048, "tn": 1536,
         "pad_row_share": 0.5, "calls_a_step": 6, "experts_held": 8,
         "experts_total": 64, "rows_bound": 32768},
        {"kind": "share.plan", "rank": 0, "t": 1.0, "share_chips": 8, "share_rank": 0,
         "experts_held": 8, "experts_total": 64, "vocab_held": 19360,
         "vocab_total": 154880, "recomputed": "every block"},
        {"kind": "span", "rank": 0, "t": 1.0, "v": 1, "name": "step",
         "t0": 0.0, "dur": 0.01, "track": "pipeline", "phase": "train"},
    ]
    with open(tdir / "rank00000.jsonl", "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    rep = run_report.build_report(str(tmp_path))
    gmm = rep["kernels"]["selected"]["moe_gmm"]
    assert (gmm["experts_held"], gmm["experts_total"], gmm["rows_bound"]) == (8, 64, 32768)
    plan = rep["kernels"]["share_plan"]
    assert (plan["share_chips"], plan["vocab_held"], plan["vocab_total"]) == (8, 19360, 154880)
    run_report._print_report(rep)
    printed = capsys.readouterr().out
    assert "moe_gmm: " in printed and "experts_held=8" in printed
    assert "experts_total=64" in printed and "rows_bound=32768" in printed
    assert "of 8 chips holds 8 of 64 experts and 19360 of 154880 vocabulary rows" in printed


@pytest.mark.parametrize("kind,where,kept_proj", [
    ("loop.plan", "looped stack: 4 passes over 8 layers, 32 block applications", None),
    ("share.plan", "share of a layer: rank 0 of 8 chips", None),
    ("loop.plan", "looped stack: 4 passes over 8 layers, 32 block applications", 23),
    ("loop.plan", "looped stack: 4 passes over 8 layers, 32 block applications", 0),
])
def test_run_report_prints_what_the_recomputed_blocks_keep(
        tmp_path, capsys, kind, where, kept_proj):
    """Both plan records in the ``kernels`` section with what their
    recomputed blocks keep a step: the bytes in all and, of them, the flash
    kernel's output, log-sum-exp, q, k and v and the branches' outputs
    (``ouro_2_6b.train_seq4096``'s numbers); a ``loop.plan`` that planned the
    MLP's two products says how many applications keep them, their bytes and
    the three numbers the count was planned from (23 on the v5e), or that it
    had no device to plan for (0)."""
    import run_report

    plan = {
        "loop.plan": {"layers": 8, "passes": 4, "block_applications": 32},
        "share.plan": {"share_chips": 8, "share_rank": 0, "experts_held": 8,
                       "experts_total": 64, "vocab_held": 19360, "vocab_total": 154880},
    }[kind]
    proj = 0
    if kept_proj is not None:
        proj = kept_proj * 92_274_688
        plan.update(
            kept_proj_applications=kept_proj, kept_proj_bytes=proj,
            capacity_bytes=16 * 2**30 if kept_proj else None,
            planned_bytes=12_457_918_476 + proj, reserve_bytes=2400 * 2**20)
    tdir = tmp_path / "telemetry"
    os.makedirs(tdir)
    recs = [
        {"kind": "clock", "rank": 0, "t": 0.0, "unix": 0.0, "mono": 0.0},
        {"kind": "kernel.select", "rank": 0, "t": 1.0, "op": "flash_attn",
         "impl": "pallas", "requested": "auto"},
        {"kind": kind, "rank": 0, "t": 1.0, **plan,
         "kept_bytes": 32 * 2**25 + 2**30 + 2155872256 + proj, "kept_branch_bytes": 2**30,
         "kept_flash_bytes": 2155872256,
         "recomputed": "every block, from its float32 input and the flash "
                       "kernel's output, log-sum-exp, q, k and v"},
        {"kind": "span", "rank": 0, "t": 1.0, "v": 1, "name": "step",
         "t0": 0.0, "dur": 0.01, "track": "pipeline", "phase": "train"},
    ]
    with open(tdir / "rank00000.jsonl", "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    rep = run_report.build_report(str(tmp_path))
    kern = rep["kernels"]
    assert kern[kind.replace(".", "_")]["kept_flash_bytes"] == 2155872256
    assert kern["share_plan" if kind == "loop.plan" else "loop_plan"] is None
    run_report._print_report(rep)
    printed = capsys.readouterr().out
    assert where in printed
    assert "recomputed: every block, from its float32 input and the flash" in printed
    assert (f"kept {4104 + proj / 2**20:.1f} MiB a step, 2056.0 of them the flash "
            "kernel's output, log-sum-exp, q, k and v, 1024.0 the branches' "
            "outputs") in printed
    assert ("the MLP's two products" in printed) == (kept_proj is not None)
    if kept_proj:
        assert kern["loop_plan"]["kept_proj_applications"] == 23
        assert ("outputs, 2024.0 the MLP's two products in the last 23 applications "
                "(planned 13.58 GiB of 16.00 less a reserve of 2.34)") in printed
    elif kept_proj == 0:
        assert ("0.0 the MLP's two products in the last 0 applications "
                "(no device to plan for)") in printed


def test_bench_index_kernel_series_and_resnet50_reference(chip_bench_root):
    """BENCH_r09's kernel_* series must ride the index WITHOUT touching
    the img/s regression reference (the PR 8 clobbering lesson): the
    resnet50 throughput series still sources the newest chip record
    after regeneration, and run_report's gate extractor still reads it."""
    import bench_history
    import run_report

    root, _, copy_in = chip_bench_root
    copy_in("BENCH_r09.json")
    index = bench_history.build_index(root)
    series = index["series"]
    kernel_series = [k for k in series if k.startswith("kernel_")]
    assert kernel_series, "BENCH_r09.json kernel series missing"
    for k in kernel_series:
        assert "images_per_sec" not in k and "img_per_sec" not in k
    ref = series["resnet50_train_images_per_sec_per_chip"][-1]
    assert ref["source"] == "BENCH_r05.json"
    gates = run_report.comparable_metrics(index)
    assert gates["img_per_sec"] == pytest.approx(ref["value"])


def test_bench_r09_artifact_committed():
    """The acceptance artifact: BENCH_r09.json carries the per-kernel
    A/B matrix with the roofline movement and the recorded caveat."""
    root = os.path.join(os.path.dirname(__file__), "..")
    with open(os.path.join(root, "BENCH_r09.json")) as f:
        doc = json.load(f)
    assert "cost_analysis" in doc["caveat"] or "custom call" in doc["caveat"]
    for name in ("opt_update_sgd", "opt_update_adamw", "decode_attn",
                 "conv_epilogue"):
        row = doc["kernels"][name]
        assert row["bytes_ratio_xla_over_pallas"] > 1.0
        assert row["pallas"]["intensity"] > row["xla"]["intensity"]
    assert doc["kernels"]["opt_update_sgd"]["bit_exact"]
    assert doc["kernels"]["opt_update_adamw"]["bit_exact"]
    for label in ("efficientnet_b0_train_opt_update", "gen_decode_b4_c256"):
        row = doc["step_ab"][label]
        assert row["intensity_with_kernel"] > row["intensity_xla"]
