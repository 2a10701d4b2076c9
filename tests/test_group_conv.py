"""UnrolledGroupConv (the TPU-friendly grouped-conv path in ConvBN): same
canonical parameter as the fused feature_group_count lowering, same outputs,
and the width-based auto-selection."""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from distribuuuu_tpu.models.layers import ConvBN
import pytest


def _conv_bn(groups, features=256, stride=1):
    return ConvBN(
        features, (3, 3), stride, groups=groups, use_bn=False,
        dtype=jnp.float32,
    )


def _kernel_of(variables):
    kernel = variables["params"]["Conv_0"]["kernel"]
    return getattr(kernel, "unbox", lambda: kernel)()


@pytest.mark.parametrize("what", ["output", "grads"])
@pytest.mark.parametrize(
    "stride,groups,width",
    [
        (1, 4, 64),
        (2, 2, 128),
        (2, 2, 112),  # RegNetY-16GF's group width; its stride-2 backward
                      # is the hot op of regnety_160.train (ROADMAP S7)
    ],
)
def test_unrolled_matches_feature_group_count(stride, groups, width, what):
    """The path ``regnety_160.train`` runs — ConvBN's per-group slice loop
    over ONE canonical ``(kh, kw, in/G, out)`` kernel — against
    ``lax.conv_general_dilated(feature_group_count=G)`` on the same
    kernel: the output, and the gradients to input and kernel (so the
    same variables and checkpoints drive either lowering)."""
    C = groups * width
    mod = _conv_bn(groups, features=C, stride=stride)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 8, 8, C)), jnp.float32)
    variables = mod.init(jax.random.key(0), x)
    kernel = _kernel_of(variables)
    assert kernel.shape == (3, 3, width, C)

    def unrolled(xx, kk):
        boxed = jax.tree.map(lambda _: kk, variables)
        return mod.apply(boxed, xx)

    def fused(xx, kk):
        return lax.conv_general_dilated(
            xx, kk, (stride, stride), [(1, 1), (1, 1)],
            feature_group_count=groups,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )

    if what == "output":
        got, ref = [unrolled(x, kernel)], [fused(x, kernel)]
        assert got[0].shape == (2, 8 // stride, 8 // stride, C)
    else:
        ct = jnp.asarray(
            rng.standard_normal((2, 8 // stride, 8 // stride, C)), jnp.float32
        )
        got, ref = (
            jax.grad(lambda xx, kk: jnp.sum(f(xx, kk) * ct), argnums=(0, 1))(
                x, kernel
            )
            for f in (unrolled, fused)
        )
    for a, b in zip(got, ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4
        )


def test_width_gate_selects_the_right_path():
    """The ≥64-per-group gate: narrow (ResNeXt-style) groups stay on
    nn.Conv, wide (RegNet-style) groups go unrolled. Inspect the actual
    submodule types — both paths share param path/shape/output by design,
    so only the module tree reveals the selection."""
    kw = dict(console_kwargs={"width": 400})
    x_narrow = jnp.ones((1, 4, 4, 256), jnp.float32)
    types_narrow = str(
        _conv_bn(groups=32).tabulate(jax.random.key(0), x_narrow, **kw)
    )  # 8 per group
    assert "UnrolledGroupConv" not in types_narrow

    x_wide = jnp.ones((1, 4, 4, 256), jnp.float32)
    types_wide = str(
        _conv_bn(groups=4).tabulate(jax.random.key(0), x_wide, **kw)
    )
    assert "UnrolledGroupConv" in types_wide

    # and the narrow path still runs
    mod = _conv_bn(groups=32)
    variables = mod.init(jax.random.key(0), x_narrow)
    assert _kernel_of(variables).shape == (3, 3, 8, 256)
    assert mod.apply(variables, x_narrow).shape == (1, 4, 4, 256)


@pytest.mark.slow  # dominates the fast tier; full tier covers it
def test_unrolled_group_conv_composes_with_tensor_parallel():
    """The unrolled path slices the kernel's OUT dim, which TP shards over
    `model` — GSPMD must resolve slice-across-shard without error."""
    import distribuuuu_tpu.config as config
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.parallel import mesh as mesh_lib, sharding as sharding_lib
    from distribuuuu_tpu.utils.optim import construct_optimizer

    config.reset_cfg()
    cfg.MODEL.ARCH = "regnety_160"
    cfg.MODEL.NUM_CLASSES = 10
    cfg.DEVICE.COMPUTE_DTYPE = "float32"
    cfg.MESH.DATA, cfg.MESH.MODEL = 4, 2
    mesh = mesh_lib.build_mesh(data=4, model=2)
    model = trainer.build_model_from_cfg()
    state = trainer.create_train_state(model, jax.random.key(0), mesh, 64)
    step = trainer.make_train_step(model, construct_optimizer(), 5)
    rng = np.random.default_rng(0)
    hb = {
        "image": rng.standard_normal((8, 64, 64, 3)).astype(np.float32),
        "label": rng.integers(0, 10, size=(8,)).astype(np.int32),
        "mask": np.ones((8,), np.float32),
    }
    state, m = step(state, sharding_lib.shard_batch(mesh, hb))
    assert np.isfinite(float(m["loss"]))


def test_regnet_forward_still_correct():
    """RegNet (the arch the auto-selection targets) still runs and keeps its
    published param count (oracle: SURVEY.md §6 — 83.590M for regnety_160)."""
    from distribuuuu_tpu import models
    from distribuuuu_tpu.utils.metrics import count_parameters

    model = models.build_model(
        "regnety_160", num_classes=1000, dtype=jnp.float32
    )
    x = jnp.ones((1, 64, 64, 3), jnp.float32)
    variables = jax.eval_shape(
        lambda k: model.init(k, x, train=False), jax.random.key(0)
    )
    m_params, _ = count_parameters(variables["params"])
    assert abs(m_params - 83.590) < 0.01
