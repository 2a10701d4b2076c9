"""The plain float32 references against the program's models at tiny sizes,
and the operation counts against the published figures."""

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness.discovery import Catalog
from distribuuuu_tpu.models import regnet as program_regnet
from distribuuuu_tpu.models import resnet as program_resnet

CATALOG = Catalog()


def _program_outputs(model, images_u8):
    """(variables, train logits, eval logits) of the program's model in
    float32 on normalized pixels. The variables are not the initial ones
    (zero BN scales and unit variances would hide most of the network):
    zero-mean kernels, positive scales and statistics."""
    from distribuuuu_tpu.data.transforms import normalize_in_graph

    shapes = flax.linen.meta.unbox(jax.eval_shape(
        lambda: model.init(
            jax.random.key(0), jnp.zeros(images_u8.shape, jnp.float32), train=False
        )
    ))

    def fill(leaf):
        i = jnp.arange(leaf.size, dtype=jnp.float32).reshape(leaf.shape)
        return 0.6 + 0.3 * jnp.cos(i) if leaf.ndim == 1 else 0.1 * jnp.cos(0.7 * i)

    @jax.jit
    def outputs(images_u8):
        x = normalize_in_graph(images_u8)
        variables = jax.tree.map(fill, shapes)
        train_logits, _ = model.apply(
            variables, x, train=True, mutable=["batch_stats"]
        )
        return variables, train_logits, model.apply(variables, x, train=False)

    return outputs(images_u8)


def _reference_outputs(name, variables, images, architecture, bn_group):
    reference = CATALOG.reference(name)

    @jax.jit
    def outputs(variables, images):
        return [
            reference.logits(
                variables["params"], variables["batch_stats"], images,
                architecture=architecture, train=train, bn_group=bn_group,
            )
            for train in (True, False)
        ]

    return outputs(variables, images)


def _assert_agree(got, want):
    """float32 on both sides, logits of magnitude ~1. Inference is the same
    arithmetic (1e-6). In training the program's one-pass variance
    E[d^2] - E[d]^2 cancels where the reference's two-pass one does not; on
    these contrived weights that is up to 1e-3 — two orders under the bf16
    tolerance the drivers hold the real sizes to."""
    train_got, eval_got = got
    train_want, eval_want = want
    np.testing.assert_allclose(eval_got, eval_want, atol=1e-5)
    np.testing.assert_allclose(train_got, train_want, atol=3e-3)
    assert float(np.abs(np.asarray(train_want)).max()) > 0.3


def _images(n, size):
    return jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (n, size, size, 3), np.uint8)
    )


def test_resnet_reference_agrees_with_the_program():
    """Bottleneck blocks, with and without a projection shortcut, ghost BN in
    groups of 4; the basic block is held to the reference by the driver's own
    check in every rehearsal (resnet18)."""
    stage_blocks = [2, 1]
    model = program_resnet.ResNet(
        block=program_resnet.Bottleneck, layers=stage_blocks, num_classes=7,
        dtype=jnp.float32, bn_group=4,
    )
    images = _images(8, 16)
    variables, *want = _program_outputs(model, images)
    got = _reference_outputs(
        "resnet", variables, images,
        {"block": "Bottleneck", "stage_blocks": stage_blocks}, 4,
    )
    _assert_agree(got, want)


def test_regnet_reference_agrees_with_the_program():
    model = program_regnet.RegNet(
        w_a=8.0, w_0=16, w_m=2.0, depth=4, group_w=8, se_ratio=0.25,
        num_classes=7, stem_w=8, dtype=jnp.float32, bn_group=4,
    )
    widths, depths = program_regnet.generate_widths(8.0, 16, 2.0, 4)
    assert len(depths) >= 2 and max(depths) >= 2  # both kinds of block
    images = _images(8, 32)
    variables, *want = _program_outputs(model, images)
    got = _reference_outputs(
        "regnet", variables, images,
        {"stage_depths": depths, "group_width": 8}, 4,
    )
    _assert_agree(got, want)


@pytest.mark.parametrize("config,published_gmacs", [
    ("resnet50", 4.09),      # torchvision's count for ResNet-50 at 224
    ("regnety_160", 15.96),  # the paper's "16GF"
])
def test_operation_counts_match_the_published_figures(config, published_gmacs):
    cfg = CATALOG.config(config)
    costs = CATALOG.costs(cfg["costs"])
    gmacs = costs.forward_macs_per_item(cfg["architecture"]) / 1e9
    assert gmacs == pytest.approx(published_gmacs, rel=0.005)
    assert CATALOG.costs("common").train_flops(1000) == 3 * 2 * 1000


def test_configs_state_the_sizes_the_program_builds():
    """Parameter counts of the configuration files equal the program's."""
    from distribuuuu_tpu import models

    for name in ("resnet50", "regnety_160"):
        cfg = CATALOG.config(name)
        model = models.build_model(cfg["program"]["arch"], num_classes=1000)
        shapes = jax.eval_shape(
            lambda m=model: m.init(jax.random.key(0), jnp.zeros((1, 224, 224, 3)), train=False)
        )
        count = sum(x.size for x in jax.tree.leaves(shapes["params"]))
        assert count == cfg["architecture"]["parameters"]


def test_opt_update_bytes_are_one_pass():
    costs = CATALOG.costs("opt_update")
    # float32 parameters, gradients and one momentum: read 3, write 2
    assert costs.one_pass_bytes(400, 400, 400) == 5 * 400
    assert costs.roofline_seconds(819e9, {"hbm_bytes_per_s": 819e9}) == 1.0


@pytest.mark.parametrize("seed", [7, 2**31 - 1, 2**31 + 7, 3_500_000_703])
def test_the_device_batch_takes_any_seed_the_contract_allows(seed):
    """``drivers/train_step.make_batch`` hands the seed to its jitted draw as
    an int32; a seed of 2**31 or more (the driver's are large) overflowed it
    and killed the run. It is taken modulo 2**31: a seed under 2**31 gives
    the bits it gave, a larger one those of its remainder."""
    train_step = CATALOG.driver("train_step")
    here = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    batch = train_step.make_batch(seed, 4, 8, 10, {"image": here, "label": here})
    assert batch["image"].shape == (4, 8, 8, 3) and batch["image"].dtype == jnp.uint8
    as_before = seed if seed < 2**31 else seed - 2**31
    k_img, k_lbl = jax.random.split(
        jax.random.fold_in(jax.random.key(np.int32(as_before)), 1))
    assert np.array_equal(batch["image"], jax.random.randint(
        k_img, (4, 8, 8, 3), 0, 256, jnp.int32).astype(jnp.uint8))
    assert np.array_equal(batch["label"], jax.random.randint(
        k_lbl, (4,), 0, 10, jnp.int32))
