"""Every kernel ``KERNELS.* auto`` (and ViT's ``auto`` attention) can
select on a TPU must LOWER for the TPU at the main-path shapes — on the
CPU, before it costs chip budget.

``jit(f).trace(...).lower(lowering_platforms=("tpu",))`` runs the Pallas
TPU front end: it refuses a block whose last two dims are neither
(8, 128)-divisible nor whole, a scalar in the wrong memory space, an op
with no Mosaic rule. That is how ``decode_attn`` was found broken without
a chip (PERF.md "Bring-up on the chip tool"). It says nothing about
whether Mosaic then COMPILES the kernel — ``chip_smoke.py`` does.
"""

import jax
import jax.numpy as jnp
import pytest

from distribuuuu_tpu.ops import flash_attention as fa
from distribuuuu_tpu.ops.pallas import conv_epilogue, decode_attn, opt_update


def _lowers_for_tpu(fn, *avals) -> None:
    text = jax.jit(fn).trace(*avals).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("b,h,c,d", [
    (1, 4, 256, 32), (2, 4, 256, 32), (4, 4, 256, 32),  # gpt_nano tiles
    (1, 4, 1, 32),     # the T=1 prefill of a one-token prompt (C = 1)
    (8, 16, 1024, 128),                                 # a published width
])
def test_decode_attn_lowers(b, h, c, d, cache_dtype):
    cache = jax.ShapeDtypeStruct((b, h, c, d), cache_dtype)
    _lowers_for_tpu(
        lambda q, k, v, n: decode_attn.decode_attention(
            q, k, v, n, scale=d ** -0.5, blk_k=128),
        jax.ShapeDtypeStruct((b, h, d), cache_dtype), cache, cache,
        jax.ShapeDtypeStruct((b,), jnp.int32),
    )


# a conv, a bias-sized vector and the classifier leaf of ResNet-50
LEAVES = [(3, 3, 64, 64), (64,), (2048, 1000)]


@pytest.mark.parametrize("shape", LEAVES)
@pytest.mark.parametrize("trace_dtype", [jnp.float32, jnp.bfloat16])
def test_opt_update_sgd_lowers(shape, trace_dtype):
    _lowers_for_tpu(
        lambda p, g, t, lr: opt_update.sgd_leaf(
            p, g, t, lr, wd=5e-5, mom=0.9, nesterov=True, interpret=False),
        _f32(*shape), _f32(*shape),
        jax.ShapeDtypeStruct(shape, trace_dtype), _f32(),
    )


@pytest.mark.parametrize("shape", LEAVES)
def test_opt_update_adamw_lowers(shape):
    _lowers_for_tpu(
        lambda p, g, m, v, lr, c1, c2: opt_update.adamw_leaf(
            p, g, m, v, lr, c1, c2, b1=0.9, b2=0.999, eps=1e-8, wd=0.01,
            interpret=False),
        *[_f32(*shape)] * 4, _f32(), _f32(), _f32(),
    )


@pytest.mark.parametrize("m,cin,cout", [
    (128 * 56 * 56, 64, 256),   # ResNet-50 stage-1 expand, eval batch 128
    (128 * 7 * 7, 2048, 512),   # ResNet-50 stage-4 reduce
    (8 * 7 * 7, 320, 1280),     # EfficientNet-B0 head, serve bucket 8
])
def test_conv_epilogue_lowers(m, cin, cout):
    bf16 = jnp.bfloat16
    _lowers_for_tpu(
        lambda x, w, a, c: conv_epilogue.conv1x1_bn_act(
            x, w, a, c, "relu", interpret=False),
        jax.ShapeDtypeStruct((m, cin), bf16),
        jax.ShapeDtypeStruct((1, 1, cin, cout), bf16),
        _f32(cout), _f32(cout),
    )


def test_flash_fwd_bwd_lowers():
    qkv = jax.ShapeDtypeStruct((2, 4, 1024, 64), jnp.bfloat16)

    def loss(q, k, v):
        return fa.flash_attention(
            q, k, v, causal=True, interpret=False).astype(jnp.float32).sum()

    _lowers_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)
