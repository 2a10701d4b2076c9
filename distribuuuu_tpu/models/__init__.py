"""Model registry (ref: /root/reference/distribuuuu/models/__init__.py:1-7).

The reference dispatches ``build_model(arch)`` through module globals with a
timm fallback at the call site (ref: trainer.py:123-128). timm does not exist
here; every baseline arch — including RegNet-X/Y and EfficientNet-B0, which
the reference outsources to timm — is implemented natively, so the registry
is closed and errors are explicit.
"""

from __future__ import annotations

from distribuuuu_tpu.models.resnet import (  # noqa: F401
    resnet18,
    resnet34,
    resnet50,
    resnet101,
    resnet152,
    resnext50_32x4d,
    resnext101_32x8d,
    wide_resnet50_2,
    wide_resnet101_2,
)
from distribuuuu_tpu.models.densenet import (  # noqa: F401
    densenet121,
    densenet161,
    densenet169,
    densenet201,
)
from distribuuuu_tpu.models.botnet import botnet50  # noqa: F401
from distribuuuu_tpu.models.regnet import (  # noqa: F401
    regnetx_160,
    regnety_160,
    regnety_320,
)
from distribuuuu_tpu.models.efficientnet import efficientnet_b0  # noqa: F401
from distribuuuu_tpu.models.vit import (  # noqa: F401
    vit_small,
    vit_tiny,
    vit_tiny_moe,
)
from distribuuuu_tpu.models.gpt import gpt_nano, gpt_nano_moe  # noqa: F401
from distribuuuu_tpu.models.olmoe import olmoe_1b_7b, olmoe_tiny  # noqa: F401
from distribuuuu_tpu.models.ouro import ouro_2_6b, ouro_tiny  # noqa: F401
from distribuuuu_tpu.models.glm_moe import glm_4_7_flash, glm_moe_tiny  # noqa: F401
from distribuuuu_tpu.models.lfm2_moe import lfm2_24b_a2b, lfm2_moe_tiny  # noqa: F401
from distribuuuu_tpu.models.afmoe import afmoe_tiny, trinity_mini  # noqa: F401
from distribuuuu_tpu.models.sdar_moe import sdar_30b_a3b, sdar_moe_tiny  # noqa: F401
from distribuuuu_tpu.models.traits import ArchTraits

_REGISTRY = {}


def register_model(fn):
    _REGISTRY[fn.__name__] = fn
    return fn


for _fn in (
    resnet18,
    resnet34,
    resnet50,
    resnet101,
    resnet152,
    resnext50_32x4d,
    resnext101_32x8d,
    wide_resnet50_2,
    wide_resnet101_2,
    densenet121,
    densenet161,
    densenet169,
    densenet201,
    botnet50,
    regnetx_160,
    regnety_160,
    regnety_320,
    efficientnet_b0,
    # TPU-native extensions (no reference analogue): seq-parallel-capable ViT
    vit_tiny,
    vit_small,
    # expert-parallel MoE variant (ops/moe.py over the model axis)
    vit_tiny_moe,
    # decoder-only LM workload plane (models/gpt.py, ISSUE 12): token
    # batches, causal attention, next-token CE through the same trainer
    gpt_nano,
    gpt_nano_moe,
    # OLMoE (models/olmoe.py): rotary / RMSNorm / QK-norm attention and
    # dropless sorted top-k gated experts, at the published sizes and tiny
    olmoe_1b_7b,
    olmoe_tiny,
    # Ouro (models/ouro.py): a stack of sandwich-normed dense blocks run
    # four times over shared weights, a learned exit gate and its loss
    ouro_2_6b,
    ouro_tiny,
    # GLM-4.7-Flash (models/glm_moe.py): latent attention, a sigmoid router
    # with a balancing bias, a shared expert, the MTP module; one chip's
    # share of an expert-parallel group
    glm_4_7_flash,
    glm_moe_tiny,
    # LFM2-24B-A2B (models/lfm2_moe.py): a stack of two layer kinds by the
    # published pattern (gated short convolutions and grouped-query
    # attention), leading dense layers, GLM's mixture without a shared
    # expert, a tied head; one chip's share of an expert-parallel group
    lfm2_24b_a2b,
    lfm2_moe_tiny,
    # Trinity-Mini (models/afmoe.py): window and full attention layers in
    # one stack by the published pattern, a gated attention output, four
    # norms a block, GLM's mixture with its shared expert, an untied head;
    # one chip's share of an expert-parallel group
    trinity_mini,
    afmoe_tiny,
    # SDAR-30B-A3B-Chat (models/sdar_moe.py): Qwen3-MoE's block trained by
    # block diffusion over a noised and a clean copy of every sequence (the
    # mask inside the flash kernels), a softmax router renormalised over its
    # choices in GLM's mixture, a loss over the masked positions alone; one
    # chip's share of an expert-parallel group
    sdar_30b_a3b,
    sdar_moe_tiny,
):
    register_model(_fn)


def available_models():
    return sorted(_REGISTRY)


def traits(arch: str) -> ArchTraits:
    """What ``arch`` declares of itself (models/traits.py); the defaults for
    an arch that declares nothing, or that the registry lacks."""
    return getattr(_REGISTRY.get(str(arch)), "traits", None) or ArchTraits()


def build_model(arch: str, **kwargs):
    """Construct a model by name (≙ models.build_model + timm fallback)."""
    if arch not in _REGISTRY:
        raise KeyError(
            f"Unknown arch '{arch}'. Available: {', '.join(available_models())}. "
            "This zoo is closed — there is no timm fallback (ref: "
            "trainer.py:123-128); register a custom arch with "
            "@distribuuuu_tpu.models.register_model (see README 'Custom "
            "architectures')."
        )
    return _REGISTRY[arch](**kwargs)
