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
from distribuuuu_tpu.ops import moe as moe_ops
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
# every other block shape the benchmark's cells give the kernel (RegNetY's
# widths are no lane multiples): 308 rows over a ragged last block, a last
# dimension of 224 and of 8, a stem's second-minor 3, RegNetY's classifier
SGD_LEAVES = LEAVES + [
    (1, 1, 308, 1232), (3, 3, 112, 224), (1, 1, 224, 8), (7, 7, 3, 64),
    (3024, 1000),
]
# AdamW holds four operands a block: the widest leaf and a ragged one
ADAMW_LEAVES = LEAVES + [(1, 1, 1232, 3024), (1, 1, 308, 1232)]


def _sgd_text(shape, trace_dtype=jnp.float32) -> str:
    return jax.jit(
        lambda p, g, t, lr: opt_update.sgd_leaf(
            p, g, t, lr, wd=5e-5, mom=0.9, nesterov=True, interpret=False)
    ).trace(
        _f32(*shape), _f32(*shape),
        jax.ShapeDtypeStruct(shape, trace_dtype), _f32(),
    ).lower(lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("shape", [(3, 3, 256, 256), (64,), (7, 7, 3, 64)])
def test_opt_update_reaches_the_kernel_through_reshapes_alone(shape):
    """A leaf goes to the kernel as a 3-D view (a 1-D leaf as [1, 1, n]):
    reshapes that leave the two tiled dimensions alone, so bitcasts on the
    TPU. Nothing is padded and nothing sliced back."""
    text = _sgd_text(shape)
    assert "tpu_custom_call" in text and "stablehlo.reshape" in text
    assert "stablehlo.pad" not in text and "stablehlo.slice" not in text


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a described (not attached) v5e host: the installed
    XLA:TPU and Mosaic compile for it. Described inside the fixture, so only
    the worker that runs this file loads the TPU's library."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices[0]


@pytest.mark.parametrize("shape,order", [
    ((3, 3, 256, 256), (0, 1, 2, 3)),   # rests row-major
    ((2048, 1000), (1, 0)),             # ResNet-50's classifier: column-major
    ((1, 1, 256, 64), (0, 1, 3, 2)),    # 256 on the lanes, not 64
    ((1, 1, 224, 8), (0, 1, 3, 2)),     # RegNetY's first SE reduce
    ((1, 1, 308, 1232), (0, 2, 1, 3)),  # an SE expand: 308 in (1, 128) tiles
    ((7, 7, 3, 64), (0, 2, 1, 3)),      # ResNet-50's stem
])
def test_opt_update_compiles_for_the_v5e_with_no_copy_of_the_leaf(
        v5e_chip, shape, order):
    """The whole point of the view: compiled for the chip, with the state
    resting in the layouts the TPU's client gives it and donated as the
    trainer donates it, the program around the Mosaic call holds no copy,
    transpose, pad or slice of the leaf — the kernel reads and writes the
    leaf where it rests."""
    from jax.sharding import SingleDeviceSharding

    assert opt_update._resting_order(shape, jnp.float32, v5e_chip) == order
    chip = SingleDeviceSharding(v5e_chip)
    leaf = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip)
    compiled = jax.jit(
        lambda p, g, t, lr: opt_update.sgd_leaf(
            p, g, t, lr, wd=5e-5, mom=0.9, nesterov=True, interpret=False,
            device=v5e_chip),
        donate_argnums=(0, 2),
    ).lower(
        leaf, leaf, leaf, jax.ShapeDtypeStruct((), jnp.float32, sharding=chip)
    ).compile().as_text()
    entry = compiled[compiled.index("\nENTRY "):]
    view = opt_update._plan(shape, [jnp.float32], v5e_chip)[1]
    of_the_leaf = tuple(
        "f32[" + ",".join(map(str, dims)) + "]" for dims in (shape, view))
    moving = ("copy(", "transpose(", "pad(", "slice(", "fusion(", "reshape(")
    moved = [
        line.strip()[:120] for line in entry.splitlines()
        if any(f" {op}" in line for op in moving)
        and line.split(" = ")[1].startswith(of_the_leaf)
    ]
    assert "tpu_custom_call" in entry and not moved, moved


@pytest.mark.parametrize("shape", SGD_LEAVES)
@pytest.mark.parametrize("trace_dtype", [jnp.float32, jnp.bfloat16])
def test_opt_update_sgd_lowers(shape, trace_dtype):
    assert "tpu_custom_call" in _sgd_text(shape, trace_dtype)


@pytest.mark.parametrize("shape", ADAMW_LEAVES)
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


@pytest.mark.parametrize("data", [1, 8])
def test_flash_fwd_bwd_lowers(data):
    """Bare, and per data rank under the caller's mesh (a bare Mosaic call
    in a program over several devices is what GSPMD refuses)."""
    from distribuuuu_tpu.parallel import mesh as mesh_lib

    qkv = jax.ShapeDtypeStruct((8, 4, 1024, 64), jnp.bfloat16)
    mesh = mesh_lib.build_mesh(data=data) if data > 1 else None

    def loss(q, k, v):
        return fa.flash_attention(
            q, k, v, causal=True, interpret=False, mesh=mesh
        ).astype(jnp.float32).sum()

    _lowers_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)


@pytest.mark.parametrize("shape,causal", [
    ((1, 16, 4096, 128), True),    # ouro_2_6b.train_seq4096
    ((4, 16, 4096, 128), True),    # olmoe_1b_7b.train_seq4096
    ((1, 20, 8192, 256), True),    # glm_4_7_flash.train_seq8192: two lane tiles a head
    ((4, 3, 4096, 64), False),     # ViT-Ti at 1024px
    ((1, 3, 4097, 64), False),     # ... with a class token: padded keys
])
def test_flash_compiles_for_the_v5e_at_the_blocks_the_shape_chooses(
        v5e_chip, shape, causal):
    """Mosaic takes the forward and the fused backward at the blocks
    ``choose_blocks`` gives the shape, under the VMEM limit the calls ask
    for: one backward kernel, its dq accumulator resident."""
    from jax.sharding import SingleDeviceSharding

    qkv = jax.ShapeDtypeStruct(
        shape, jnp.bfloat16, sharding=SingleDeviceSharding(v5e_chip))

    def loss(q, k, v):
        return fa.flash_attention(
            q, k, v, causal=causal, interpret=False).astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        qkv, qkv, qkv).compile().as_text()
    calls = [line.split(" = ")[0] for line in text.splitlines()
             if "custom-call(" in line and "dtpu_flash_" in line]
    assert len(calls) == 4 and sum("dtpu_flash_bwd" in c for c in calls) == 1, calls
    L, d = shape[2:]
    blk_q, blk_k, lp = fa._resolve_blocks(L, *fa.choose_blocks(L, d, causal))
    assert fa.fits_vmem(L, d)
    assert fa._vmem_bytes(lp, d, 2, blk_q, blk_k) < fa._VMEM_LIMIT


def test_grouped_flash_compiles_for_the_v5e_without_a_repeated_key_or_value(v5e_chip):
    """``lfm2_24b_a2b.train_seq8192``'s attention: 32 query heads on 8
    key/value heads of 64 at 2 x 8192 tokens, causal. Mosaic takes both
    kernels with K and V at their own 8 heads (no broadcast of them in the
    program), and dK and dV come out a key/value head: one XLA reduction over
    the group behind the backward kernel."""
    from jax.sharding import SingleDeviceSharding

    chip = SingleDeviceSharding(v5e_chip)
    q = jax.ShapeDtypeStruct((2, 32, 8192, 64), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((2, 8, 8192, 64), jnp.bfloat16, sharding=chip)

    def loss(q, k, v):
        return fa.flash_attention(
            q, k, v, causal=True, interpret=False).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and "dtpu_flash_" in line]
    assert len(calls) == 4 and sum("dtpu_flash_bwd" in c for c in calls) == 1
    # the forward kernel's K and V operands are the 16 (batch, kv head) rows
    forward = [c for c in calls if "dtpu_flash_fwd" in c][0]
    assert forward.count("bf16[16,8192,64]") == 2 and forward.count("bf16[64,8192,64]") >= 2
    assert [tuple(x.shape) for x in compiled.out_info] == [
        (2, 32, 8192, 64), (2, 8, 8192, 64), (2, 8, 8192, 64)]
    assert fa.fits_vmem(8192, 64)


def _step_for_the_v5e(v5e_chip, monkeypatch, yaml: str, batch: tuple,
                      compiled: bool = True, **lm):
    """``config/<yaml>.yaml``'s train step at ``batch`` tokens (sequences,
    length) with the ``LM`` keys ``lm`` over it, compiled for the chip:
    ``(what lowering.lower returned, the abstract state, the executable)``,
    or with ``compiled=False`` the batch's avals in the executable's place."""
    from jax.sharding import SingleDeviceSharding

    import distribuuuu_tpu.config as config
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.ops import pallas as kernel_tier
    from distribuuuu_tpu.parallel import mesh as mesh_lib
    from distribuuuu_tpu.parallel.partition import lowering, topology
    from distribuuuu_tpu.utils.optim import construct_optimizer

    # the tier asks the live backend (a CPU with 8 devices here): steer it to
    # what it resolves to on one chip
    monkeypatch.setattr(kernel_tier, "interpret_mode", lambda: False)
    monkeypatch.setattr(kernel_tier, "compiled_across_devices", lambda: False)
    config.reset_cfg()
    config.merge_from_file(f"config/{yaml}.yaml")
    for key, value in lm.items():
        setattr(cfg.LM, key, value)
    cfg.MESH.DATA, cfg.KERNELS.OPT_UPDATE = 1, "pallas"
    try:
        layout = topology.from_cfg(cfg, n_devices=1)
        lowered = lowering.lower(
            trainer.build_model_from_cfg(layout), construct_optimizer(), 5,
            mesh=mesh_lib.build_mesh(data=1, devices=[v5e_chip]),
            topology=layout, im_size=cfg.TRAIN.IM_SIZE,
        )
        state, avals = lowered.abstract_args(batch[0])
    finally:
        config.reset_cfg()
    chip = SingleDeviceSharding(v5e_chip)
    avals = {k: jax.ShapeDtypeStruct(batch, jnp.int32, sharding=chip) for k in avals}
    if not compiled:
        return lowered, state, avals
    return lowered, state, lowered.train_step.lower(state, avals).compile()


def _dtpu_calls(text: str) -> dict:
    """Kernel name -> the ``op_name`` of each of its calls in a compiled
    program's text."""
    calls = {}
    for line in text.splitlines():
        if "custom-call(" in line and "dtpu_" in line:
            name = line.split(" = ")[0].split()[-1].lstrip("%").rsplit(".", 1)[0]
            calls.setdefault(name, []).append(line.split('op_name="')[1].split('"')[0])
    return calls


def test_lfm2_step_compiles_for_the_v5e_under_its_scopes(v5e_chip, monkeypatch):
    """The step of ``lfm2_24b_a2b.train_seq8192`` (``config/lfm2_24b_a2b.yaml``:
    published widths, 2 x 8192 tokens, 8 of 64 experts and 8,192 vocabulary
    rows held) compiled for the chip at the dense conv layer and the
    attention mixture (published layers 1..2; the cell's 1..5 compile in a
    minute here). What the benchmark's readers find in it: every scope they
    sum, ``short_conv_gate`` inside ``short_conv`` and no matmul under it,
    the grouped flash kernels and the six grouped matmuls by name, and no
    ``while``; with ``LM.RECOMPUTE`` both block kinds run again in the
    backward, without the flash forward kernel."""
    from benchmark.harness.trace import in_scope, op_names_from_hlo

    lowered, state, compiled = _step_for_the_v5e(
        v5e_chip, monkeypatch, "lfm2_24b_a2b", (2, 8192),
        FIRST_LAYER=1, LAYERS=2, RECOMPUTE=True)
    model = lowered.model
    assert model.layer_kinds == ("conv", "full_attention") and model.dense_here == 1
    assert model.held == (0, 8) and model.vocab_held == 8192
    text = compiled.as_text()
    assert " while(" not in text and " conditional(" not in text
    assert "ragged-dot" not in text  # the held experts run the Pallas kernels
    paths = list(op_names_from_hlo(text).values())
    for scope in ("fwd", "bwd", "short_conv", "short_conv_gate", "attn", "mlp", "moe",
                  "moe_route", "moe_experts", "lm_head", "optimizer_update",
                  "opt_kernel", "rematted_computation"):
        assert any(in_scope(p, scope) for p in paths), scope
    gate = [p for p in paths if in_scope(p, "short_conv_gate")]
    assert all(in_scope(p, "short_conv") for p in gate)
    assert not any(p.endswith("dot_general") for p in gate)  # no matmul in it
    assert any(in_scope(p, "short_conv") and p.endswith("in_proj/dot_general")
               for p in paths)
    calls = _dtpu_calls(text)
    flash = {k: len(v) for k, v in calls.items() if "flash" in k}
    assert (flash["dtpu_flash_fwd"], flash["dtpu_flash_bwd"]) == (1, 1)
    assert not any(in_scope(p, "rematted_computation") or in_scope(p, "bwd")
                   for p in calls["dtpu_flash_fwd"])
    recomputed = [p for p in paths if in_scope(p, "rematted_computation")]
    assert any(in_scope(p, "short_conv_gate") for p in recomputed)
    assert any(in_scope(p, "moe_route") for p in recomputed)
    # the kept q, k and v spare the recomputation v's projection, the rotary
    # and the head layouts; q's and k's projections run again, because the
    # per-head norm that follows them reads their output in its own backward;
    # the kept mixers' outputs spare it the conv's out_proj and W_o, and what
    # nothing reads (the FFNs' outputs: no norm follows them) is not made
    # again either: the dense MLP's down_proj and the combine
    for last in ("v_proj", "out_proj", "o_proj", "down_proj"):
        assert any(f"{last}/dot_general" in p for p in paths), last
        assert not any(f"{last}/dot_general" in p for p in recomputed), last
    for proj in ("q_proj", "k_proj"):
        assert any(f"{proj}/dot_general" in p for p in recomputed), proj
    gmm = {k: len(v) for k, v in calls.items() if "moe_gmm" in k}
    assert gmm == {
        "dtpu_moe_gmm_gate_up": 2, "dtpu_moe_gmm_fwd": 2, "dtpu_moe_gmm_act_bwd": 1,
        "dtpu_moe_gmm_dx_gate_up": 1, "dtpu_moe_gmm_dw_down": 1,
        "dtpu_moe_gmm_dw_gate_up": 1}
    _movers_of_held_mixtures(calls, in_scope, mixtures=1)
    # the conv layer's gates and filter: one call each way (the forward again
    # in the recomputation), under the scope the benchmark's readers sum
    conv = {k: v for k, v in calls.items() if "short_conv" in k}
    assert {k: len(v) for k, v in conv.items()} == {
        "dtpu_short_conv_fwd": 2, "dtpu_short_conv_bwd": 1}
    assert all(in_scope(p, "short_conv_gate") and in_scope(p, "short_conv")
               for v in conv.values() for p in v)
    # the tied embedding is ONE leaf and takes one AdamW call
    assert len(calls["dtpu_opt_update_adamw"]) == len(jax.tree.leaves(state.params))


def test_windowed_flash_compiles_for_the_v5e_at_a_group_of_eight(v5e_chip):
    """``trinity_mini.train_seq8192``'s sliding layers: 32 query heads on 4
    key/value heads of 128 at 2 x 8192 tokens, causal, a window of 2048.
    Mosaic takes both kernels with the walks bounded at both ends (traced
    loop bounds from the program id) and K and V at their own 4 heads; the
    resident set is the causal call's (the window changes no block)."""
    from jax.sharding import SingleDeviceSharding

    chip = SingleDeviceSharding(v5e_chip)
    q = jax.ShapeDtypeStruct((2, 32, 8192, 128), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((2, 4, 8192, 128), jnp.bfloat16, sharding=chip)

    def loss(q, k, v):
        return fa.flash_attention(
            q, k, v, causal=True, interpret=False, window=2048,
        ).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if "custom-call(" in line and "dtpu_flash_" in line]
    assert len(calls) == 4 and sum("dtpu_flash_bwd" in c for c in calls) == 1
    forward = [c for c in calls if "dtpu_flash_fwd" in c][0]
    assert forward.count("bf16[8,8192,128]") == 2 and forward.count("bf16[64,8192,128]") >= 2
    assert [tuple(x.shape) for x in compiled.out_info] == [
        (2, 32, 8192, 128), (2, 4, 8192, 128), (2, 4, 8192, 128)]
    assert fa.fits_vmem(8192, 128)
    assert fa.tile_counts(8192, 512, 512, True, 2048) == (70, 28)


def test_diffusion_flash_compiles_for_the_v5e_at_two_copies_of_8192_tokens(v5e_chip):
    """``sdar_30b_a3b.train_seq8192``'s layers: 32 query heads on 4 key/value
    heads of 128 over the 16,384 rows of a noised and a clean copy of one
    8192-token sequence, under the block-diffusion mask at blocks of 4 and of
    32 tokens. Mosaic takes both kernels with each walk as TWO ranges of tiles
    (traced loop bounds from the program id) and the mask as two compares of
    per-row and per-key codes; the resident set is a causal call's of 16,384
    rows and fits as it stands: no scan path."""
    from jax.sharding import SingleDeviceSharding

    chip = SingleDeviceSharding(v5e_chip)
    q = jax.ShapeDtypeStruct((1, 32, 16384, 128), jnp.bfloat16, sharding=chip)
    kv = jax.ShapeDtypeStruct((1, 4, 16384, 128), jnp.bfloat16, sharding=chip)
    for block in (4, 32):
        def loss(q, k, v, block=block):
            return fa.flash_attention(
                q, k, v, causal=True, interpret=False, diffusion_block=block,
            ).astype(jnp.float32).sum()

        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile()
        text = compiled.as_text()
        calls = [line for line in text.splitlines()
                 if "custom-call(" in line and "dtpu_flash_" in line]
        assert len(calls) == 4 and sum("dtpu_flash_bwd" in c for c in calls) == 1
        assert "16384,16384" not in text and " while(" not in text
        assert [tuple(x.shape) for x in compiled.out_info] == [
            (1, 32, 16384, 128), (1, 4, 16384, 128), (1, 4, 16384, 128)]
    assert fa.fits_vmem(16384, 128)
    assert fa.tile_counts(16384, 512, 512, True, None, 4) == (288, 48)


def _movers_of_held_mixtures(calls, in_scope, mixtures: int, normed_after=False):
    """A recomputed held mixture moves its rows through ``ops/pallas/
    moe_rows``: ``take`` forward, again under recomputation and as the
    combine's backward; ``combine`` forward and as the take's backward, and
    never under recomputation: it has no use for the mixture's output, and
    where a norm follows the mixture (``normed_after``, Trinity-Mini's), whose
    backward reads that output, the block keeps it (``models/ouro.recomputed``;
    before PR 45 the combine ran a third time there); a ``pack`` before each;
    all under ``moe_route``, where the benchmark's readers sum them."""
    del normed_after  # the same calls either way
    movers = {k: len(v) // mixtures for k, v in calls.items() if "moe_rows" in k}
    assert movers == {"dtpu_moe_rows_take": 3, "dtpu_moe_rows_combine": 2,
                      "dtpu_moe_rows_pack": 5}, movers
    for name in movers:
        assert all(in_scope(p, "moe_route") and not in_scope(p, "moe_experts")
                   for p in calls[name]), name
    assert not any(in_scope(p, "rematted_computation")
                   for p in calls["dtpu_moe_rows_combine"])


def _olmoe_experts(tokens=16384, d=2048, f=1024, experts=64, top=8):
    """``sorted_experts`` at the widths of ``olmoe_1b_7b.train_seq4096``
    (4 x 4096 tokens a step), the kernel arm forced compiled."""
    bf16 = jnp.bfloat16
    avals = (
        {"w_gate": _f32(experts, d, f), "w_up": _f32(experts, d, f),
         "w_down": _f32(experts, f, d)},
        jax.ShapeDtypeStruct((tokens, d), bf16), _f32(tokens, top),
        jax.ShapeDtypeStruct((tokens, top), jnp.int32),
    )

    def forward(params, x, weights, indices):
        return moe_ops.sorted_experts(
            params, x, weights, indices, interpret=False)

    return forward, avals


@pytest.mark.parametrize("direction", ["forward", "gradient"])
def test_moe_gmm_lowers_at_the_cells_shapes(direction):
    forward, avals = _olmoe_experts()
    fn = forward
    if direction == "gradient":
        def fn(params, x, weights, indices):
            return jax.grad(
                lambda *a: forward(*a, indices).astype(jnp.float32).sum(),
                argnums=(0, 1, 2))(params, x, weights)

    _lowers_for_tpu(fn, *avals)


def test_moe_gmm_compiles_for_the_v5e_under_its_scope(v5e_chip):
    """Mosaic takes all six calls at the cell's shapes (the resident weight
    blocks need more than a call's default 16 MiB of VMEM), and each, the
    backward's too, carries ``moe_experts`` in its ``op_name``: the
    benchmark's two MoE readers sum that scope."""
    from jax.sharding import SingleDeviceSharding

    from benchmark.harness.trace import in_scope
    from distribuuuu_tpu.ops.pallas import moe_gmm

    forward, avals = _olmoe_experts()
    chip = SingleDeviceSharding(v5e_chip)
    avals = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), avals)

    def step(params, x, weights, indices):
        return jax.value_and_grad(
            lambda *a: forward(*a, indices).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(params, x, weights)

    text = jax.jit(step).lower(*avals).compile().as_text()
    calls = [line for line in text.splitlines()
             if "custom-call(" in line and moe_gmm.NAME in line]
    names = {line.split(" = ")[0].split()[-1].lstrip("%").rsplit(".", 1)[0]
             for line in calls}
    assert names == {
        f"{moe_gmm.NAME}_{part}" for part in (
            "gate_up", "fwd", "act_bwd", "dx_gate_up", "dw_down", "dw_gate_up")
    }, names
    assert len(calls) == moe_gmm.CALLS_A_STEP
    for line in calls:
        op_name = line.split('op_name="')[1].split('"')[0]
        assert in_scope(op_name, "moe_experts"), op_name
    assert "ragged-dot" not in text


def test_short_conv_compiles_for_the_v5e_whole_in_and_whole_out(v5e_chip):
    """LFM2's gated short convolution at the cell's shape (``[2, 8192, 3 x
    2048]`` bf16, 3 taps), forward and backward: Mosaic takes the blocks of
    512 positions the whole ``3H`` wide, the halo tiles, the sublane rolls and
    the resident float32 ``dw`` block in the VMEM the calls ask for; ``bcu``
    reaches both calls as it came and ``dbcu`` leaves as ONE array: beside
    the two calls the program holds nothing the size of an activation."""
    from jax.sharding import SingleDeviceSharding

    from distribuuuu_tpu.ops import short_conv as op

    chip = SingleDeviceSharding(v5e_chip)
    bcu = jax.ShapeDtypeStruct((2, 8192, 3 * 2048), jnp.bfloat16, sharding=chip)
    w = jax.ShapeDtypeStruct((2048, 3), jnp.float32, sharding=chip)

    dy = jax.ShapeDtypeStruct((2, 8192, 2048), jnp.bfloat16, sharding=chip)

    def step(bcu, w, dy):
        y, vjp = jax.vjp(lambda bcu, w: op.gated_short_conv(bcu, w, False), bcu, w)
        return y, vjp(dy)

    text = jax.jit(step).lower(bcu, w, dy).compile().as_text()
    entry = [line.strip() for line in text[text.index("\nENTRY "):].splitlines()
             if " = " in line]
    calls = [line for line in entry if "custom-call(" in line]
    assert sorted(line.split(" = ")[0].lstrip("%").rsplit(".", 1)[0] for line in calls) == [
        "dtpu_short_conv_bwd", "dtpu_short_conv_fwd"]
    activations = [line[:160] for line in entry
                   if line.split(" = ")[1].startswith(("bf16[2,8192,", "f32[2,8192,"))
                   and not any(f" {kind}(" in line for kind in (
                       "custom-call", "get-tuple-element", "parameter"))]
    assert not activations, activations


def test_ssd_compiles_for_the_v5e_with_no_decay_matrix_and_no_chunk_state_beside_it(
        v5e_chip):
    """Mamba-2's chunked scan at ``nemotron_3_super_120b_a12b.train_seq8192``'s
    shape (``[1, 8192, 16, 64]`` bf16 on one group of a 128 state, 64 chunks
    of 128), forward and backward: Mosaic takes the chunk's blocks, the
    transposed-LHS contractions, the 128 x 128 transposes and the lane sums
    in the VMEM the calls ask for; and beside the two calls the program holds
    nothing the size of the decay matrices or of the chunks' states but the
    states entering each chunk, which go from one call to the other."""
    from jax.sharding import SingleDeviceSharding

    from distribuuuu_tpu.ops import ssd as op

    chip = SingleDeviceSharding(v5e_chip)
    B, S, H, P, G, N = 1, 8192, 16, 64, 1, 128
    f32, bf16 = jnp.float32, jnp.bfloat16

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    args = (aval((B, S, H, P), bf16), aval((B, S, H), f32), aval((H,), f32),
            aval((B, S, G, N), bf16), aval((B, S, G, N), bf16), aval((H,), f32))
    cotangents = (aval((B, S, H, P), f32), aval((B, H, P, N), f32))

    def step(args, cotangents):
        out, vjp = jax.vjp(lambda *t: op.ssd(*t, interpret=False), *args)
        return out, vjp(cotangents)

    text = jax.jit(step).lower(args, cotangents).compile().as_text()
    entry = [line.strip() for line in text[text.index("\nENTRY "):].splitlines()
             if " = " in line]
    calls = [line for line in entry if "custom-call(" in line]
    assert sorted(line.split(" = ")[0].lstrip("%").rsplit(".", 1)[0] for line in calls) == [
        "dtpu_ssd_bwd", "dtpu_ssd_fwd"]
    chunks = S // op.CHUNK
    assert not [line[:160] for line in entry if f"[{B},{chunks},{H}," in line
                or f",{op.CHUNK},{op.CHUNK}]" in line.split(" = ")[1].split("(")[0]]
    states = [line for line in entry if f"f32[{B},{chunks},{N},{H * P}]" in line.split("(")[0]]
    assert all("custom-call(" in line or "get-tuple-element(" in line for line in states)


@pytest.mark.parametrize("batch,seq,heads,rotary", [
    (1, 16384, 32, True), (1, 16384, 4, True), (2, 8192, 32, False), (2, 8192, 4, False),
], ids=["sdar_q", "sdar_k", "trinity_q_full", "trinity_k_full"])
def test_head_prologue_compiles_for_the_v5e_as_the_projection_wrote_it(
        v5e_chip, batch, seq, heads, rotary):
    """A q or k projection's way to the flash kernels at SDAR's and
    Trinity-Mini's shapes (heads of 128, bf16), forward and backward: Mosaic
    takes the row blocks of 512 the whole ``n D`` wide in, the ``[n, 512,
    128]`` head blocks out, the lane roll and the partial sums in the VMEM the
    calls ask for; ``t`` reaches both calls as it came (no transpose, no
    reshape that copies) and beside the two calls the program holds nothing
    the size of an activation."""
    from jax.sharding import SingleDeviceSharding

    from distribuuuu_tpu.ops import head_prologue as op

    chip = SingleDeviceSharding(v5e_chip)
    t = jax.ShapeDtypeStruct((batch, seq, heads * 128), jnp.bfloat16, sharding=chip)
    scale = jax.ShapeDtypeStruct((128,), jnp.float32, sharding=chip)
    positions = jax.ShapeDtypeStruct((seq,), jnp.int32, sharding=chip)
    dy = jax.ShapeDtypeStruct((batch, heads, seq, 128), jnp.bfloat16, sharding=chip)

    def step(t, scale, positions, dy):
        y, vjp = jax.vjp(lambda t, scale: op.head_prologue(
            t, scale, positions, heads=heads, eps=1e-6,
            theta=1e6 if rotary else None, interpret=False), t, scale)
        return y, vjp(dy)

    text = jax.jit(step).lower(t, scale, positions, dy).compile().as_text()
    entry = [line.strip() for line in text[text.index("\nENTRY "):].splitlines()
             if " = " in line]
    calls = [line for line in entry if "custom-call(" in line]
    assert sorted(line.split(" = ")[0].lstrip("%").rsplit(".", 1)[0] for line in calls) == [
        "dtpu_head_prologue_bwd", "dtpu_head_prologue_fwd"]
    activations = [line[:160] for line in entry
                   if line.split(" = ")[1].startswith((
                       f"bf16[{batch},{seq},", f"f32[{batch},{seq},",
                       f"bf16[{batch},{heads},", f"f32[{batch},{heads},"))
                   # k's 16 MiB alone: XLA stages them through the alternate
                   # memory with asynchronous copies of its own
                   and not any(f" {kind}(" in line for kind in (
                       "custom-call", "get-tuple-element", "parameter",
                       "copy-start", "copy-done"))]
    assert not activations, activations


@pytest.mark.parametrize("tokens", [8192, 16384], ids=["glm", "lfm2"])
def test_moe_rows_compile_for_the_v5e_under_moe_route(v5e_chip, tokens):
    """The movers of a held share at the two cells' shapes (8 of 64 experts
    of 2048 x 1536, 4 a token, bf16): Mosaic takes the one-tile row copies,
    the SMEM index blocks and the combine's 1024-word windows, forward and
    backward; each call carries ``moe_route`` and not ``moe_experts``; no
    ``[T * k, d]`` gather is left beside them and no loop at the XLA level."""
    from jax.sharding import SingleDeviceSharding

    from benchmark.harness.trace import in_scope
    from distribuuuu_tpu.ops.pallas import moe_rows

    chip = SingleDeviceSharding(v5e_chip)
    d, f, held, total, top = 2048, 1536, 8, 64, 4
    avals = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        ({"w_gate": _f32(held, d, f), "w_up": _f32(held, d, f),
          "w_down": _f32(held, f, d)},
         jax.ShapeDtypeStruct((tokens, d), jnp.bfloat16), _f32(tokens, top),
         jax.ShapeDtypeStruct((tokens, top), jnp.int32)))

    def step(params, x, weights, indices):
        return jax.value_and_grad(
            lambda *a: moe_ops.sorted_experts(
                *a, indices, held=(0, total), interpret=False,
            ).astype(jnp.float32).sum(), argnums=(0, 1, 2))(params, x, weights)

    text = jax.jit(step).lower(*avals).compile().as_text()
    calls = [line for line in text.splitlines() if "custom-call(" in line
             and line.split(" = ")[0].split()[-1].lstrip("%").startswith(moe_rows.NAME)]
    names = sorted(line.split(" = ")[0].split()[-1].lstrip("%").rsplit(".", 1)[0]
                   for line in calls)
    assert names == [f"{moe_rows.NAME}_combine"] * 2 + [
        f"{moe_rows.NAME}_pack"] * 4 + [f"{moe_rows.NAME}_take"] * 2, names
    for line in calls:
        op_name = line.split('op_name="')[1].split('"')[0]
        assert in_scope(op_name, "moe_route") and not in_scope(op_name, "moe_experts")
    assert " while(" not in text and " conditional(" not in text
    height = (tokens * top // 256 + held) * 256
    gathers = [line for line in text.splitlines() if " gather(" in line]
    assert not [g for g in gathers if f"bf16[{height},{d}]" in g.split(" gather(")[0]
                or f"bf16[{tokens * top},{d}]" in g.split(" gather(")[0]], gathers


def test_ouro_step_compiles_for_the_v5e_under_its_scopes(v5e_chip, monkeypatch):
    """The step of ``ouro_2_6b.train_seq4096`` (``config/ouro_2_6b.yaml``:
    published widths, 4096 tokens, all four passes) compiled for the chip at
    1 of the cell's 8 layers (8 compile in two minutes here, 1 in under
    one), with a capacity handed to the planner at which 2 of the 4 block
    applications keep the MLP's two products. What the benchmark's readers
    find in it: every scope they sum, the three flash kernels by name, the
    recomputed forward by the ``op_name`` ``jax.checkpoint``'s transpose gives
    it, with NO forward kernel, no projection of q, k or v and neither
    branch's last matmul (``o_proj``, ``down_proj``: the block keeps what they
    made) in it, ``gate_proj`` and ``up_proj`` in it for exactly the 2
    applications that do not keep their products, no instruction XLA
    rematerialized on its own, and no ``while`` (a loop in a device trace is
    one operation AND its body's)."""
    import decoder_contract
    from benchmark.harness.trace import in_scope, op_names_from_hlo

    lowered, state, avals = _step_for_the_v5e(
        v5e_chip, monkeypatch, "ouro_2_6b", (1, 4096), compiled=False, LAYERS=1)
    decoder_contract.room_for_kept_products(
        monkeypatch, lowered.model, state.params, (1, 4096), kept=2)
    compiled = lowered.train_step.lower(state, avals).compile()
    text = compiled.as_text()
    assert " while(" not in text and " conditional(" not in text
    assert ".remat" not in text  # XLA found room for what the plan keeps
    paths = list(op_names_from_hlo(text).values())
    for scope in ("fwd", "bwd", "attn", "mlp", "exit_gate", "lm_head",
                  "optimizer_update", "opt_kernel"):
        assert any(in_scope(p, scope) for p in paths), scope
    calls = _dtpu_calls(text)
    # 4 block applications: the forward kernel runs ONCE each, in the forward
    # (the block keeps its output, log-sum-exp, q, k and v, so the backward's
    # recomputation has no use for it), the one backward kernel once (and the
    # two empty calls under the names the benchmark's ``trace_kernels`` asks)
    assert {k: len(v) for k, v in calls.items() if "flash" in k} == {
        "dtpu_flash_fwd": 4, "dtpu_flash_bwd": 4,
        "dtpu_flash_dq": 4, "dtpu_flash_dkdv": 4}
    assert len(calls["dtpu_opt_update_adamw"]) == len(jax.tree.leaves(state.params))
    assert all(in_scope(p, "fwd") and in_scope(p, "attn") and not in_scope(p, "bwd")
               for p in calls["dtpu_flash_fwd"])
    for kernel in ("dtpu_flash_bwd", "dtpu_flash_dq", "dtpu_flash_dkdv"):
        assert all(in_scope(p, "bwd") and in_scope(p, "attn") for p in calls[kernel])
    assert not any(in_scope(p, "rematted_computation")
                   for kernel in calls if "flash" in kernel for p in calls[kernel])
    # the recomputed forward is the blocks' alone, less the kernel, what made
    # its inputs (a block application keeps q, k and v as the kernels take
    # them) AND the last matmul of each branch (it keeps the branch's output,
    # which the post-norm's backward alone reads): the four norms, gate_proj,
    # up_proj and the gated product; no projection of q, k or v (their
    # weights' casts remain, for the projections' own dx), no W_o, no
    # down_proj, no head
    recomputed = [p for p in paths if in_scope(p, "rematted_computation")]
    for still in ("attn_norm", "attn_post_norm"):
        assert any(in_scope(p, "attn") and still in p for p in recomputed), still
    for still in ("mlp_norm", "mlp_post_norm", "gate_proj/dot_general",
                  "up_proj/dot_general"):
        assert any(in_scope(p, "mlp") and still in p for p in recomputed), still
    for proj in ("q_proj", "k_proj", "v_proj", "o_proj", "down_proj"):
        assert any(f"{proj}/dot_general" in p for p in paths), proj
        assert not any(f"{proj}/dot_general" in p for p in recomputed), proj
    assert not any(in_scope(p, "lm_head") or in_scope(p, "exit_gate") for p in recomputed)
    # the two products: made again in the 2 applications that do not keep
    # them (one fusion each), in the forward of all 4
    for proj in ("gate_proj", "up_proj"):
        fusions = [line.split('op_name="')[1].split('"')[0]
                   for line in text.splitlines() if " fusion(" in line
                   and "calls=" in line and f'{proj}/dot_general"' in line]
        assert sum(in_scope(p, "rematted_computation") for p in fusions) == 2, proj
        assert sum(in_scope(p, "fwd") and not in_scope(p, "bwd") for p in fusions) == 4, proj


def test_the_described_v5e_plans_what_its_capacity_by_hand_plans(v5e_chip, monkeypatch):
    """The same step traced for the described chip with NOTHING patched: the
    plan is made from the table's 16 GiB for a ``TPU v5 lite`` (the step
    declared the chip it is lowered for; the live backend here is a CPU, which
    plans none), it is the count the pure planner gives those numbers by hand,
    and at 1 layer that is every application: the rehearsal and the chip
    compile one program."""
    from distribuuuu_tpu.models import ouro

    lowered, state, avals = _step_for_the_v5e(
        v5e_chip, monkeypatch, "ouro_2_6b", (1, 4096), compiled=False, LAYERS=1)
    said = []

    def say_plan(*a):
        said.append(ouro.loop_plan(*a))
        return said[-1]

    monkeypatch.setattr(ouro, "_say_plan", say_plan)
    jaxpr = jax.make_jaxpr(lowered.train_step)(state, avals)
    plan = [p for p in said if p["capacity_bytes"]][-1]
    assert v5e_chip.device_kind == "TPU v5 lite" and plan["capacity_bytes"] == 16 * 2**30
    proj = 2 * 4096 * 5632 * 2
    by_hand = ouro.plan_kept_proj(
        16 * 2**30, plan["planned_bytes"] - plan["kept_proj_bytes"], proj, 4,
        ouro.RESERVE_BYTES)
    assert plan["kept_proj_applications"] == by_hand == 4
    assert plan["kept_proj_bytes"] == 4 * proj
    assert str(jaxpr).count(f"name={ouro.KEPT_PROJ}") == 2 * 4
    # ... and the same trace with no declared device plans none
    assert jax.devices()[0].platform == "cpu"
    model = lowered.model
    jax.eval_shape(
        lambda p, t: model.apply({"params": p}, t, hidden_only=True),
        state.params, avals["image"])
    assert (said[-1]["capacity_bytes"], said[-1]["kept_proj_applications"]) == (None, 0)


def test_glm_step_compiles_for_the_v5e_under_its_scopes(v5e_chip, monkeypatch):
    """The step of ``glm_4_7_flash.train_seq8192`` (``config/glm_4_7_flash.yaml``:
    published widths, 8192 tokens, 8 of 64 experts and 19,360 vocabulary rows
    held) compiled for the chip at the dense layer, one mixture layer and the
    MTP module (the cell's 1 + 4 compile in a minute here). What the
    benchmark's readers find in it: every scope they sum, the flash kernels
    at head dim 256 and the six grouped matmuls on the held experts by name,
    the recomputed forward, and no ``while``."""
    from benchmark.harness.trace import in_scope, op_names_from_hlo

    lowered, state, compiled = _step_for_the_v5e(
        v5e_chip, monkeypatch, "glm_4_7_flash", (1, 8192), LAYERS=2)
    assert lowered.model.held == (0, 8) and lowered.model.vocab_held == 19360
    text = compiled.as_text()
    assert " while(" not in text and " conditional(" not in text
    assert "ragged-dot" not in text  # the held experts run the Pallas kernels
    paths = list(op_names_from_hlo(text).values())
    for scope in ("fwd", "bwd", "attn", "mla_latent", "mlp", "moe", "moe_route",
                  "moe_experts", "moe_shared", "mtp", "lm_head", "optimizer_update",
                  "opt_kernel", "rematted_computation"):
        assert any(in_scope(p, scope) for p in paths), scope
    calls = _dtpu_calls(text)
    # 3 blocks: the forward kernel once each, in the forward alone (a block
    # keeps its output, log-sum-exp, q, k and v), the one backward kernel once
    flash = {k: len(v) for k, v in calls.items() if "flash" in k}
    assert (flash["dtpu_flash_fwd"], flash["dtpu_flash_bwd"]) == (3, 3)
    assert not any(in_scope(p, "rematted_computation") or in_scope(p, "bwd")
                   for p in calls["dtpu_flash_fwd"])
    assert all(in_scope(p, "attn") and not in_scope(p, "mla_latent")
               for k in ("dtpu_flash_fwd", "dtpu_flash_bwd") for p in calls[k])
    # the recomputation makes no q, k or v again, nor the attention's output
    # (the block keeps it: the sum the second norm reads is made of it): the
    # two projections out of the latents and W_o run in the forward alone;
    # those into the latents, their norms (what the former's own backward
    # reads) and the mixture still run again, the mixture short of what
    # nothing reads: the shared expert's down_proj and the combine
    recomputed = [p for p in paths if in_scope(p, "rematted_computation")]
    for proj in ("q_b_proj", "kv_b_proj", "o_proj"):
        assert any(f"{proj}/dot_general" in p for p in paths), proj
        assert not any(f"{proj}/dot_general" in p for p in recomputed), proj
    for still in ("q_a_proj/dot_general", "kv_a_proj/dot_general", "q_a_norm",
                  "kv_a_norm", "attn_norm"):
        assert any(in_scope(p, "attn") and still in p for p in recomputed), still
    assert any(in_scope(p, "moe_shared") and "gate_proj/dot_general" in p
               for p in recomputed)
    assert not any("down_proj/dot_general" in p for p in recomputed)
    # 2 mixtures: gate_up and fwd run forward and again, the four backward
    # kernels once; all under moe_experts, none under moe_shared
    gmm = {k: len(v) for k, v in calls.items() if "moe_gmm" in k}
    assert gmm == {
        "dtpu_moe_gmm_gate_up": 4, "dtpu_moe_gmm_fwd": 4, "dtpu_moe_gmm_act_bwd": 2,
        "dtpu_moe_gmm_dx_gate_up": 2, "dtpu_moe_gmm_dw_down": 2,
        "dtpu_moe_gmm_dw_gate_up": 2}
    for name in gmm:
        assert all(in_scope(p, "moe_experts") and not in_scope(p, "moe_shared")
                   for p in calls[name])
    assert any(in_scope(p, "mtp") for p in calls["dtpu_moe_gmm_fwd"])
    _movers_of_held_mixtures(calls, in_scope, mixtures=2)
    assert len(calls["dtpu_opt_update_adamw"]) == len(jax.tree.leaves(state.params))
    # it fits the chip with room for the cell's two more mixture layers
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes) < 12 * 2**30


def test_trinity_step_compiles_for_the_v5e_short_of_each_branchs_last_matmul(
        v5e_chip, monkeypatch):
    """The step of ``trinity_mini.train_seq8192`` (``config/trinity_mini.yaml``:
    published widths, 2 x 8192 tokens, 16 of 128 experts and 25,024 vocabulary
    rows held, every block recomputed) compiled for the chip at the dense
    layer and one sliding mixture (published layers 1..2; the cell's 1..5
    compile in a minute here, and ``tests/benchmark/test_benchmark_trinity.py``
    compiles 2..3 under the benchmark's scopes). A norm follows each of a
    block's two parts and its backward reads that part's output, so the block
    keeps both (``models/ouro.recomputed``) and the second forward stops
    short of what made them: ``o_proj``, the dense MLP's and the shared
    expert's ``down_proj`` and the movers' combine run once, in the forward.
    The experts' down product ``dtpu_moe_gmm_fwd`` DOES run again: the
    gradient of the routing weights is each expert's output row times the
    cotangent, so the combine's backward reads the rows the kept sum was made
    of (``ops/moe.sorted_experts``; ROADMAP S9 (a)), and with it the take and
    ``dtpu_moe_gmm_gate_up`` before it."""
    from benchmark.harness.trace import in_scope, op_names_from_hlo

    lowered, state, compiled = _step_for_the_v5e(
        v5e_chip, monkeypatch, "trinity_mini", (2, 8192), FIRST_LAYER=1, LAYERS=2)
    model = lowered.model
    assert model.layer_kinds == ("sliding_attention",) * 2 and model.dense_here == 1
    assert model.held == (0, 16) and model.vocab_held == 25024 and model.recompute
    text = compiled.as_text()
    assert " while(" not in text and " conditional(" not in text
    paths = list(op_names_from_hlo(text).values())
    recomputed = [p for p in paths if in_scope(p, "rematted_computation")]
    for last in ("v_proj", "o_proj", "down_proj"):
        assert any(f"{last}/dot_general" in p for p in paths), last
        assert not any(f"{last}/dot_general" in p for p in recomputed), last
    assert any(in_scope(p, "moe_shared") and "down_proj/dot_general" in p for p in paths)
    assert any(in_scope(p, "mlp") and "down_proj/dot_general" in p for p in paths)
    # what still runs again: the four norms' inputs are kept or rebuilt from
    # what is kept, q's, k's and the gate's projections, gate_proj and up_proj
    # of the dense MLP and of the shared expert, the router
    for still, scope in (("q_proj", "attn"), ("k_proj", "attn"), ("gate_proj", "attn_gate"),
                         ("gate_proj", "mlp"), ("up_proj", "mlp"),
                         ("gate_proj", "moe_shared"), ("up_proj", "moe_shared")):
        assert any(in_scope(p, scope) and f"{still}/dot_general" in p
                   for p in recomputed), (still, scope)
    for norm in ("input_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm"):
        assert any(norm in p for p in recomputed), norm
    calls = _dtpu_calls(text)
    again = {k: sum(in_scope(p, "rematted_computation") for p in v)
             for k, v in calls.items() if "moe_" in k}
    assert {k: v for k, v in again.items() if v} == {
        "dtpu_moe_rows_pack": 1, "dtpu_moe_rows_take": 1,
        "dtpu_moe_gmm_gate_up": 1, "dtpu_moe_gmm_fwd": 1}, again
    _movers_of_held_mixtures(calls, in_scope, mixtures=1, normed_after=True)
    flash = {k: len(v) for k, v in calls.items() if "flash" in k}
    assert (flash["dtpu_flash_fwd"], flash["dtpu_flash_bwd"]) == (2, 2)
    assert not any(in_scope(p, "rematted_computation") for p in calls["dtpu_flash_fwd"])
    # q's and k's way from their projections to the kernels: one call each way
    # a layer (PR 51), under ``attn_prologue`` with no matmul in it; the second
    # forward runs the projections for the backward call and never the
    # forward one, whose one reader's outputs are kept
    prologue = {k: v for k, v in calls.items() if "head_prologue" in k}
    assert {k: len(v) for k, v in prologue.items()} == {
        "dtpu_head_prologue_fwd": 4, "dtpu_head_prologue_bwd": 4}
    assert all(in_scope(p, "attn_prologue") and in_scope(p, "attn_window")
               for v in prologue.values() for p in v)
    assert not any(in_scope(p, "rematted_computation")
                   for p in prologue["dtpu_head_prologue_fwd"])
    assert not any(p.endswith("dot_general") for p in paths if in_scope(p, "attn_prologue"))
    assert len(calls["dtpu_opt_update_adamw"]) == len(jax.tree.leaves(state.params))
