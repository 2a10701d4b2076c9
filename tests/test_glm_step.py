"""GLM-4.7-Flash's blocks through the partition layer and the trainer
(``tests/test_glm_moe.py`` holds the model against its reference): the step
``lower`` builds, what it reports and what it leaves in the state, its
placement rules and traits, and ``train_net.py`` on the YAML."""

import json
import os
import re

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distribuuuu_tpu.config as config
from distribuuuu_tpu import models, trainer
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu.parallel import mesh as mesh_lib
from distribuuuu_tpu.parallel.partition import lowering
from test_glm_moe import CHUNK, REPO, VOCAB, architecture, build, mixture_biases, reference
from test_ouro import walk

YAML = os.path.join(REPO, "config", "glm_4_7_flash.yaml")


def _lowered(seq_len=100, dtype="float32", chunk=CHUNK, rank=0):
    config.reset_cfg()
    config.merge_from_file(YAML)
    cfg.MODEL.ARCH = "glm_moe_tiny"
    cfg.MODEL.NUM_CLASSES = VOCAB
    cfg.LM.SEQ_LEN = seq_len
    cfg.LM.SHARE_CHIPS, cfg.LM.SHARE_RANK = 2, rank
    cfg.DEVICE.COMPUTE_DTYPE = dtype
    cfg.MESH.DATA = 8
    topology = trainer.check_trainer_mesh()
    model = trainer.build_model_from_cfg(topology).clone(head_chunk=chunk)
    from distribuuuu_tpu.utils.optim import construct_optimizer

    return lowering.lower(
        model, construct_optimizer(), 5, mesh=mesh_lib.build_mesh(data=8),
        topology=topology, im_size=32,
    )


def test_the_step_through_lower_reports_the_references_terms_and_moves_the_bias():
    """Through ``lowering.lower`` on the 8-device data mesh, the yaml's
    recipe, rank 1 of the two chips that share the layers: the step's
    metrics are the reference's terms, the biases it leaves the rule's on the
    reference's counts, the first AdamW update a plain one on the reference's
    gradient; the optimizer holds no bias; evaluation reads the trunk."""
    ids = 256 + np.random.default_rng(1).integers(0, 256, (8, 101)).astype(np.int32)
    host = {"image": ids[:, :-1], "label": ids[:, 1:], "mask": np.ones(8, np.float32)}
    low = _lowered(rank=1)
    model = low.model
    assert (model.share_chips, model.share_rank, model.aux_weight) == (2, 1, 1e-4)
    state = low.init_state(jax.random.key(0), 32)
    params, biases = jax.device_get((state.params, state.batch_stats))
    assert not float(jnp.abs(mixture_biases(model, biases)).max())  # a fresh state's
    n_params = len(jax.tree.leaves(params))
    moments = [s for s in jax.tree.leaves(
        state.opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu")]
    assert len(jax.tree.leaves(moments[0].mu)) == n_params  # no leaf for a bias
    batch = low.put_batch(host)
    evaluated = jax.device_get(low.eval_step(state, batch))
    state, metrics = low.train_step(state, {k: batch[k] for k in ("image", "label")})
    metrics = jax.device_get(metrics)
    lr, wd = float(cfg.OPTIM.BASE_LR), float(cfg.OPTIM.WEIGHT_DECAY)
    config.reset_cfg()
    assert set(metrics) >= {
        "loss", "top1", "topk", "ce", "ce_mtp", "moe_aux", "moe_dropped",
        "moe_load_max_over_mean", "moe_held_row_share", "router_bias_abs_max",
        "nonfinite"}

    def plain(p):
        terms = reference.loss(p, biases, host["image"], host["label"],
                               architecture=architecture(model))
        return terms["loss"], terms

    (_, want), grads = jax.value_and_grad(plain, has_aux=True)(params)
    for got, term in (("loss", "loss"), ("ce", "ce"), ("ce_mtp", "ce_mtp"),
                      ("moe_aux", "load_balance"),
                      ("moe_held_row_share", "held_row_share")):
        np.testing.assert_allclose(metrics[got], want[term], rtol=1e-5, err_msg=got)
    assert float(metrics["moe_dropped"]) == 0.0
    assert float(metrics["router_bias_abs_max"]) == pytest.approx(0.001)
    # the biases rode the state the step returns, by the rule and no gradient
    np.testing.assert_array_equal(
        mixture_biases(model, jax.device_get(state.batch_stats)),
        reference.bias_after(jnp.zeros((3, 8)), want["counts"], 0.001))
    # the first AdamW step (zero moments): lr * (g / (|g| + eps) + wd p), over
    # the length of the step (an element whose gradient is near eps is free)
    after = jax.device_get(state.params)
    for (path, p0), g, p1 in zip(jax.tree_util.tree_leaves_with_path(params),
                                 jax.tree.leaves(grads), jax.tree.leaves(after)):
        step = lr * (g / (jnp.abs(g) + 1e-8) + wd * p0)
        assert float(jnp.linalg.norm(p1 - (p0 - step))) <= 2e-3 * float(
            jnp.linalg.norm(step)), jax.tree_util.keystr(path)
    # evaluation: the trunk's cross-entropy, a token a count
    assert float(evaluated["count"]) == 8 * 100
    np.testing.assert_allclose(
        evaluated["loss_sum"] / evaluated["count"], want["ce"], rtol=1e-5)


def test_the_lowered_step_holds_no_while_and_one_headwalk():
    """Layers, the MTP module and the head's chunks are Python loops (a
    ``while`` in a device trace is one operation AND its body's); trunk and
    MTP module share ONE walk of the head: three vocabulary-wide matmuls a
    chunk over 2 x B rows, not six."""
    low = _lowered()
    state, batch = low.abstract_args(8)
    batch = {k: jax.ShapeDtypeStruct((8, 100), jnp.int32, sharding=v.sharding)
             for k, v in batch.items()}
    config.reset_cfg()
    text = low.train_step.lower(state, batch).compile().as_text()
    assert " while(" not in text and " conditional(" not in text
    jaxpr = jax.make_jaxpr(low.train_step)(state, batch).jaxpr
    held = VOCAB // 2
    wide = [
        eqn for eqn in walk(jaxpr)
        if eqn.primitive.name == "dot_general" and any(
            held in getattr(v.aval, "shape", ())
            for v in list(eqn.invars) + list(eqn.outvars))
    ]
    assert len(wide) == 3 * -(-100 // CHUNK)
    assert any(tuple(e.outvars[0].aval.shape) == (8 * 2, CHUNK, held) for e in wide)


def test_lm_spec_table_places_every_leaf():
    from jax.sharding import PartitionSpec as P

    from distribuuuu_tpu.parallel.partition import specs, topology

    model = build()
    table = model.param_spec_table()
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    for path, _ in jax.tree_util.tree_leaves_with_path(flax.linen.meta.unbox(shapes)):
        assert table.spec_for(specs.leaf_path(path)) is not None, specs.leaf_path(path)
    # latent attention: down-projections and latents' norms replicated, the
    # up-projections split by head, the output projection by its rows
    for name in ("q_a_proj", "kv_a_proj"):
        assert table.spec_for(f"Block_1/attn/{name}/kernel") == P()
    for name in ("q_b_proj", "kv_b_proj"):
        assert table.spec_for(f"Block_1/attn/{name}/kernel") == P(None, "model")
    assert table.spec_for("mtp_block/attn/o_proj/kernel") == P("model")
    for norm in ("attn/q_a_norm", "attn/kv_a_norm", "attn_norm", "moe_norm"):
        assert table.spec_for(f"Block_2/{norm}/scale") == P()
    assert table.spec_for("Block_0/mlp/gate_proj/kernel") == P(None, "model")
    assert table.spec_for("Block_1/moe/shared/down_proj/kernel") == P("model")
    assert table.spec_for("Block_1/moe/router") == P()
    assert table.spec_for("mtp_proj/kernel") == P()
    for norm in ("final_norm", "mtp_embed_norm", "mtp_hidden_norm", "mtp_final_norm"):
        assert table.spec_for(f"{norm}/scale") == P()
    assert table.spec_for("head") == P(None, "model")
    config.reset_cfg()
    cfg.MODEL.ARCH = "glm_moe_tiny"
    cfg.MESH.DATA, cfg.MESH.MODEL = 4, 2
    with pytest.raises(topology.TopologyError, match="MESH.DATA=n meshes only, got model=2"):
        topology.from_cfg(cfg, n_devices=8)
    config.reset_cfg()


@pytest.mark.parametrize("arch", ["glm_4_7_flash", "glm_moe_tiny"])
def test_the_arch_declares_what_shared_code_asks_of_it(arch):
    from distribuuuu_tpu.parallel.partition import specs

    got = models.traits(arch)
    assert (got.token_batch, got.batch_norm, got.mesh_axes) == (True, False, ("data",))
    assert specs.is_token_arch(arch)
    assert got.serve_refusal and ". " not in got.serve_refusal  # one sentence
    config.reset_cfg()
    cfg.MODEL.ARCH, cfg.LM.SEQ_LEN, cfg.LM.LAYERS = arch, 64, 2
    cfg.LM.SHARE_CHIPS, cfg.LM.SHARE_RANK = 4, 3
    cfg.MODEL.MOE.AUX_WEIGHT = 0.001
    cfg.MESH.DATA = 8
    try:
        model = trainer.build_model_from_cfg(trainer.check_trainer_mesh())
        cfg.LM.SHARE_CHIPS = 0  # the arch's own
        own = trainer.build_model_from_cfg(trainer.check_trainer_mesh())
    finally:
        config.reset_cfg()
    assert (model.seq_len, model.depth, model.share_chips, model.share_rank,
            model.aux_weight) == (64, 2, 4, 3, 0.001)
    assert model.held == (3 * model.num_experts // 4, model.num_experts // 4)
    assert own.share_chips == {"glm_4_7_flash": 1, "glm_moe_tiny": 2}[arch]
    assert not hasattr(model, "moe_axis")


def test_serving_refuses_the_arch_in_one_sentence():
    import serve_net

    config.reset_cfg()
    with pytest.raises(SystemExit, match="'glm_4_7_flash' trains only.*latents"):
        serve_net.main(["--cfg", YAML])
    config.reset_cfg()


def test_the_share_says_its_plan_and_the_experts_their_bound_once_a_shape(tmp_path):
    from distribuuuu_tpu.ops import pallas as kernel_tier
    from distribuuuu_tpu.telemetry import schema, spans

    kernel_tier.reset_selection()
    spans.setup_telemetry(str(tmp_path), 0)
    try:
        model = build().clone(depth=2, seq_len=24, share_rank=1)
        variables = flax.linen.meta.unbox(
            model.init(jax.random.key(0), jnp.full((3, 24), 256, jnp.int32)))
        for _ in range(2):
            model.apply(variables, jnp.full((3, 24), 300, jnp.int32), hidden_only=True)
    finally:
        spans.close_telemetry()
    lines = [json.loads(line) for name in os.listdir(tmp_path)
             for line in open(tmp_path / name)]
    plans = [r for r in lines if r.get("kind") == "share.plan"]
    assert len(plans) == 1
    schema.check_fields("share.plan", plans[0])
    assert {k: plans[0][k] for k in (
        "share_chips", "share_rank", "experts_held", "experts_total", "vocab_held",
        "vocab_total",
    )} == {"share_chips": 2, "share_rank": 1, "experts_held": 4, "experts_total": 8,
           "vocab_held": 256, "vocab_total": 512}
    assert "every block" in plans[0]["recomputed"]
    # 2 blocks and the MTP module's, 3 x 24 tokens, float32 here: an input
    # and the attention's output a block
    assert plans[0]["kept_branch_bytes"] == 3 * 3 * 24 * 64 * 4
    assert plans[0]["kept_bytes"] == 2 * 3 * 3 * 24 * 64 * 4
    # the experts' record: a share's fields beside the tiles
    chose = [r for r in lines if r.get("kind") == "kernel.select" and r["op"] == "moe_gmm"]
    assert chose and chose[-1]["impl"] == "xla"  # the CPU: ragged_dot
    reasons = [r["reason"] for r in lines
               if r.get("kind") == "kernel.fallback" and r["op"] == "moe_gmm"]
    assert reasons and all("rows an expert" in r or "128 lanes" in r or "platform" in r
                           for r in reasons)


def test_train_net_trains_the_yaml_at_a_tiny_size_resumes_and_validates(
    tmp_path, monkeypatch,
):
    """``train_net.py --cfg config/glm_4_7_flash.yaml`` with the CPU-size
    override (1 + 1 layers and the MTP module; ``LM.SHARE_CHIPS 1``, the whole
    model: the shards' ids range over the whole vocabulary), through ``trainer.train_model``: one epoch on packed token shards with
    its evaluation and its checkpoint, which holds the routers' biases; a
    second run resumes from it into epoch 2; ``test_net.py`` validates what
    was saved."""
    import test_net
    import train_net
    from distribuuuu_tpu.data.shards import tokens as token_shards

    S = 16
    rng = np.random.default_rng(0)
    docs = [bytes(rng.integers(32, 120, (400,)).astype(np.uint8)) for _ in range(12)]
    for split in ("train", "val"):
        token_shards.write_token_shards(
            str(tmp_path / split), token_shards.pack_token_stream(docs, S), S,
        )
    out_dir = tmp_path / "out"
    argv = [
        "--cfg", YAML,
        "MODEL.ARCH", "glm_moe_tiny", "MODEL.NUM_CLASSES", "512", "LM.SEQ_LEN", str(S),
        "LM.LAYERS", "2", "LM.SHARE_CHIPS", "1", "DEVICE.COMPUTE_DTYPE", "float32",
        "TRAIN.BATCH_SIZE", "1", "TEST.BATCH_SIZE", "1", "TRAIN.WORKERS", "0",
        "TRAIN.DATASET", str(tmp_path), "TEST.DATASET", str(tmp_path),
        "TRAIN.PRINT_FREQ", "2", "OUT_DIR", str(out_dir),
    ]
    from distribuuuu_tpu.telemetry import spans
    from distribuuuu_tpu.utils import logger

    # the log file of THIS run's OUT_DIR, whichever test of this worker
    # process set the logger up first (it is set up once a process)
    monkeypatch.setattr(logger, "_configured", False)
    try:
        for epochs in ("1", "2"):
            config.reset_cfg()
            monkeypatch.setattr(
                "sys.argv", ["train_net.py", *argv, "OPTIM.MAX_EPOCH", epochs])
            train_net.main()
    finally:
        spans.close_telemetry()  # train_model leaves its sink open
    logs = "".join(open(out_dir / name).read()
                   for name in os.listdir(out_dir) if name.endswith(".log"))
    assert re.search(r"resumed from .*ckpt_ep_000 \(epoch 1\)", logs), logs[-2000:]
    assert {"ckpt_ep_000", "ckpt_ep_001"} <= set(os.listdir(out_dir / "checkpoints"))
    config.reset_cfg()
    monkeypatch.setattr("sys.argv", [
        "test_net.py", *argv, "MODEL.WEIGHTS", str(out_dir / "checkpoints" / "ckpt_ep_001")])
    try:
        test_net.main()
    finally:
        spans.close_telemetry()
        config.reset_cfg()
