"""Ouro through the partition layer and the trainer (``tests/test_ouro.py``
holds the model against its reference): the step ``lower`` builds, what it
reports, its placement rules and traits, and ``train_net.py`` on the YAML."""

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
from test_ouro import BETA, CHUNK, REPO, VOCAB, architecture, build, reference, seeded, walk


def _lowered(seq_len=100, dtype="float32", chunk=CHUNK):
    config.reset_cfg()
    config.merge_from_file(os.path.join(REPO, "config", "ouro_2_6b.yaml"))
    cfg.MODEL.ARCH = "ouro_tiny"
    cfg.MODEL.NUM_CLASSES = VOCAB
    cfg.LM.SEQ_LEN = seq_len
    cfg.DEVICE.COMPUTE_DTYPE = dtype
    cfg.MESH.DATA = 8
    topology = trainer.check_trainer_mesh()
    model = trainer.build_model_from_cfg(topology).clone(head_chunk=chunk)
    from distribuuuu_tpu.utils.optim import construct_optimizer

    return lowering.lower(
        model, construct_optimizer(), 5, mesh=mesh_lib.build_mesh(data=8),
        topology=topology, im_size=32,
    )


def test_the_step_through_lower_reports_the_references_terms():
    """Through ``lowering.lower`` on the 8-device data mesh, the yaml's
    recipe, the head in chunks of 48 of a 100-token sequence: the step's
    metrics are the reference's terms; evaluation reads the last pass."""
    ids = np.random.default_rng(1).integers(0, VOCAB, (8, 101)).astype(np.int32)
    host = {"image": ids[:, :-1], "label": ids[:, 1:], "mask": np.ones(8, np.float32)}
    low = _lowered()
    assert low.model.exit_beta == BETA and low.model.passes == 4
    state = low.init_state(jax.random.key(0), 32)
    params = jax.device_get(state.params)
    batch = low.put_batch(host)
    evaluated = jax.device_get(low.eval_step(state, batch))
    state, metrics = low.train_step(state, {k: batch[k] for k in ("image", "label")})
    metrics = jax.device_get(metrics)
    config.reset_cfg()
    assert set(metrics) >= {
        "loss", "top1", "topk", "ce", "ce_pass_0", "ce_pass_1", "ce_pass_2",
        "ce_pass_3", "exit_entropy", "exit_step_mean", "nonfinite"}
    want = reference.loss(params, host["image"], host["label"],
                          architecture=architecture(build(seq_len=100)))
    for term in ("loss", "ce", "exit_entropy", "exit_step_mean"):
        np.testing.assert_allclose(metrics[term], want[term], rtol=1e-5)
    np.testing.assert_allclose(
        [metrics[f"ce_pass_{t}"] for t in range(4)], want["ce_pass"], rtol=1e-5)
    # a fresh gate is at 1/2: 1 x 1/2 + 2 x 1/4 + 3 x 1/8 + 4 x 1/8
    assert float(metrics["exit_step_mean"]) == pytest.approx(1.875, abs=0.05)
    # it trained: every leaf moved
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(jax.device_get(state.params))):
        assert not np.array_equal(a, b)
    # evaluation: the last pass's cross-entropy, a token a count
    assert float(evaluated["count"]) == 8 * 100
    np.testing.assert_allclose(
        evaluated["loss_sum"] / evaluated["count"], want["ce_pass"][-1], rtol=1e-5)


def test_the_lowered_step_holds_no_while_and_one_headwalk():
    """Passes, layers and the head's chunks are Python loops (a ``while`` in
    a device trace is one operation AND its body's); the four passes share
    ONE walk of the head: three vocabulary-wide matmuls a chunk, not twelve."""
    low = _lowered()
    state, batch = low.abstract_args(8)
    batch = {k: jax.ShapeDtypeStruct((8, 100), jnp.int32, sharding=v.sharding)
             for k, v in batch.items()}
    config.reset_cfg()
    text = low.train_step.lower(state, batch).compile().as_text()
    assert " while(" not in text and " conditional(" not in text
    jaxpr = jax.make_jaxpr(low.train_step)(state, batch).jaxpr
    wide = sum(
        eqn.primitive.name == "dot_general" and any(
            VOCAB in getattr(v.aval, "shape", ())
            for v in list(eqn.invars) + list(eqn.outvars))
        for eqn in walk(jaxpr)
    )
    assert wide == 3 * -(-100 // CHUNK)
    # the head's rows are the batch's sequences, four times over
    assert any(
        eqn.primitive.name == "dot_general"
        and tuple(eqn.outvars[0].aval.shape) == (8 * 4, CHUNK, VOCAB)
        for eqn in walk(jaxpr))


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
    assert table.spec_for("Block_0/mlp/gate_proj/kernel") == P(None, "model")
    assert table.spec_for("Block_0/mlp/up_proj/kernel") == P(None, "model")
    assert table.spec_for("Block_0/mlp/down_proj/kernel") == P("model")
    assert table.spec_for("Block_2/attn/q_proj/kernel") == P(None, "model")
    for norm in ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm"):
        assert table.spec_for(f"Block_1/{norm}/scale") == P()
    assert table.spec_for("final_norm/scale") == P()
    assert table.spec_for("exit_gate/kernel") == table.spec_for("exit_gate/bias") == P()
    assert table.spec_for("head") == P(None, "model")
    config.reset_cfg()
    cfg.MODEL.ARCH = "ouro_tiny"
    cfg.MESH.DATA, cfg.MESH.MODEL = 4, 2
    with pytest.raises(topology.TopologyError, match="MESH.DATA=n meshes only, got model=2"):
        topology.from_cfg(cfg, n_devices=8)
    config.reset_cfg()


@pytest.mark.parametrize("arch", ["ouro_2_6b", "ouro_tiny"])
def test_the_arch_declares_what_shared_code_asks_of_it(arch):
    from distribuuuu_tpu.parallel.partition import specs

    got = models.traits(arch)
    assert (got.token_batch, got.batch_norm, got.mesh_axes) == (True, False, ("data",))
    assert specs.is_token_arch(arch)
    assert got.serve_refusal and ". " not in got.serve_refusal  # one sentence
    config.reset_cfg()
    cfg.MODEL.ARCH, cfg.LM.SEQ_LEN, cfg.LM.LAYERS = arch, 64, 2
    cfg.MODEL.EXIT_ENTROPY_WEIGHT = 0.1
    cfg.MESH.DATA = 8
    try:
        model = trainer.build_model_from_cfg(trainer.check_trainer_mesh())
    finally:
        config.reset_cfg()
    assert (model.seq_len, model.depth, model.passes, model.exit_beta) == (64, 2, 4, 0.1)
    assert not hasattr(model, "moe_axis")


def test_serving_refuses_the_arch_in_one_sentence():
    import serve_net

    config.reset_cfg()
    with pytest.raises(SystemExit, match="'ouro_2_6b' trains only.*cache a pass"):
        serve_net.main(["--cfg", os.path.join(REPO, "config", "ouro_2_6b.yaml")])
    config.reset_cfg()


def test_the_loop_says_its_plan_once_a_shape(tmp_path):
    from distribuuuu_tpu.telemetry import schema, spans

    spans.setup_telemetry(str(tmp_path), 0)
    try:
        model = build().clone(depth=2, seq_len=24)
        params, tokens, _ = seeded(model, batch=3, seq=24)
        for _ in range(2):
            model.apply({"params": params}, tokens, hidden_only=True)
    finally:
        spans.close_telemetry()
    records = [json.loads(line) for name in os.listdir(tmp_path)
               for line in open(tmp_path / name) if '"loop.plan"' in line]
    records = [r for r in records if r["kept_bytes"] != 3 * 8 * 1 * 8 * 64 * 4]  # init's
    assert len(records) == 1
    schema.check_fields("loop.plan", records[0])
    assert (records[0]["layers"], records[0]["passes"],
            records[0]["block_applications"]) == (2, 4, 8)
    # float32 here: an input and two branches' outputs a block application
    assert records[0]["kept_branch_bytes"] == 2 * 8 * 3 * 24 * 64 * 4
    assert records[0]["kept_bytes"] == 3 * 8 * 3 * 24 * 64 * 4


def test_train_net_trains_the_yaml_at_a_tiny_size_resumes_and_validates(
    tmp_path, monkeypatch,
):
    """``train_net.py --cfg config/ouro_2_6b.yaml`` with the CPU-size
    override (one layer, four passes), through ``trainer.train_model``: one epoch on packed token
    shards with its evaluation and its checkpoint; a second run resumes from
    that checkpoint into epoch 2; ``test_net.py`` validates what was saved."""
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
        "--cfg", os.path.join(REPO, "config", "ouro_2_6b.yaml"),
        "MODEL.ARCH", "ouro_tiny", "MODEL.NUM_CLASSES", "512", "LM.SEQ_LEN", str(S),
        "LM.LAYERS", "1", "DEVICE.COMPUTE_DTYPE", "float32",
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
