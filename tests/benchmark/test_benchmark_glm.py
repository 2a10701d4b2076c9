"""The GLM-4.7-Flash configuration, its cell, its costs, its driver, its
reference's blocks and its eight readers: what the files state against what
the program builds, the readers on synthetic events (and on a program without
the scopes), and the cell's driver at its rehearsal size through the real
command."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import trace
from benchmark.harness.discovery import Catalog
from benchmark.harness.observation import Observed
from benchmark.harness.trace import Reduction
from benchmark_testlib import REPO, finish, make_root, start_run

CELL = "glm_4_7_flash.train_seq8192"
NEW = ("models.mla_ms_per_step", "models.mla_latent_ms_per_step",
       "kernels.flash_d256_roofline", "models.moe_held_ms_per_step",
       "models.moe_shared_ms_per_step", "kernels.moe_held_roofline",
       "models.moe_held_row_share", "models.mtp_ms_per_step")
CATALOG = Catalog()
TERMS = ("ce", "ce_mtp", "load_balance", "loss")
SOURCE = "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json"


def published() -> dict:
    """``config.json`` of zai-org/GLM-4.7-Flash as the catalog beside the
    ``model-configs`` guide holds it, or the same keys by hand where the
    guides are not installed."""
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
            rows = [json.loads(line) for line in f]
        return next(r for r in rows if r["name"] == "GLM-4.7-Flash")["config"]
    except (OSError, StopIteration):
        return {
            "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
            "intermediate_size": 10240, "max_position_embeddings": 202752,
            "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
            "topk_method": "noaux_tc", "norm_topk_prob": True,
            "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
            "n_routed_experts": 64, "n_shared_experts": 1,
            "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
            "first_k_dense_replace": 1, "num_hidden_layers": 47,
            "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
            "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
            "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
            "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
            "v_head_dim": 256, "vocab_size": 154880,
        }


def op(name, start, dur, op_name=""):
    _, opcode = trace.parse_instruction(name)
    return {"plane": "/device:TPU:0", "line": "XLA Ops", "name": name,
            "opcode": opcode, "op_name": op_name, "start_ns": float(start),
            "dur_ns": float(dur)}


def observed_for(events, counters, cell_name=CELL):
    cell = CATALOG.cell(cell_name)
    return Observed(
        cell=cell, section=lambda name: cell.config[name], traffic=cell.traffic,
        end_to_end={"train_items_per_s_per_chip": 1.0, "setup_s": 1.0},
        counters=counters, device={"count": 1}, peaks=CATALOG.peaks("TPU v5 lite"),
        catalog=CATALOG, trace=None if events is None else Reduction(events),
    )


def reader(name):
    by_name = {m["name"]: m for m in CATALOG.benchmark["per_layer"]}
    return CATALOG.layer_metric(by_name[name])


def read_new(observed):
    return {n: reader(n).read(observed) for n in NEW}


def test_the_configuration_is_the_published_one_cut_in_depth_experts_held_and_vocabulary():
    body = CATALOG.config("glm_4_7_flash")
    entry = [c for c in CATALOG.benchmark["configs"] if c["name"] == "glm_4_7_flash"][0]
    assert entry["reduced"] == body["reduced"] == ["layers", "experts_held", "vocab_held"]
    assert entry["source"] == body["source"] == SOURCE
    want = published()
    assert (want["num_hidden_layers"], want["n_routed_experts"],
            want["vocab_size"]) == (47, 64, 154880)
    for key, value in want.items():
        assert body[key] == value, key  # config.json's keys at the top level, verbatim
    arch = body["architecture"]
    for key, value in want.items():
        if key != "num_hidden_layers":
            assert arch[key] == value, key  # no width, no router output, no count cut
    assert (arch["layers"], arch["experts_held"], arch["vocab_held"]) == (
        body["layers"], body["experts_held"], body["vocab_held"]) == (5, 8, 19360)
    # the deployment: 8 chips share a layer, this is rank 0; what is held derives
    assert (arch["share_chips"], arch["share_rank"]) == (8, 0)
    assert arch["experts_held"] == want["n_routed_experts"] // arch["share_chips"]
    assert arch["vocab_held"] == want["vocab_size"] // arch["share_chips"]
    # the guide's floors: a whole period and four layers after the dense one,
    # 8 routed experts a layer, an eighth of the vocabulary
    assert arch["layers"] - arch["first_k_dense_replace"] >= 4
    assert arch["experts_held"] >= 8 and arch["vocab_held"] * 8 >= want["vocab_size"]
    job = body["train_job"]
    assert job["seq_len"] == arch["train_context"] == 8192
    assert job["sequences_per_chip"] == 1
    assert set(job["reference_tolerance"]) == {
        *TERMS, "held_row_share", "gradient", "gradient_experts", "gradient_router",
        "update", "second_moment"}
    assert job["adamw"] == {"b1": 0.9, "b2": 0.95, "eps": 1e-08, "weight_decay": 0.1}
    assert {"dtpu_flash_fwd", "dtpu_flash_bwd", "dtpu_moe_gmm_gate_up",
            "dtpu_opt_update_adamw"} <= set(job["trace_kernels"])
    assert "8 chips" in body["deployment"] and "rank 0" in body["deployment"]
    for name in ("architecture.layers", "architecture.experts_held",
                 "architecture.vocab_held", "architecture.train_context", "attention",
                 "rotary", "router", "mtp", "loss", "optimizer", "initialiser", "costs",
                 "weights", "batch"):
        assert body["assumed"][name], name
    for key in ("bias_update_rate", "mtp_loss_weight", "balance_loss_weight"):
        assert str(arch[key]) in body["assumed"]["router"] + body["assumed"]["mtp"] + (
            body["assumed"]["loss"].replace("1e-4", "0.0001")), key


def test_the_cell_is_one_chip_and_reports_what_the_issue_lists():
    cell = CATALOG.cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "train_device_tokens_share"
    assert {k: cell.traffic[k] for k in ("driver", "warmup_steps", "chunk_steps",
                                         "trace_steps")} == {
        "driver": "lm_share_train_step", "warmup_steps": 2, "chunk_steps": 3,
        "trace_steps": 4}
    assert [m["name"] for m in cell.end_to_end] == [
        "train_items_per_s_per_chip", "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert names == {
        *NEW, "models.mfu", "models.fwd_bwd_ms_per_step", "models.fwd_ms_per_step",
        "models.bwd_ms_per_step",
        "kernels.opt_update_ms_per_step", "kernels.opt_update_roofline",
        "kernels.opt_kernel_ms_per_step", "entry.lower_s", "entry.init_state_s",
        "entry.compiles_in_window", "device.idle_frac", "device.hbm_peak_frac",
        # accepted readers that read this program as it stands (PR 40):
        # `lm_head`, the leading dense layer's `mlp`, `rematted_computation`
        "models.lm_head_ms_per_step", "models.mlp_ms_per_step",
        "models.recompute_ms_per_step"}
    # the eight new ones list this cell; the next decoder of the kind may be
    # appended (`in`, not `==`)
    for m in CATALOG.benchmark["per_layer"]:
        if m["name"] in NEW:
            assert CELL in m["workloads"]
            assert m["moves"] == "train_items_per_s_per_chip"
    # and the cells the benchmark had report what they reported
    for other in ("resnet50.train", "regnety_160.train", "resnet50.train_dp4",
                  "olmoe_1b_7b.train_seq4096", "ouro_2_6b.train_seq4096"):
        assert not {m["name"] for m in CATALOG.cell(other).per_layer} & set(NEW)
    # this cell is on one chip; how many cells there are, and how many of
    # them may take four, is the contract's rule (test_benchmark_contract.py)
    why = [w for w in CATALOG.benchmark["workloads"] if w["name"] == CELL][0]["why"]
    assert len(why) <= 200 and "1/8" in why and "8x their share" in why


def test_the_configuration_states_the_sizes_the_program_builds():
    """The parameter count, the share and every width of the file equal the
    program's module at the cell's own settings (config file + overrides)."""
    import distribuuuu_tpu.config as program_config
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.config import cfg

    body = CATALOG.config("glm_4_7_flash")
    arch = body["architecture"]
    program_config.reset_cfg()
    program_config.merge_from_file(f"{REPO}/{body['program']['cfg_file']}")
    assert (cfg.LM.SHARE_CHIPS, cfg.LM.SHARE_RANK, cfg.MODEL.NUM_CLASSES) == (8, 0, 154880)
    cfg.merge_from_list([str(x) for kv in body["program"]["overrides"].items() for x in kv])
    cfg.MESH.DATA = 8
    try:
        model = trainer.build_model_from_cfg()
        assert (cfg.OPTIM.OPTIMIZER, cfg.OPTIM.BETA1, cfg.OPTIM.BETA2,
                cfg.OPTIM.WEIGHT_DECAY, cfg.OPTIM.BASE_LR) == (
            "adamw", 0.9, 0.95, 0.1, body["train_job"]["lr"])
        assert cfg.LM.SEQ_LEN == arch["train_context"]
    finally:
        program_config.reset_cfg()
    assert {
        "layers": model.depth, "first_k_dense_replace": model.dense_layers,
        "num_nextn_predict_layers": model.mtp_layers, "hidden_size": model.dim,
        "intermediate_size": model.mlp_hidden,
        "moe_intermediate_size": model.expert_hidden,
        "num_attention_heads": model.num_heads, "q_lora_rank": model.q_lora_rank,
        "kv_lora_rank": model.kv_lora_rank, "qk_nope_head_dim": model.qk_nope_head_dim,
        "qk_rope_head_dim": model.qk_rope_head_dim, "v_head_dim": model.v_head_dim,
        "n_routed_experts": model.num_experts, "num_experts_per_tok": model.top_k,
        "n_shared_experts": model.shared_experts,
        "routed_scaling_factor": model.routed_scale, "vocab_size": model.vocab_size,
        "train_context": model.seq_len, "rms_norm_eps": model.rms_norm_eps,
        "rope_theta": model.rope_theta, "share_chips": model.share_chips,
        "share_rank": model.share_rank, "experts_held": model.held[1],
        "vocab_held": model.vocab_held, "bias_update_rate": model.bias_rate,
        "mtp_loss_weight": model.mtp_weight, "balance_loss_weight": model.aux_weight,
    } == {key: arch[key] for key in (
        "layers", "first_k_dense_replace", "num_nextn_predict_layers", "hidden_size",
        "intermediate_size", "moe_intermediate_size", "num_attention_heads",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "n_routed_experts", "num_experts_per_tok", "n_shared_experts",
        "routed_scaling_factor", "vocab_size", "train_context", "rms_norm_eps",
        "rope_theta", "share_chips", "share_rank", "experts_held", "vocab_held",
        "bias_update_rate", "mtp_loss_weight", "balance_loss_weight")}
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    params = shapes["params"]

    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    # the issue's table, row by row
    assert count(params["Block_0"]["attn"]) == 21_759_232
    assert count(params["Block_0"]) == 84_677_888
    assert [count(params[f"Block_{i}"]) for i in range(1, 5)] == [106_829_056] * 4
    assert count({k: params[k] for k in ("tok_embed", "head", "final_norm")}) == 79_300_608
    assert count({k: v for k, v in params.items() if k.startswith("mtp_")}) == 115_223_808
    assert count(params) == arch["parameters"] == 706_518_528
    # beside them 5 x 64 bias entries that are no parameters
    assert count(shapes["batch_stats"]) == 5 * 64
    assert params["Block_1"]["moe"]["w_gate"].shape == (8, 2048, 1536)
    assert params["Block_1"]["moe"]["router"].shape == (2048, 64)
    assert params["head"].shape == (2048, 19360)
    assert params["tok_embed"]["embedding"].shape == (19360, 2048)
    assert params["mtp_proj"]["kernel"].shape == (4096, 2048)


def test_costs_count_what_the_issue_counts():
    costs = CATALOG.costs("glm_moe")
    arch = CATALOG.config("glm_4_7_flash")["architecture"]
    assert (costs.blocks(arch), costs.mixtures(arch)) == (6, 5)
    assert costs.projection_macs_per_token(arch) == 6 * 21_757_952
    assert costs.attention_macs_per_token(arch) == 6 * 41_943_040 == 6 * 4096 * 20 * 512
    assert costs.expert_macs_per_row(arch) == 9_437_184
    assert costs.held_expert_macs_per_token(arch) == 5 * 4 * 9_437_184 / 8
    assert costs.held_expert_macs_per_token(arch, 0.25) == 5 * 9_437_184
    assert costs.forward_macs_per_item(arch) == 604_241_920 == (
        6 * (21_757_952 + 41_943_040) + 62_914_560
        + 5 * (131_072 + 9_437_184 + 4_718_592) + 8_388_608 + 2 * 2048 * 19360)
    # 3.63 GFLOP a token a step, 29.7 TFLOP a step of 8192 tokens
    flops = CATALOG.costs("common").train_flops(costs.forward_macs_per_item(arch))
    assert 8192 * flops == pytest.approx(29.70e12, rel=1e-3)
    # latent attention is 63 % of the counted work
    mla = costs.projection_macs_per_token(arch) + costs.attention_macs_per_token(arch)
    assert mla / costs.forward_macs_per_item(arch) == pytest.approx(0.633, abs=2e-3)


PRE = "jit(train_step)/jvp(fwd)/GLMMoE/"
AGAIN = ("jit(train_step)/bwd/transpose(jvp(fwd))/GLMMoE/jvp(fwd)/GLMMoE/checkpoint/"
         "rematted_computation/")
BACK = "jit(train_step)/bwd/transpose(jvp(fwd))/GLMMoE/jvp(fwd)/GLMMoE/checkpoint/"
LATENT = "Block_1/attn/attn/mla_latent/q_b_proj/dot_general"
FLASH = "Block_1/attn/attn/dtpu_flash_fwd/pallas_call"
FLASH_BWD = "Block_1/attn/attn/dtpu_flash_bwd/pallas_call"
O_PROJ = "Block_1/attn/attn/o_proj/dot_general"
ROUTE = "Block_1/moe/moe/moe_route/sort"
EXPERTS = "Block_1/moe/moe/moe_experts/dtpu_moe_gmm_gate_up/pallas_call"
SHARED = "Block_1/moe/moe/moe_shared/shared/up_proj/dot_general"
MTP_PROJ = "mtp/mtp_proj/dot_general"
MTP_ATTN = "mtp/mtp_block/attn/attn/mla_latent/kv_b_proj/dot_general"
HEAD = "jit(train_step)/jvp(fwd)/GLMMoE.head_loss/lm_head/head_loss_fp32/bcd,dv->bcv/dot_general"
UPDATE = "jit(train_step)/optimizer_update/opt_kernel/dtpu_opt_update_adamw/pallas_call"


def test_the_eight_readers_on_synthetic_events():
    """Two steps; per step, ms: latent projections 2 forward + 2 again + 4
    backward, the flash kernels 3 + 3 + 6, the output projection 1, routing 2,
    the held experts' kernel 4, the shared expert 3, the MTP module's
    projection 1 and its attention 2, head 7, update 5."""
    events, t = [], 0
    for _step in range(2):
        for name, dur, op_name in (
            ("fusion.1", 2e6, PRE + LATENT), ("dtpu_flash_fwd.1", 3e6, PRE + FLASH),
            ("fusion.2", 1e6, PRE + O_PROJ), ("fusion.3", 2e6, PRE + ROUTE),
            ("dtpu_moe_gmm_gate_up.1", 4e6, PRE + EXPERTS),
            ("fusion.4", 3e6, PRE + SHARED), ("fusion.5", 1e6, PRE + MTP_PROJ),
            ("fusion.6", 2e6, PRE + MTP_ATTN), ("fusion.7", 7e6, HEAD),
            ("fusion.8", 2e6, AGAIN + LATENT), ("dtpu_flash_fwd.2", 3e6, AGAIN + FLASH),
            ("fusion.9", 4e6, BACK + LATENT), ("dtpu_flash_bwd.1", 6e6, BACK + FLASH_BWD),
            ("dtpu_opt_update_adamw.1", 5e6, UPDATE),
        ):
            events.append(op(name, t, dur, op_name))
            t += dur
    observed = observed_for(events, {
        "trace_steps": 2, "tokens_per_step": 8192, "moe_held_row_share": 0.13,
    })
    peak = CATALOG.peaks("TPU v5 lite")["bf16_flops_per_s"]
    assert read_new(observed) == {
        "models.mla_ms_per_step": pytest.approx(2 + 3 + 1 + 2 + 2 + 3 + 4 + 6),
        "models.mla_latent_ms_per_step": pytest.approx(2 + 2 + 2 + 4),
        "kernels.flash_d256_roofline": pytest.approx(
            100 * 6 * 6 * 41_943_040 * 8192 / peak / 0.012),
        "models.moe_held_ms_per_step": pytest.approx(2 + 4),
        "models.moe_shared_ms_per_step": pytest.approx(3.0),
        "kernels.moe_held_roofline": pytest.approx(
            100 * 6 * 5 * 4 * 0.13 * 9_437_184 * 8192 / peak / 0.004),
        "models.moe_held_row_share": 0.13,
        "models.mtp_ms_per_step": pytest.approx(3.0),
    }
    # the readers the cell shares with the other cells read this program too
    assert reader("models.fwd_bwd_ms_per_step").read(observed) == pytest.approx(40.0)
    assert reader("models.bwd_ms_per_step").read(observed) == pytest.approx(15.0)
    assert reader("kernels.opt_update_ms_per_step").read(observed) == pytest.approx(5.0)
    # ... and the pinned ones would (PERF.md section 7)
    assert reader("models.lm_head_ms_per_step").read(observed) == pytest.approx(7.0)
    assert reader("models.recompute_ms_per_step").read(observed) == pytest.approx(5.0)


def test_the_readers_find_nothing_in_a_program_without_the_scopes():
    """The parent's kind of program (OLMoE's step, Ouro's, a conv net's), read
    in ITS cell: every new reader returns None and raises nothing, with and
    without a trace; OLMoE's ``moe`` without a ``moe_shared`` is not a held
    share, and its flash kernels at head dim 128 are not this metric's."""
    events = [
        op("fusion.1", 0, 10e6, "jit(train_step)/jvp(fwd)/OLMoE/Block_0/moe/moe/moe_route/sort"),
        op("dtpu_flash_fwd.1", 10e6, 5e6,
           "jit(train_step)/jvp(fwd)/OLMoE/Block_0/attn_/dtpu_flash_fwd/pallas_call"),
        op("fusion.2", 15e6, 10e6,
           "jit(train_step)/jvp(fwd)/ResNet/ConvBN_0/conv_general_dilated"),
        op("dtpu_opt_update_sgd.1", 25e6, 5e6,
           "jit(train_step)/optimizer_update/opt_kernel/dtpu_opt_update_sgd/pallas_call"),
    ]
    counters = {"trace_steps": 1, "tokens_per_step": 16384}
    for cell in ("olmoe_1b_7b.train_seq4096", "ouro_2_6b.train_seq4096", "resnet50.train"):
        assert read_new(observed_for(events, counters, cell)) == dict.fromkeys(NEW), cell
        assert read_new(observed_for(None, {}, cell)) == dict.fromkeys(NEW), cell


def test_a_program_without_the_arch_is_refused_before_the_device(tmp_path):
    """What the parent of this configuration's PR does on the cell: a
    DiscoveryError and a non-zero exit, at once."""
    root = make_root(tmp_path)
    path = f"{root}/benchmark/configs/glm_4_7_flash.json"
    with open(path) as f:
        body = json.load(f)
    body["rehearse"]["program"]["arch"] = "glm_of_a_later_pr"
    with open(path, "w") as f:
        json.dump(body, f)
    code, out, err = finish(start_run(
        root, "--workload", CELL, "--seed", "1", "--seconds", "1", "--rehearse"))
    assert code != 0 and "DiscoveryError" in err and "cannot run" in err
    assert not [ln for ln in out.splitlines() if ln.startswith("{")]


@pytest.fixture(scope="module")
def rehearsal():
    # a seed past 2**31, as the driver draws them
    return finish(start_run(
        REPO, "--workload", CELL, "--seed", str(2**31 + 54321), "--seconds", "2",
        "--trace", "1", "--rehearse", "--set", "traffic.reference_teeth=true"),
        timeout=600)


def test_rehearsal_runs_the_driver_end_to_end(rehearsal):
    code, out, err = rehearsal
    assert code == 0, err[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 4
    # the timed program's own step after the warm-up: every term of the loss
    for term in TERMS:
        assert f"reference: {term} step" in out
    assert "reference: share of the (token, slot) choices on held experts" in out
    assert "reference: experts chosen equal in 1.00000" in out
    assert "reference: the routers' biases after the first step equal" in out
    # its first step: the gradient on every leaf, the AdamW arithmetic
    for kind in ("gradient", "gradient_experts", "gradient_router", "update",
                 "second_moment"):
        assert f"reference: {kind} of the first step against" in out
    assert "worst of 67 leaves" in out  # 12 + 3 x 17 and the four outside the blocks
    # the gradient by how a routing flip reaches a leaf: the three mixtures'
    # routers, their three expert tensors each, every other leaf
    for leaves in (3, 9, 55):
        assert f"worst of {leaves} leaves" in out
    assert out.count("agrees") == 12 and "DISAGREES" not in out
    assert "moe_dropped max 0;" in out and "share of the choices on held experts 0." in out


def test_the_tolerances_have_teeth(rehearsal):
    """The reference computed in bfloat16 throughout (the nearest precision
    below the float32 the configuration states for residual stream, norms,
    router, softmaxes and loss) fails the rehearsal's tolerances, the worst
    10x and more outside. The same reading at the published widths is a chip
    run's (PERF.md)."""
    _code, out, _err = rehearsal
    teeth = [ln for ln in out.splitlines() if "teeth:" in ln]
    assert len(teeth) == 6 and sum("fails, as it must" in ln for ln in teeth) >= 4
    assert max(float(ln.split("(relative ")[1].split(",")[0])
               for ln in teeth if "(relative" in ln) > 10 * 1e-5


def tiny():
    """(reference, architecture, params, biases, tokens, labels) at the
    rehearsal size, two sequences of 64 tokens."""
    import flax

    from distribuuuu_tpu import models

    arch = CATALOG.config("glm_4_7_flash")["rehearse"]["architecture"]
    model = models.build_model("glm_moe_tiny", dtype=jnp.float32)
    k_init, k_tok, k_bias = jax.random.split(jax.random.key(5), 3)
    variables = flax.linen.meta.unbox(model.init(k_init, jnp.zeros((1, 8), jnp.int32)))
    biases = jax.tree.map(
        lambda b: 0.02 * jax.random.normal(k_bias, b.shape), variables["batch_stats"])
    ids = jax.random.randint(k_tok, (2, 65), 0, arch["vocab_held"], jnp.int32)
    return (CATALOG.reference("glm_moe"), arch, variables["params"], biases,
            ids[:, :-1], ids[:, 1:])


def test_the_references_blocks_change_no_value(monkeypatch):
    """On the chip the reference takes 1024 queries and 2048 rows at a time
    so that 8192 tokens fit; blocks of 16 queries and 32 rows at the CPU's
    size give the unblocked terms and gradient."""
    reference, arch, params, biases, tokens, labels = tiny()

    def both():
        def total(p):
            terms = reference.loss(p, biases, tokens, labels, architecture=arch)
            return terms["loss"], terms

        return jax.value_and_grad(total, has_aux=True)(params)

    (_, whole), grads = both()
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)
    monkeypatch.setattr(reference, "ROW_BLOCK", 32)
    (_, blocked), blocked_grads = both()
    for term in (*TERMS, "held_row_share"):
        np.testing.assert_allclose(blocked[term], whole[term], rtol=1e-6, err_msg=term)
    np.testing.assert_array_equal(blocked["experts"], whole["experts"])
    for a, b in zip(jax.tree.leaves(blocked_grads), jax.tree.leaves(grads), strict=True):
        assert float(jnp.linalg.norm(a - b)) <= 1e-5 * float(jnp.linalg.norm(b))


def test_the_drivers_bias_check_tells_a_flip_near_the_mean_from_a_wrong_rule():
    driver = CATALOG.driver("lm_share_train_step")
    arch = {"bias_update_rate": 0.001, "first_k_dense_replace": 1, "layers": 2,
            "num_nextn_predict_layers": 1}
    assert driver.mixture_names(arch) == ["Block_1", "mtp_block"]
    counts = np.asarray([[10, 6, 8, 8], [9, 7, 12, 4]])

    def biases(a, b):
        return {"Block_1": {"moe": {"router_bias": np.asarray(a)}},
                "mtp_block": {"moe": {"router_bias": np.asarray(b)}}}

    rule = ([-0.001, 0.001, 0.0, 0.0], [-0.001, 0.001, -0.001, 0.001])
    assert driver.bias_errors(arch, counts, biases(*rule)) == (1.0, 0.0)
    # an expert one choice from the mean ended on the other side of it
    near = ([-0.001, 0.001, 0.0, 0.0], [0.001, 0.001, -0.001, 0.001])
    assert driver.bias_errors(arch, counts, biases(*near)) == (7 / 8, 1.0)
    # a rule with the sign the other way round is far from the mean
    same, off = driver.bias_errors(arch, counts, biases(*[[-x for x in r] for r in rule]))
    assert same == 2 / 8 and off == 4.0


def test_the_tie_margin_is_read_on_tokens_whose_earlier_choices_agreed():
    driver = CATALOG.driver("lm_share_train_step")
    # two mixtures, three tokens, 2 of 4 experts a token
    scores = np.asarray([
        [[.9, .8, .1, .0], [.9, .50, .49, .0], [.9, .8, .1, .0]],
        [[.9, .8, .1, .0], [.9, .8, .1, .0], [.9, .8, .1, .0]],
    ])
    want = {"experts": np.asarray([[[0, 1], [0, 1], [0, 1]]] * 2), "chosen_by": scores}
    same = want["experts"].copy()
    assert driver.routing_agreement(same, want) == (1.0, 0.0)
    # token 1 breaks a near-tie the other way in mixture 0; one layer down it
    # is another input and lands far off: the share counts it, the margin not
    flipped = same.copy()
    flipped[0, 1] = [0, 2]
    flipped[1, 1] = [0, 3]
    share, margin = driver.routing_agreement(flipped, want)
    assert share == 10 / 12 and margin == pytest.approx(0.01)
    # the same far choice with nothing upstream to excuse it is wrong routing
    wrong = same.copy()
    wrong[1, 1] = [0, 3]
    assert driver.routing_agreement(wrong, want)[1] == pytest.approx(0.8)
