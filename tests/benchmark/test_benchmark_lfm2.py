"""The LFM2-24B-A2B configuration, its cell, its costs, its driver, its
reference's blocks and its three readers: what the files state against what
the program builds, the readers on synthetic events (and on a program without
the scopes), planted faults against the driver's limits, and the cell's
driver at its rehearsal size through the real command."""

import json
import types

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import trace
from benchmark.harness.discovery import Catalog
from benchmark.harness.observation import Observed
from benchmark.harness.trace import Reduction
from benchmark_testlib import REPO, finish, make_root, start_run

CELL = "lfm2_24b_a2b.train_seq8192"
NEW = ("models.short_conv_ms_per_step", "models.short_conv_gate_ms_per_step",
       "kernels.short_conv_gate_roofline")
CATALOG = Catalog()
TERMS = ("ce", "load_balance", "loss")
SOURCE = "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
PATTERN = ["conv", "conv", "full_attention", "conv"] * 10


def published() -> dict:
    """``config.json`` of LiquidAI/LFM2-24B-A2B as the catalog beside the
    ``model-configs`` guide holds it, or the same keys by hand where the
    guides are not installed."""
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
            rows = [json.loads(line) for line in f]
        return next(r for r in rows if r["name"] == "LFM2-24B-A2B")["config"]
    except (OSError, StopIteration):
        return {
            "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
            "intermediate_size": 11776, "layer_types": PATTERN,
            "max_position_embeddings": 128000, "model_type": "lfm2_moe",
            "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
            "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
            "num_experts_per_tok": 4, "num_hidden_layers": 40,
            "num_key_value_heads": 8,
            "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
            "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
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
    body = CATALOG.config("lfm2_24b_a2b")
    entry = [c for c in CATALOG.benchmark["configs"] if c["name"] == "lfm2_24b_a2b"][0]
    assert entry["reduced"] == body["reduced"] == ["layers", "experts_held", "vocab_held"]
    assert entry["source"] == body["source"] == SOURCE
    assert entry["file"] == "benchmark/configs/lfm2_24b_a2b.json"
    want = published()
    assert (want["num_hidden_layers"], want["num_experts"], want["vocab_size"],
            want["num_dense_layers"]) == (40, 64, 65536, 2)
    assert want["layer_types"] == PATTERN
    for key, value in want.items():
        assert body[key] == value, key  # config.json's keys at the top level, verbatim
    arch = body["architecture"]
    # no width, no router output, no count per token differs in what is run
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads", "conv_L_cache",
                "conv_bias", "num_experts", "num_experts_per_tok", "norm_eps",
                "norm_topk_prob", "routed_scaling_factor", "use_expert_bias",
                "vocab_size", "max_position_embeddings", "model_type"):
        assert arch[key] == want[key], key
    assert arch["rope_theta"] == want["rope_parameters"]["rope_theta"]
    assert arch["head_dim"] * arch["num_attention_heads"] == arch["hidden_size"]
    # the cut: the published 40 / 64 / 65,536 beside the held 5 / 8 / 8192
    assert (arch["layers"], arch["experts_held"], arch["vocab_held"]) == (
        body["layers"], body["experts_held"], body["vocab_held"]) == (5, 8, 8192)
    first = arch["first_layer"]
    assert arch["layer_types"] == want["layer_types"][first:first + arch["layers"]] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    # the leading dense layers counted once; behind it a whole period, 3 : 1
    assert arch["num_dense_layers"] == want["num_dense_layers"] - first == 1
    mixtures = arch["layer_types"][arch["num_dense_layers"]:]
    assert len(mixtures) >= 4 and sorted(mixtures[:4]) == sorted(PATTERN[:4])
    # the deployment: 8 chips share a layer, this is rank 0; what is held derives
    assert (arch["share_chips"], arch["share_rank"]) == (8, 0)
    assert arch["experts_held"] == want["num_experts"] // arch["share_chips"] >= 8
    assert arch["vocab_held"] == want["vocab_size"] // arch["share_chips"]
    assert arch["vocab_held"] * 8 >= want["vocab_size"]
    assert arch["tie_word_embeddings"] is True
    job = body["train_job"]
    assert job["seq_len"] == arch["train_context"] == 8192
    assert job["sequences_per_chip"] in (1, 2)
    assert set(job["reference_tolerance"]) == {
        *TERMS, "held_row_share", "gradient", "gradient_experts", "gradient_router",
        "update", "second_moment"}
    assert job["adamw"] == {"b1": 0.9, "b2": 0.95, "eps": 1e-08, "weight_decay": 0.1}
    assert {"dtpu_flash_fwd", "dtpu_flash_bwd", "dtpu_moe_gmm_gate_up",
            "dtpu_opt_update_adamw"} <= set(job["trace_kernels"])
    assert "8 chips" in body["deployment"] and "rank 0" in body["deployment"]
    for name in ("architecture.layers", "architecture.experts_held",
                 "architecture.vocab_held", "architecture.tie_word_embeddings",
                 "architecture.train_context", "intermediate_size", "conv", "attention",
                 "rotary", "router", "loss", "optimizer", "initialiser", "costs",
                 "weights", "batch", "train_job.sequences_per_chip",
                 "program.overrides.LM.RECOMPUTE"):
        assert len(body["assumed"][name]) > 40, name
    assert "1e-6" in body["assumed"]["router"] and "0.001" in body["assumed"]["router"]
    assert body["costs"] == body["reference"] == "lfm2_moe"


def test_the_cell_is_one_chip_and_lists_the_readers_that_read_it():
    cell = CATALOG.cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "train_device_tokens_pattern"
    share = CATALOG.traffic("train_device_tokens_share")
    assert {k: cell.traffic[k] for k in ("warmup_steps", "chunk_steps", "trace_steps")
            } == {k: share[k] for k in ("warmup_steps", "chunk_steps", "trace_steps")}
    assert cell.traffic["driver"] == "lm_pattern_train_step"
    assert {m["name"] for m in cell.end_to_end} == {
        "train_items_per_s_per_chip", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert names >= {
        *NEW, "models.mfu", "models.fwd_bwd_ms_per_step", "models.fwd_ms_per_step",
        "models.bwd_ms_per_step", "kernels.opt_update_ms_per_step",
        "kernels.opt_update_roofline", "kernels.opt_kernel_ms_per_step",
        "entry.lower_s", "entry.init_state_s", "entry.compiles_in_window",
        "device.idle_frac", "device.hbm_peak_frac", "models.attn_ms_per_step",
        "models.mlp_ms_per_step", "models.lm_head_ms_per_step",
        "kernels.flash_attn_roofline", "models.moe_held_row_share",
        "kernels.moe_held_roofline"}
    # it recomputes its blocks, so the recomputed forward is read
    recomputes = cell.config["program"]["overrides"]["LM.RECOMPUTE"]
    assert ("models.recompute_ms_per_step" in names) is recomputes
    # a mixture with no shared expert has no `moe_shared` scope: that reader
    # returns None for this program and the cell is not on its list
    assert "models.moe_held_ms_per_step" not in names
    for m in CATALOG.benchmark["per_layer"]:
        if m["name"] in NEW:
            assert CELL in m["workloads"]
            assert m["moves"] == "train_items_per_s_per_chip"
            assert m["source"] == "device_trace"
    # the cells the benchmark had report none of the new three
    for other in ("resnet50.train", "olmoe_1b_7b.train_seq4096",
                  "ouro_2_6b.train_seq4096", "glm_4_7_flash.train_seq8192"):
        assert not {m["name"] for m in CATALOG.cell(other).per_layer} & set(NEW)
    why = [w for w in CATALOG.benchmark["workloads"] if w["name"] == CELL][0]["why"]
    assert len(why) <= 200 and "1/8" in why and "outweigh" in why


def test_the_configuration_states_the_sizes_the_program_builds():
    """The parameter count, the share, the pattern and every width of the
    file equal the program's module at the cell's own settings (config file +
    overrides)."""
    import distribuuuu_tpu.config as program_config
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.config import cfg

    body = CATALOG.config("lfm2_24b_a2b")
    arch = body["architecture"]
    program_config.reset_cfg()
    program_config.merge_from_file(f"{REPO}/{body['program']['cfg_file']}")
    assert (cfg.LM.SHARE_CHIPS, cfg.LM.SHARE_RANK, cfg.MODEL.NUM_CLASSES) == (8, 0, 65536)
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
        "first_layer": model.first_layer, "layers": model.depth,
        "layer_types": list(model.layer_kinds), "num_dense_layers": model.dense_here,
        "hidden_size": model.dim, "intermediate_size": model.mlp_hidden,
        "moe_intermediate_size": model.expert_hidden,
        "num_attention_heads": model.num_heads, "num_key_value_heads": model.kv_heads,
        "head_dim": model.dim // model.num_heads, "conv_L_cache": model.conv_taps,
        "num_experts": model.num_experts, "num_experts_per_tok": model.top_k,
        "routed_scaling_factor": model.routed_scale,
        "route_norm_eps": model.route_norm_eps, "vocab_size": model.vocab_size,
        "train_context": model.seq_len, "norm_eps": model.norm_eps,
        "rope_theta": model.rope_theta, "share_chips": model.share_chips,
        "share_rank": model.share_rank, "experts_held": model.held[1],
        "vocab_held": model.vocab_held, "bias_update_rate": model.bias_rate,
        "balance_loss_weight": model.aux_weight,
    } == {key: arch[key] for key in (
        "first_layer", "layers", "layer_types", "num_dense_layers", "hidden_size",
        "intermediate_size", "moe_intermediate_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "conv_L_cache", "num_experts",
        "num_experts_per_tok", "routed_scaling_factor", "route_norm_eps", "vocab_size",
        "train_context", "norm_eps", "rope_theta", "share_chips", "share_rank",
        "experts_held", "vocab_held", "bias_update_rate", "balance_loss_weight")}
    # the whole published list is the module's own default
    assert list(type(model)().layer_types) == body["layer_types"]
    assert model.recompute is body["program"]["overrides"]["LM.RECOMPUTE"]
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    params = shapes["params"]

    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    # the issue's table, row by row
    assert count(params["Block_0"]["short_conv"]) == 16_783_360
    assert count(params["Block_0"]) == 89_139_200  # the dense layer (a conv layer)
    assert count(params["Block_1"]["attn"]) == 10_485_888
    assert count(params["Block_1"]) == 86_118_528  # the attention mixture layer
    assert [count(params[f"Block_{i}"]) for i in (2, 3, 4)] == [92_416_000] * 3
    assert count({k: params[k] for k in ("tok_embed", "final_norm")}) == 16_779_264
    assert "head" not in params  # the head is the embedding
    assert count(params) == arch["parameters"] == 469_284_992
    assert count(shapes["batch_stats"]) == 4 * 64  # bias entries, no parameters
    assert params["Block_1"]["moe"]["w_gate"].shape == (8, 2048, 1536)
    assert params["Block_1"]["moe"]["router"].shape == (2048, 64)
    assert params["Block_1"]["attn"]["k_proj"]["kernel"].shape == (2048, 512)
    assert params["Block_0"]["short_conv"]["filter"].shape == (2048, 3)
    assert params["tok_embed"]["embedding"].shape == (8192, 2048)


def test_costs_count_what_the_issue_counts_and_a_hand_count_at_the_tiny_size():
    costs = CATALOG.costs("lfm2_moe")
    body = CATALOG.config("lfm2_24b_a2b")
    arch = body["architecture"]
    assert costs.mixtures(arch) == 4 and costs.head_dim(arch) == 64
    conv, attn = 4 * (2048 * 6144 + 2048 * 2048), 2 * 2048 * 2048 + 2 * 2048 * 512
    assert costs.projection_macs_per_token(arch) == conv + attn == 67_108_864 + 10_485_760
    # useful causal MACs at d = 64 (not the padded 128) and S / 2 keys
    assert costs.attention_macs_per_token(arch) == 4096 * 32 * 128 == 16_777_216
    assert costs.expert_macs_per_row(arch) == 9_437_184
    assert costs.held_expert_macs_per_token(arch) == 4 * 4 * 9_437_184 / 8
    assert costs.held_expert_macs_per_token(arch, 0.25) == 4 * 9_437_184
    total = costs.forward_macs_per_item(arch)
    assert total == conv + attn + 16_777_216 + 72_351_744 + 4 * 131_072 + (
        18_874_368 + 2048 * 8192) == 202_899_456
    shares = {"conv": conv / total, "attention": (attn + 16_777_216) / total,
              "dense": 72_351_744 / total, "experts": 18_874_368 / total,
              "head": 2048 * 8192 / total}
    assert {k: round(100 * v, 1) for k, v in shares.items()} == {
        "conv": 33.1, "attention": 13.4, "dense": 35.7, "experts": 9.3, "head": 8.3}
    # 19.9 TFLOP a step of 2 x 8192 tokens
    flops = CATALOG.costs("common").train_flops(total)
    assert 16384 * flops == pytest.approx(19.95e12, rel=1e-3)
    # the gates' bytes: 11 d elements a token a conv layer, 4 conv layers
    assert costs.short_conv_gate_bytes_per_token(arch) == 4 * 11 * 2048 * 2 == 180_224
    assert costs.short_conv_gate_bytes_per_token(arch, 4) == 2 * 180_224
    # by hand at the rehearsal's size: 5 conv and 1 attention layers of 64,
    # 4 heads on 2 of 16 at 64 keys, 2 dense of 160, 4 mixtures of 8 experts
    # of 32 with 2 a token and 4 held, 256 rows of the vocabulary
    tiny = body["rehearse"]["architecture"]
    assert costs.projection_macs_per_token(tiny) == 5 * (64 * 192 + 64 * 64) + (
        64 * 64 + 2 * 64 * 32 + 64 * 64) == 94_208
    assert costs.attention_macs_per_token(tiny) == 64 * 4 * 32 == 8_192
    assert costs.held_expert_macs_per_token(tiny) == 4 * 2 * 0.5 * 3 * 64 * 32
    assert costs.forward_macs_per_item(tiny) == 94_208 + 8_192 + 2 * 3 * 64 * 160 + (
        4 * 64 * 8) + 24_576 + 64 * 256 == 206_848
    assert costs.short_conv_gate_bytes_per_token(tiny, 4) == 5 * 11 * 64 * 4


PRE = "jit(train_step)/jvp(fwd)/LFM2MoE/"
BACK = "jit(train_step)/bwd/transpose(jvp(fwd))/LFM2MoE/"
AGAIN = BACK + "jvp(fwd)/LFM2MoE/checkpoint/rematted_computation/"
IN_PROJ = "Block_0/short_conv/short_conv/in_proj/dot_general"
GATE = "Block_0/short_conv/short_conv/short_conv_gate/mul"
GATE_BWD = "Block_2/short_conv/short_conv/short_conv_gate/reduce_sum"
FLASH = "Block_1/attn/attn/dtpu_flash_fwd/pallas_call"
FLASH_BWD = "Block_1/attn/attn/dtpu_flash_bwd/pallas_call"
DENSE = "Block_0/mlp/mlp/up_proj/dot_general"
ROUTE = "Block_1/moe/moe/moe_route/sort"
EXPERTS = "Block_1/moe/moe/moe_experts/dtpu_moe_gmm_gate_up/pallas_call"
HEAD = "jit(train_step)/jvp(fwd)/LFM2MoE.head_loss/lm_head/head_loss_fp32/bcd,dv->bcv/dot_general"
UPDATE = "jit(train_step)/optimizer_update/opt_kernel/dtpu_opt_update_adamw/pallas_call"


def test_the_three_readers_on_synthetic_events():
    """Two steps; per step, ms: the mixer's in-projection 4 forward and 6
    backward, its gates 1 forward, 1 again and 2 backward, the flash kernels
    3 and 6, the dense FFN 5, routing 2, the held experts' kernel 4, head 7,
    update 5."""
    events, t = [], 0
    for _step in range(2):
        for name, dur, op_name in (
            ("fusion.1", 4e6, PRE + IN_PROJ), ("fusion.2", 1e6, PRE + GATE),
            ("dtpu_flash_fwd.1", 3e6, PRE + FLASH), ("fusion.3", 5e6, PRE + DENSE),
            ("fusion.4", 2e6, PRE + ROUTE),
            ("dtpu_moe_gmm_gate_up.1", 4e6, PRE + EXPERTS), ("fusion.5", 7e6, HEAD),
            ("fusion.6", 1e6, AGAIN + GATE), ("fusion.7", 2e6, BACK + GATE_BWD),
            ("fusion.8", 6e6, BACK + IN_PROJ),
            ("dtpu_flash_bwd.1", 6e6, BACK + FLASH_BWD),
            ("dtpu_opt_update_adamw.1", 5e6, UPDATE),
        ):
            events.append(op(name, t, dur, op_name))
            t += dur
    observed = observed_for(events, {
        "trace_steps": 2, "tokens_per_step": 16384, "moe_held_row_share": 0.2,
    })
    peaks = CATALOG.peaks("TPU v5 lite")
    assert read_new(observed) == {
        "models.short_conv_ms_per_step": pytest.approx(4 + 1 + 1 + 2 + 6),
        "models.short_conv_gate_ms_per_step": pytest.approx(1 + 1 + 2),
        # 180,224 bytes a token in bfloat16 over 4 ms a step
        "kernels.short_conv_gate_roofline": pytest.approx(
            100 * 180_224 * 16384 / peaks["hbm_bytes_per_s"] / 0.004),
    }
    assert read_new(observed)["kernels.short_conv_gate_roofline"] < 100
    # the accepted readers the cell lists read this program too
    assert reader("models.fwd_bwd_ms_per_step").read(observed) == pytest.approx(41.0)
    assert reader("models.bwd_ms_per_step").read(observed) == pytest.approx(15.0)
    assert reader("models.attn_ms_per_step").read(observed) == pytest.approx(9.0)
    assert reader("models.mlp_ms_per_step").read(observed) == pytest.approx(5.0)
    assert reader("models.lm_head_ms_per_step").read(observed) == pytest.approx(7.0)
    assert reader("models.moe_ms_per_step").read(observed) == pytest.approx(6.0)
    assert reader("kernels.opt_update_ms_per_step").read(observed) == pytest.approx(5.0)
    assert reader("models.recompute_ms_per_step").read(observed) == pytest.approx(1.0)
    assert reader("models.moe_held_row_share").read(observed) == 0.2
    assert reader("kernels.flash_attn_roofline").read(observed) == pytest.approx(
        100 * 6 * 16_777_216 * 16384 / peaks["bf16_flops_per_s"] / 0.009)
    assert reader("kernels.moe_held_roofline").read(observed) == pytest.approx(
        100 * 6 * 4 * 4 * 0.2 * 9_437_184 * 16384 / peaks["bf16_flops_per_s"] / 0.004)
    # no `moe_shared` scope: nothing, and no error
    by_name = {m["name"]: m for m in CATALOG.benchmark["per_layer"]}
    held = CATALOG.layer_metric(by_name["models.moe_held_ms_per_step"])
    assert held.read(observed) is None


def test_the_readers_find_nothing_in_a_program_without_the_scopes():
    """The parent's kind of program (GLM's step, OLMoE's, a conv net's), read
    in ITS cell and in this one: every new reader returns None and raises
    nothing, with and without a trace."""
    events = [
        op("fusion.1", 0, 10e6, "jit(train_step)/jvp(fwd)/GLMMoE/Block_1/moe/moe/moe_route/sort"),
        op("dtpu_flash_fwd.1", 10e6, 5e6,
           "jit(train_step)/jvp(fwd)/OLMoE/Block_0/attn/dtpu_flash_fwd/pallas_call"),
        op("fusion.2", 15e6, 10e6,
           "jit(train_step)/jvp(fwd)/ResNet/ConvBN_0/conv_general_dilated"),
        op("dtpu_opt_update_sgd.1", 25e6, 5e6,
           "jit(train_step)/optimizer_update/opt_kernel/dtpu_opt_update_sgd/pallas_call"),
    ]
    counters = {"trace_steps": 1, "tokens_per_step": 16384}
    for cell in (CELL, "glm_4_7_flash.train_seq8192", "olmoe_1b_7b.train_seq4096",
                 "resnet50.train"):
        assert read_new(observed_for(events, counters, cell)) == dict.fromkeys(NEW), cell
        assert read_new(observed_for(None, {}, cell)) == dict.fromkeys(NEW), cell


def test_a_program_without_the_arch_is_refused_before_the_device(tmp_path):
    """What the parent of this configuration's PR does on the cell: a
    DiscoveryError and a non-zero exit, at once."""
    root = make_root(tmp_path)
    path = f"{root}/benchmark/configs/lfm2_24b_a2b.json"
    with open(path) as f:
        body = json.load(f)
    body["rehearse"]["program"]["arch"] = "lfm2_of_a_later_pr"
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
    for term in TERMS:
        assert f"reference: {term} step" in out
    # every number compared beside its limit: the line's last key, and the
    # last lines of standard error
    compared = line["compared"]
    assert list(line)[-1] == "compared"
    assert set(compared) == {
        *TERMS, "held_row_share", "experts_disagreeing", "expert_tie_margin",
        "gradient", "gradient_experts", "gradient_router", "update", "second_moment",
        "biases_disagreeing", "bias_count_margin", "losses_not_finite",
        "loss_did_not_fall", "rows_dropped", "traced_kernels_missing"}
    assert all(c["value"] <= c["limit"] for c in compared.values())
    assert compared["experts_disagreeing"]["value"] == 0
    said = [ln for ln in err.splitlines() if ln.startswith("compared ")]
    assert len(said) == len(compared) and err.rstrip().endswith(said[-1])
    assert "DISAGREES" not in out
    # 6 blocks of the tiny pattern: 8 leaves a dense conv block, 10 and 8 a
    # mixture block with attention and with a convolution, and the two outside
    assert out.count("['short_conv']['filter']") == 5
    assert "moe_dropped max 0;" in out and "share of the choices on held experts 0." in out


def test_the_tolerances_have_teeth(rehearsal):
    """The reference computed in bfloat16 throughout fails the rehearsal's
    limits. The same reading at the published widths is a chip run's
    (PERF.md)."""
    _code, out, _err = rehearsal
    teeth = [ln for ln in out.splitlines() if "teeth:" in ln]
    assert sum("fails, as it must" in ln for ln in teeth) >= 3
    assert "throughout fails" in teeth[-1] and " 0 of " not in teeth[-1]


def tiny(seed=5):
    """(model, reference, architecture, params, biases, tokens, labels) at
    the rehearsal size, two sequences of 64 tokens."""
    from distribuuuu_tpu import models

    arch = CATALOG.config("lfm2_24b_a2b")["rehearse"]["architecture"]
    model = models.build_model("lfm2_moe_tiny", dtype=jnp.float32)
    k_init, k_tok, k_bias = jax.random.split(jax.random.key(seed), 3)
    variables = flax.linen.meta.unbox(model.init(k_init, jnp.zeros((1, 8), jnp.int32)))
    biases = jax.tree.map(
        lambda b: 0.02 * jax.random.normal(k_bias, b.shape), variables["batch_stats"])
    ids = jax.random.randint(k_tok, (2, 65), 0, arch["vocab_held"], jnp.int32)
    return (model, CATALOG.reference("lfm2_moe"), arch, variables["params"], biases,
            ids[:, :-1], ids[:, 1:])


def test_the_references_blocks_change_no_value(monkeypatch):
    """On the chip the reference takes 1024 queries of a sequence and 2048
    rows at a time so that 2 x 8192 tokens fit; blocks of 16 queries and 32
    rows at the CPU's size give the unblocked terms and gradient."""
    _, reference, arch, params, biases, tokens, labels = tiny()

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


def compared_for(model):
    """The driver's numbers (``numbers``: terms, held share, routing, the
    three gradient classes) for ``model``'s step against the float32
    reference, at the rehearsal's limits."""
    driver = CATALOG.driver("lm_pattern_train_step")
    body = CATALOG.config("lfm2_24b_a2b")
    job = {**body["train_job"], **body["rehearse"]["train_job"]}
    _, reference, arch, params, biases, tokens, labels = tiny()
    # filters at the size of a trained model's, so that a tap matters
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: 25 * x if "filter" in jax.tree_util.keystr(path) else x, params)

    def program(p):
        outputs, sown = model.apply(
            {"params": p, "batch_stats": biases}, tokens, train=True,
            hidden_only=True, mutable=["batch_stats", "moe_route"])
        loss, _, extra = model.head_loss(outputs, model.head_kernel(p), labels, topk=(1, 5))
        return loss, (extra, sown["moe_route"])

    (loss, (extra, routes)), grads = jax.value_and_grad(program, has_aux=True)(params)

    def plain(p):
        terms = reference.loss(p, biases, tokens, labels, architecture=arch)
        return terms["loss"], terms

    (_, want), want_grads = jax.value_and_grad(plain, has_aux=True)(params)
    errors = {
        jax.tree_util.keystr(path): {
            "gradient": float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)),
            "update": 0.0, "second_moment": 0.0}
        for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(want_grads), strict=True)}
    chosen = np.stack([
        np.asarray(routes[name]["moe"]["experts"][0]).reshape(-1, arch["num_experts_per_tok"])
        for name in CATALOG.driver("lm_share_train_step").mixture_names(
            driver.share_architecture(arch))])
    step = {"ce": extra["ce"], "load_balance": extra["moe_aux"], "loss": loss,
            "held_row_share": extra["moe_held_row_share"]}
    run = types.SimpleNamespace(catalog=CATALOG, section=lambda name: {"architecture": arch}[name])
    return driver.numbers(run, job, jax.device_get(want), step, chosen, errors)


def failed(compared) -> set:
    return {name for name, c in compared.items() if not c["value"] <= c["limit"]}


def test_the_sound_program_is_within_every_limit():
    model = tiny()[0]
    assert failed(compared_for(model)) == set()


def test_a_filter_shifted_by_one_position_fails_a_limit(monkeypatch):
    """Tap j reads g one position earlier than published (the position's own
    input never reaches it)."""
    from distribuuuu_tpu.models import lfm2_moe
    from distribuuuu_tpu.ops import short_conv

    def shifted(bcu, w):
        b, c, u = jnp.split(bcu, 3, axis=-1)
        late = jnp.pad(b * u, ((0, 0), (1, 0), (0, 0)))[:, :-1]
        return short_conv.gated_short_conv(
            jnp.concatenate([late, c, jnp.ones_like(u)], axis=-1), w)

    monkeypatch.setattr(lfm2_moe, "gated_short_conv", shifted)
    assert failed(compared_for(tiny()[0])) >= {"gradient"}


def test_key_value_heads_mapped_by_remainder_fail_a_limit(monkeypatch):
    """Query head h reading key/value head h % G instead of h // group."""
    from distribuuuu_tpu.models import lfm2_moe

    real = lfm2_moe._attend

    def by_remainder(q, k, v, *rest):
        heads, kv_heads = 4, 2  # the tiny model's; k and v arrive repeated
        wrong = jnp.asarray([(h % kv_heads) * (heads // kv_heads) for h in range(heads)])
        return real(q, k[:, wrong], v[:, wrong], *rest)

    monkeypatch.setattr(lfm2_moe, "_attend", by_remainder)
    assert failed(compared_for(tiny()[0])) >= {"gradient"}


def test_weights_normalised_over_all_the_scores_fail_a_limit(monkeypatch):
    """``s_i / sum over all E`` in place of ``s_i / sum over the chosen``."""
    from distribuuuu_tpu.ops import moe as moe_ops

    real = moe_ops.top_k_biased

    def over_all(scores, bias, top_k, scale=1.0, eps=1e-20):
        weights, indices = real(scores, bias, top_k, scale, eps)
        chosen = jnp.take_along_axis(scores, indices, axis=-1)
        return scale * chosen / (scores.sum(-1, keepdims=True) + eps), indices

    monkeypatch.setattr(moe_ops, "top_k_biased", over_all)
    assert failed(compared_for(tiny()[0])) & {"gradient", "gradient_experts", "loss", "ce"}


def test_the_drivers_mixtures_follow_the_pattern():
    """The share driver's helpers, which the new driver reuses, name this
    model's mixtures through ``share_architecture``'s three keys: every layer
    that is run from ``num_dense_layers`` on, no MTP module."""
    driver = CATALOG.driver("lm_pattern_train_step")
    share = CATALOG.driver("lm_share_train_step")
    arch = driver.share_architecture(
        {"bias_update_rate": 0.001, "num_dense_layers": 1,
         "layer_types": ["conv", "full_attention", "conv"]})
    assert share.mixture_names(arch) == ["Block_1", "Block_2"]
    body = CATALOG.config("lfm2_24b_a2b")
    assert share.mixture_names(driver.share_architecture(body["architecture"])) == [
        "Block_1", "Block_2", "Block_3", "Block_4"]
    assert share.mixture_names(driver.share_architecture(
        body["rehearse"]["architecture"])) == [f"Block_{i}" for i in (2, 3, 4, 5)]
    # a mixture's norm stands with its experts (no shared expert behind it)
    paths = ["['Block_0']['ffn_norm']['scale']", "['Block_1']['ffn_norm']['scale']",
             "['Block_1']['moe']['w_up']", "['Block_1']['moe']['router']",
             "['Block_1']['operator_norm']['scale']", "['tok_embed']['embedding']"]
    classes = driver.gradient_classes(
        share, {"num_dense_layers": 1, "layer_types": ["conv", "full_attention"]},
        dict.fromkeys(paths))
    assert classes == {
        "gradient_router": [paths[3]], "gradient_experts": [paths[2], paths[1]],
        "gradient": [paths[0], paths[4], paths[5]]}
    counts = np.asarray([[10, 6, 8, 8], [9, 7, 12, 4]])
    rule = {"Block_1": {"moe": {"router_bias": np.asarray([-0.001, 0.001, 0.0, 0.0])}},
            "Block_2": {"moe": {"router_bias": np.asarray([-0.001, 0.001, -0.001, 0.001])}}}
    assert share.bias_errors(arch, counts, rule) == (1.0, 0.0)
    # a run as the share driver's Reference reads it
    run = types.SimpleNamespace(
        catalog=CATALOG, cell=CATALOG.cell(CELL), section=lambda name: body[name])
    seen = driver.AsShare(run)
    assert seen.section("architecture")["first_k_dense_replace"] == 1
    assert seen.section("architecture")["num_nextn_predict_layers"] == 0
    assert seen.section("train_job") == body["train_job"] and seen.cell is run.cell


def test_the_cell_compiles_for_the_chip_here():
    """``benchmark/rehearse_compile.py`` on the cell: the real-size step
    (published widths, 2 x 8192 tokens, the grouped flash kernels at head dim
    64, the held experts' kernels, the AdamW kernel) compiled by the
    installed XLA:TPU and Mosaic for a described v5e, with no chip. At the
    dense layer and the attention mixture (layers 1..2 of the cell's 1..5,
    which compile in a minute and are the builder's to run: PERF.md)."""
    import os
    import subprocess
    import sys

    body = CATALOG.config("lfm2_24b_a2b")
    overrides = {**body["program"]["overrides"], "LM.LAYERS": 2,
                 "KERNELS.OPT_UPDATE": "pallas"}
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "rehearse_compile.py"),
         "--workload", CELL, "--set", "program.overrides=" + json.dumps(overrides),
         "--set", 'architecture.layer_types=["conv", "full_attention"]'],
        cwd=REPO, capture_output=True, text=True, timeout=900,
        env={**os.environ, "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"},
    )
    said = [ln for ln in done.stdout.splitlines() if ln.startswith(CELL)]
    if done.returncode and "topology" in done.stderr and not said:
        pytest.skip("no v5e:2x2 topology can be described here")
    assert done.returncode == 0 and len(said) == 1, done.stderr[-3000:]
    assert "train_step on 1 x v5e:2x2 chip(s)" in said[0] and "compiled in" in said[0]
    assert "all-reduce ops 0" in said[0]
    # flash forward and backward with the two empty calls, six grouped
    # matmuls, an AdamW call a leaf
    assert int(said[0].rsplit("Mosaic calls ", 1)[1]) >= 4 + 6 + 20
    assert float(said[0].split("total ")[1].split(" GiB")[0]) < 15.75
