"""The Trinity-Mini configuration, its cell, its costs, its reference's blocks
and its three readers: what the files state against what the program builds,
the readers on synthetic events (and on a program without the scopes),
planted faults against the accepted driver's limits, and the cell through
the real command at its rehearsal size and through the compile-only
rehearsal."""

import functools
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

CELL = "trinity_mini.train_seq8192"
NEW = ("models.attn_window_ms_per_step", "models.attn_gate_ms_per_step",
       "kernels.flash_window_roofline")
CATALOG = Catalog()
TERMS = ("ce", "load_balance", "loss")
SOURCE = "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
SLIDING, FULL = "sliding_attention", "full_attention"
PATTERN = [SLIDING, SLIDING, SLIDING, FULL] * 8


def published() -> dict:
    """``config.json`` of arcee-ai/Trinity-Mini as the catalog beside the
    ``model-configs`` guide holds it, or the same keys by hand where the
    guides are not installed."""
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
            rows = [json.loads(line) for line in f]
        return next(r for r in rows if r["name"] == "Trinity-Mini")["config"]
    except (OSError, StopIteration):
        return {
            "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
            "hidden_size": 2048, "intermediate_size": 6144, "layer_types": PATTERN,
            "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
            "model_type": "afmoe", "moe_intermediate_size": 1024, "mup_enabled": True,
            "n_group": 1, "num_attention_heads": 32, "num_dense_layers": 2,
            "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8,
            "num_hidden_layers": 32, "num_key_value_heads": 4, "num_limited_groups": 1,
            "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
            "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
            "score_func": "sigmoid", "sliding_window": 2048,
            "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True,
            "vocab_size": 200192,
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
    body = CATALOG.config("trinity_mini")
    entry = [c for c in CATALOG.benchmark["configs"] if c["name"] == "trinity_mini"][0]
    assert entry["reduced"] == body["reduced"] == ["layers", "experts_held", "vocab_held"]
    assert entry["source"] == body["source"] == SOURCE
    assert entry["file"] == "benchmark/configs/trinity_mini.json"
    want = published()
    assert (want["num_hidden_layers"], want["num_experts"], want["vocab_size"],
            want["num_dense_layers"]) == (32, 128, 200192, 2)
    assert want["layer_types"] == PATTERN
    for key, value in want.items():
        assert body[key] == value, key  # config.json's keys at the top level, verbatim
    arch = body["architecture"]
    # no width, no window, no router output, no count per token differs in what is run
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim",
                "num_attention_heads", "num_key_value_heads", "sliding_window",
                "global_attn_every_n_layers", "num_experts", "num_experts_per_tok",
                "num_shared_experts", "score_func", "route_norm", "route_scale",
                "n_group", "topk_group", "mup_enabled", "rms_norm_eps", "rope_theta",
                "rope_scaling", "hidden_act", "tie_word_embeddings", "vocab_size",
                "max_position_embeddings", "model_type"):
        assert arch[key] == want[key], key
    assert arch["head_dim"] * arch["num_attention_heads"] == 2 * arch["hidden_size"]
    # the cut: the published 32 / 128 / 200,192 beside the held 5 / 16 / 25,024
    assert (arch["layers"], arch["experts_held"], arch["vocab_held"]) == (
        body["layers"], body["experts_held"], body["vocab_held"]) == (5, 16, 25024)
    first = arch["first_layer"]
    assert arch["layer_types"] == want["layer_types"][first:first + arch["layers"]] == [
        SLIDING, SLIDING, FULL, SLIDING, SLIDING]
    # the leading dense layers counted once; behind it a whole period, 3 : 1
    assert arch["num_dense_layers"] == want["num_dense_layers"] - first == 1
    mixtures = arch["layer_types"][arch["num_dense_layers"]:]
    assert len(mixtures) >= 4 and sorted(mixtures[:4]) == sorted(PATTERN[:4])
    # the deployment: 8 chips share a layer, this is rank 0; what is held derives
    assert (arch["share_chips"], arch["share_rank"]) == (8, 0)
    assert arch["experts_held"] == want["num_experts"] // arch["share_chips"] >= 8
    assert arch["vocab_held"] == want["vocab_size"] // arch["share_chips"]
    assert arch["vocab_held"] * 8 >= want["vocab_size"]
    assert arch["bias_update_rate"] == want["load_balance_coeff"]
    job = body["train_job"]
    assert job["seq_len"] == arch["train_context"] == 8192 == 4 * arch["sliding_window"]
    assert job["sequences_per_chip"] in (1, 2)
    assert set(job["reference_tolerance"]) == {
        *TERMS, "held_row_share", "gradient", "gradient_experts", "gradient_router",
        "update", "second_moment"}
    assert job["adamw"] == {"b1": 0.9, "b2": 0.95, "eps": 1e-08, "weight_decay": 0.1}
    assert {"dtpu_flash_fwd", "dtpu_flash_bwd", "dtpu_moe_gmm_gate_up",
            "dtpu_opt_update_adamw"} <= set(job["trace_kernels"])
    assert "8 chips" in body["deployment"] and "rank 0" in body["deployment"]
    assumed = body["assumed"]
    for name in ("architecture.layers", "architecture.experts_held",
                 "architecture.vocab_held", "attention.output_gate", "attention.qk_norm",
                 "block.four_norms", "attention.full_layers_no_rotary",
                 "architecture.sliding_window", "intermediate_size", "shared_expert",
                 "architecture.train_context", "rotary", "router", "mup_enabled", "loss",
                 "optimizer", "initialiser", "costs", "weights", "batch",
                 "train_job.sequences_per_chip"):
        assert len(assumed[name]) > 40, name
    # the four items the model class gives and config.json does not: said so
    for name in ("attention.output_gate", "attention.qk_norm", "block.four_norms",
                 "attention.full_layers_no_rotary"):
        assert "from memory" in assumed[name] and "config.json does not" in assumed[name]
    assert "1e-20" in assumed["router"] and "0.001" in assumed["router"]
    assert "1792.125" in assumed["costs"] and "t - s < 2048" in assumed[
        "architecture.sliding_window"]
    assert body["costs"] == body["reference"] == "afmoe"


def test_the_cell_is_one_chip_on_the_accepted_pattern_traffic_and_driver():
    cell = CATALOG.cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "train_device_tokens_pattern"
    assert cell.traffic["driver"] == "lm_pattern_train_step"
    assert (cell.traffic["warmup_steps"], cell.traffic["chunk_steps"],
            cell.traffic["trace_steps"]) == (2, 3, 4)
    # LFM2's cell runs the same traffic file and driver: nothing was copied
    assert CATALOG.cell("lfm2_24b_a2b.train_seq8192").traffic == cell.traffic
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
        "models.recompute_ms_per_step", "kernels.flash_attn_roofline",
        "models.moe_ms_per_step", "models.moe_load_max_over_mean",
        "models.moe_held_ms_per_step", "models.moe_shared_ms_per_step",
        "models.moe_held_row_share", "kernels.moe_held_roofline"}
    assert cell.config["program"]["overrides"]["LM.RECOMPUTE"] is True
    for m in CATALOG.benchmark["per_layer"]:
        if m["name"] in NEW:
            assert CELL in m["workloads"]
            assert m["moves"] == "train_items_per_s_per_chip"
            assert m["source"] == "device_trace"
    # the cells the benchmark had report none of the new three
    for other in ("resnet50.train", "olmoe_1b_7b.train_seq4096",
                  "ouro_2_6b.train_seq4096", "glm_4_7_flash.train_seq8192",
                  "lfm2_24b_a2b.train_seq8192"):
        assert not {m["name"] for m in CATALOG.cell(other).per_layer} & set(NEW)
    why = [w for w in CATALOG.benchmark["workloads"] if w["name"] == CELL][0]["why"]
    assert len(why) <= 200 and "1/8" in why and "outweigh" in why
    # the cell asks for no second four-chip cell: the one there stays
    four = [w["name"] for w in CATALOG.benchmark["workloads"] if w["chips"] == 4]
    assert CELL not in four and "resnet50.train_dp4" in four


def test_the_configuration_states_the_sizes_the_program_builds():
    """The parameter count, the share, the pattern, the window and every
    width of the file equal the program's module at the cell's own settings
    (config file + overrides)."""
    import distribuuuu_tpu.config as program_config
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.config import cfg

    body = CATALOG.config("trinity_mini")
    arch = body["architecture"]
    program_config.reset_cfg()
    program_config.merge_from_file(f"{REPO}/{body['program']['cfg_file']}")
    assert (cfg.LM.SHARE_CHIPS, cfg.LM.SHARE_RANK, cfg.MODEL.NUM_CLASSES) == (8, 0, 200192)
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
    built = {
        "first_layer": model.first_layer, "layers": model.depth,
        "layer_types": list(model.layer_kinds), "num_dense_layers": model.dense_here,
        "hidden_size": model.dim, "intermediate_size": model.mlp_hidden,
        "moe_intermediate_size": model.expert_hidden,
        "num_attention_heads": model.num_heads, "num_key_value_heads": model.kv_heads,
        "head_dim": model.head_dim, "sliding_window": model.sliding_window,
        "num_experts": model.num_experts, "num_experts_per_tok": model.top_k,
        "num_shared_experts": model.shared_experts, "route_scale": model.routed_scale,
        "mup_enabled": model.mup, "vocab_size": model.vocab_size,
        "train_context": model.seq_len, "rms_norm_eps": model.norm_eps,
        "rope_theta": model.rope_theta, "share_chips": model.share_chips,
        "share_rank": model.share_rank, "experts_held": model.held[1],
        "vocab_held": model.vocab_held, "bias_update_rate": model.bias_rate,
        "balance_loss_weight": model.aux_weight,
    }
    assert built == {key: arch[key] for key in built}
    from distribuuuu_tpu.models import glm_moe

    assert arch["route_norm_eps"] == glm_moe.Mixture.norm_eps == 1e-20
    # the whole published list is the module's own default
    assert list(type(model)().layer_types) == body["layer_types"]
    assert model.recompute is True
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    params = shapes["params"]

    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    # the issue's table, row by row
    assert count(params["Block_0"]["attn"]) == 27_263_232
    assert count(params["Block_0"]) == 65_020_160  # the dense layer
    assert [count(params[f"Block_{i}"]) for i in (1, 2, 3, 4)] == [134_488_320] * 4
    assert count(params["tok_embed"]) == count(params["head"]) == 51_249_152
    assert count(params) == arch["parameters"] == 705_473_792
    assert count(shapes["batch_stats"]) == 4 * 128  # bias entries, no parameters
    assert params["Block_1"]["moe"]["w_gate"].shape == (16, 2048, 1024)
    assert params["Block_1"]["moe"]["router"].shape == (2048, 128)
    assert params["Block_1"]["moe"]["shared"]["up_proj"]["kernel"].shape == (2048, 1024)
    attn = params["Block_2"]["attn"]
    assert attn["q_proj"]["kernel"].shape == attn["gate_proj"]["kernel"].shape == (2048, 4096)
    assert attn["k_proj"]["kernel"].shape == (2048, 512)
    assert params["head"].shape == (2048, 25024)


def test_costs_count_what_the_issue_counts_and_a_hand_count_at_the_tiny_size():
    costs = CATALOG.costs("afmoe")
    body = CATALOG.config("trinity_mini")
    arch = body["architecture"]
    assert costs.mixtures(arch) == 4
    assert costs.projection_macs_per_token(arch) == 5 * 27_262_976 == 136_314_880
    # the pairs the window KEEPS, never the tiles a kernel visits
    assert costs.window_keys_per_token(arch) == 1792.125
    assert costs.window_attention_macs_per_token(arch) == 4 * 1792.125 * 32 * 256
    assert costs.attention_macs_per_token(arch) == 58_724_352 + 4096 * 32 * 256
    assert costs.window_keys_per_token({**arch, "sliding_window": 8192}) == 4096.5
    assert costs.window_keys_per_token({**arch, "sliding_window": 10**6}) == 4096.5
    assert costs.expert_macs_per_row(arch) == 6_291_456
    assert costs.held_expert_macs_per_token(arch) == 4 * 8 * 6_291_456 / 8
    assert costs.held_expert_macs_per_token(arch, 0.25) == 4 * 8 * 6_291_456 / 4
    total = costs.forward_macs_per_item(arch)
    assert total == 136_314_880 + 58_724_352 + 33_554_432 + 37_748_736 + 4 * (
        262_144 + 6_291_456) + 25_165_824 + 51_249_152 == 368_971_776
    shares = {"attention": (136_314_880 + 92_278_784) / total,
              "gate": 5 * 8_388_608 / total, "window": 58_724_352 / total,
              "full": 33_554_432 / total, "dense": 37_748_736 / total,
              "shared": 25_165_824 / total, "experts": 25_165_824 / total,
              "head": 51_249_152 / total}
    assert {k: round(100 * v, 1) for k, v in shares.items()} == {
        "attention": 62.0, "gate": 11.4, "window": 15.9, "full": 9.1, "dense": 10.2,
        "shared": 6.8, "experts": 6.8, "head": 13.9}
    # 36.3 TFLOP a step of 2 x 8192 tokens
    flops = CATALOG.costs("common").train_flops(total)
    assert 16384 * flops == pytest.approx(36.27e12, rel=1e-3)
    # by hand at the rehearsal's size: 3 sliding and 1 full layer of 64, 4 heads
    # on 1 of 32, a window of 24 in 128 keys, 2 dense of 160, 2 mixtures of 8
    # experts of 32 with 2 a token, a shared one and 4 held, 256 rows
    tiny = body["rehearse"]["architecture"]
    assert costs.projection_macs_per_token(tiny) == 4 * (3 * 64 * 128 + 2 * 64 * 32)
    assert costs.window_keys_per_token(tiny) == (24 * 25 / 2 + 104 * 24) / 128 == 21.84375
    assert costs.window_attention_macs_per_token(tiny) == 3 * 21.84375 * 4 * 64 == 16_776
    assert costs.attention_macs_per_token(tiny) == 16_776 + 64 * 4 * 64
    assert costs.held_expert_macs_per_token(tiny) == 2 * 2 * 0.5 * 3 * 64 * 32
    assert costs.forward_macs_per_item(tiny) == 114_688 + 16_776 + 16_384 + 61_440 + (
        2 * (512 + 6_144)) + 12_288 + 16_384 == 251_272


PRE = "jit(train_step)/jvp(fwd)/AfMoE/"
BACK = "jit(train_step)/bwd/transpose(jvp(fwd))/AfMoE/"
AGAIN = BACK + "jvp(fwd)/AfMoE/checkpoint/rematted_computation/"
Q_PROJ = "Block_1/attn/attn/attn_window/q_proj/dot_general"
FLASH_WINDOW = "Block_1/attn/attn/attn_window/dtpu_flash_fwd/pallas_call"
FLASH_WINDOW_BWD = "Block_1/attn/attn/attn_window/dtpu_flash_bwd/pallas_call"
GATE_WINDOW = "Block_1/attn/attn/attn_window/attn_gate/gate_proj/dot_general"
FLASH_FULL = "Block_2/attn/attn/dtpu_flash_fwd/pallas_call"
FLASH_FULL_BWD = "Block_2/attn/attn/dtpu_flash_bwd/pallas_call"
GATE_FULL = "Block_2/attn/attn/attn_gate/logistic"
DENSE = "Block_0/mlp/mlp/up_proj/dot_general"
ROUTE = "Block_1/moe/moe/moe_route/sort"
EXPERTS = "Block_1/moe/moe/moe_experts/dtpu_moe_gmm_gate_up/pallas_call"
SHARED = "Block_1/moe/moe/moe_shared/shared/up_proj/dot_general"
HEAD = "jit(train_step)/jvp(fwd)/AfMoE.head_loss/lm_head/head_loss_fp32/bcd,dv->bcv/dot_general"
UPDATE = "jit(train_step)/optimizer_update/opt_kernel/dtpu_opt_update_adamw/pallas_call"


def test_the_three_readers_on_synthetic_events():
    """Two steps; per step, ms: a sliding layer's q projection 4, its flash
    forward 20 and backward 40, its gate 1 forward and 1 again; a full layer's
    flash forward 3 and backward 6 and its gate 2 (under ``attn`` and
    ``attn_gate``, under no ``attn_window``); the dense FFN 5, routing 2, the
    held experts' kernel 4, the shared expert 3, head 7, update 5."""
    events, t = [], 0
    for _step in range(2):
        for name, dur, op_name in (
            ("fusion.1", 4e6, PRE + Q_PROJ),
            ("dtpu_flash_fwd.1", 20e6, PRE + FLASH_WINDOW),
            ("fusion.2", 1e6, PRE + GATE_WINDOW),
            ("dtpu_flash_fwd.2", 3e6, PRE + FLASH_FULL),
            ("fusion.3", 2e6, PRE + GATE_FULL), ("fusion.4", 5e6, PRE + DENSE),
            ("fusion.5", 2e6, PRE + ROUTE),
            ("dtpu_moe_gmm_gate_up.1", 4e6, PRE + EXPERTS),
            ("fusion.6", 3e6, PRE + SHARED), ("fusion.7", 7e6, HEAD),
            ("fusion.8", 1e6, AGAIN + GATE_WINDOW),
            ("dtpu_flash_bwd.1", 40e6, BACK + FLASH_WINDOW_BWD),
            ("dtpu_flash_bwd.2", 6e6, BACK + FLASH_FULL_BWD),
            ("dtpu_opt_update_adamw.1", 5e6, UPDATE),
        ):
            events.append(op(name, t, dur, op_name))
            t += dur
    observed = observed_for(events, {
        "trace_steps": 2, "tokens_per_step": 16384, "moe_held_row_share": 0.2,
    })
    peaks = CATALOG.peaks("TPU v5 lite")
    window_flops = 6 * 58_724_352 * 16384
    assert read_new(observed) == {
        "models.attn_window_ms_per_step": pytest.approx(4 + 20 + 1 + 1 + 40),
        "models.attn_gate_ms_per_step": pytest.approx(1 + 2 + 1),
        # a flash call inside attn_window is counted, one under attn alone is not
        "kernels.flash_window_roofline": pytest.approx(
            100 * window_flops / peaks["bf16_flops_per_s"] / 0.060),
    }
    assert read_new(observed)["kernels.flash_window_roofline"] < 100
    # the accepted readers the cell lists read this program too
    assert reader("models.fwd_bwd_ms_per_step").read(observed) == pytest.approx(98.0)
    assert reader("models.bwd_ms_per_step").read(observed) == pytest.approx(47.0)
    assert reader("models.attn_ms_per_step").read(observed) == pytest.approx(77.0)
    assert reader("models.mlp_ms_per_step").read(observed) == pytest.approx(5.0)
    assert reader("models.lm_head_ms_per_step").read(observed) == pytest.approx(7.0)
    assert reader("models.moe_ms_per_step").read(observed) == pytest.approx(9.0)
    assert reader("models.moe_held_ms_per_step").read(observed) == pytest.approx(6.0)
    assert reader("models.moe_shared_ms_per_step").read(observed) == pytest.approx(3.0)
    assert reader("kernels.opt_update_ms_per_step").read(observed) == pytest.approx(5.0)
    assert reader("models.recompute_ms_per_step").read(observed) == pytest.approx(1.0)
    assert reader("models.moe_held_row_share").read(observed) == 0.2
    assert reader("kernels.flash_attn_roofline").read(observed) == pytest.approx(
        100 * 6 * 92_278_784 * 16384 / peaks["bf16_flops_per_s"] / 0.069)
    assert reader("kernels.moe_held_roofline").read(observed) == pytest.approx(
        100 * 6 * 4 * 8 * 0.2 * 6_291_456 * 16384 / peaks["bf16_flops_per_s"] / 0.004)


def test_the_readers_find_nothing_in_a_program_without_the_scopes():
    """The parent's kind of program (LFM2's step, GLM's, OLMoE's, a conv
    net's), read in ITS cell and in this one: every new reader returns None
    and raises nothing, with and without a trace."""
    events = [
        op("fusion.1", 0, 10e6, "jit(train_step)/jvp(fwd)/GLMMoE/Block_1/moe/moe/moe_route/sort"),
        op("dtpu_flash_fwd.1", 10e6, 5e6,
           "jit(train_step)/jvp(fwd)/LFM2MoE/Block_1/attn/attn/dtpu_flash_fwd/pallas_call"),
        op("fusion.2", 15e6, 10e6,
           "jit(train_step)/jvp(fwd)/ResNet/ConvBN_0/conv_general_dilated"),
        op("dtpu_opt_update_sgd.1", 25e6, 5e6,
           "jit(train_step)/optimizer_update/opt_kernel/dtpu_opt_update_sgd/pallas_call"),
    ]
    counters = {"trace_steps": 1, "tokens_per_step": 16384}
    for cell in (CELL, "lfm2_24b_a2b.train_seq8192", "glm_4_7_flash.train_seq8192",
                 "olmoe_1b_7b.train_seq4096", "resnet50.train"):
        assert read_new(observed_for(events, counters, cell)) == dict.fromkeys(NEW), cell
        assert read_new(observed_for(None, {}, cell)) == dict.fromkeys(NEW), cell
    # a windowed program under a configuration whose costs count no window
    windowed = [op("dtpu_flash_fwd.1", 0, 5e6, PRE + FLASH_WINDOW)]
    assert reader("kernels.flash_window_roofline").read(
        observed_for(windowed, counters, "lfm2_24b_a2b.train_seq8192")) is None


def test_a_program_without_the_arch_is_refused_before_the_device(tmp_path):
    """What the parent of this configuration's PR does on the cell: a
    DiscoveryError and a non-zero exit, at once."""
    root = make_root(tmp_path)
    path = f"{root}/benchmark/configs/trinity_mini.json"
    with open(path) as f:
        body = json.load(f)
    body["rehearse"]["program"]["arch"] = "afmoe_of_a_later_pr"
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
        REPO, "--workload", CELL, "--seed", str(2**31 + 98765), "--seconds", "2",
        "--trace", "1", "--rehearse", "--set", "traffic.reference_teeth=true"),
        timeout=600)


def test_rehearsal_runs_the_accepted_driver_end_to_end(rehearsal):
    code, out, err = rehearsal
    assert code == 0, err[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 4
    for term in TERMS:
        assert f"reference: {term} step" in out
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
    # 4 blocks of the tiny pattern (sliding x 3, full), a gate in every one
    assert out.count("['attn']['gate_proj']['kernel']") == 4
    assert "moe_dropped max 0;" in out and "share of the choices on held experts 0." in out


def test_the_tolerances_have_teeth(rehearsal):
    """The reference computed in bfloat16 throughout fails the rehearsal's
    limits. The same reading at the published widths is a chip run's
    (PERF.md)."""
    _code, out, _err = rehearsal
    teeth = [ln for ln in out.splitlines() if "teeth:" in ln]
    assert sum("fails, as it must" in ln for ln in teeth) >= 3
    assert "throughout fails" in teeth[-1] and " 0 of " not in teeth[-1]


@functools.lru_cache(maxsize=None)
def _tiny_state(seed, kv_heads, layers, vocab_held):
    """Weights, biases and batch of the tiny model: a planted fault changes
    what the model computes, never what it holds, so every test of one
    ``kv_heads`` reads the same ones."""
    from distribuuuu_tpu import models

    model = models.build_model(
        "afmoe_tiny", dtype=jnp.float32, kv_heads=kv_heads, depth=layers)
    k_init, k_tok, k_bias = jax.random.split(jax.random.key(seed), 3)
    variables = flax.linen.meta.unbox(
        jax.jit(model.init)(k_init, jnp.zeros((1, 8), jnp.int32)))
    biases = jax.tree.map(
        lambda b: 0.02 * jax.random.normal(k_bias, b.shape), variables["batch_stats"])
    ids = jax.random.randint(k_tok, (2, 65), 0, vocab_held, jnp.int32)
    return variables["params"], biases, ids[:, :-1], ids[:, 1:]


def tiny(seed=5, kv_heads=1):
    """(model, reference, architecture, params, biases, tokens, labels) at
    the rehearsal size, two sequences of 64 tokens; ``kv_heads`` 2 gives the
    4 query heads two key/value heads to tell apart. The model is built NOW,
    under whatever a test has planted."""
    from distribuuuu_tpu import models

    arch = {**CATALOG.config("trinity_mini")["rehearse"]["architecture"],
            "num_key_value_heads": kv_heads}
    model = models.build_model(
        "afmoe_tiny", dtype=jnp.float32, kv_heads=kv_heads, depth=arch["layers"])
    return (model, CATALOG.reference("afmoe"), arch,
            *_tiny_state(seed, kv_heads, arch["layers"], arch["vocab_held"]))


def test_the_references_blocks_change_no_value(monkeypatch):
    """On the chip the reference takes 1024 queries of a sequence and 2048
    rows at a time so that 2 x 8192 tokens fit; blocks of 16 queries (under
    the window of 24) and 32 rows at the CPU's size give the unblocked terms
    and gradient (``_reference_side``: at this size the published blocks hold
    everything)."""
    _, reference, arch, params, biases, tokens, labels = tiny()
    assert reference.QUERY_BLOCK >= 64 and reference.ROW_BLOCK >= 2 * 64
    whole, grads = _reference_side(1)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)
    monkeypatch.setattr(reference, "ROW_BLOCK", 32)

    def total(p):
        terms = reference.loss(p, biases, tokens, labels, architecture=arch)
        return terms["loss"], terms

    (_, blocked), blocked_grads = jax.jit(jax.value_and_grad(total, has_aux=True))(params)
    for term in (*TERMS, "held_row_share"):
        np.testing.assert_allclose(blocked[term], whole[term], rtol=1e-6, err_msg=term)
    np.testing.assert_array_equal(blocked["experts"], whole["experts"])
    for a, b in zip(jax.tree.leaves(blocked_grads), jax.tree.leaves(grads), strict=True):
        assert float(jnp.linalg.norm(a - b)) <= 1e-5 * float(jnp.linalg.norm(b))


@functools.lru_cache(maxsize=None)
def _reference_side(kv_heads):
    """(terms and routing, gradient) of the float32 reference on the tiny
    batch: the same for the sound program and for every planted fault."""
    _, reference, arch, params, biases, tokens, labels = tiny(kv_heads=kv_heads)

    def plain(p):
        terms = reference.loss(p, biases, tokens, labels, architecture=arch)
        return terms["loss"], terms

    (_, want), want_grads = jax.jit(jax.value_and_grad(plain, has_aux=True))(params)
    return jax.device_get(want), want_grads


def compared_for(kv_heads=1):
    """The accepted driver's numbers (``numbers``: terms, held share,
    routing, the three gradient classes) for the tiny model's step, built
    NOW (under whatever a test has planted), against the float32 reference,
    at the rehearsal's limits."""
    driver = CATALOG.driver("lm_pattern_train_step")
    body = CATALOG.config("trinity_mini")
    job = {**body["train_job"], **body["rehearse"]["train_job"]}
    model, reference, arch, params, biases, tokens, labels = tiny(kv_heads=kv_heads)

    def program(p):
        outputs, sown = model.apply(
            {"params": p, "batch_stats": biases}, tokens, train=True,
            hidden_only=True, mutable=["batch_stats", "moe_route"])
        loss, _, extra = model.head_loss(outputs, model.head_kernel(p), labels, topk=(1, 5))
        return loss, (extra, sown["moe_route"])

    (loss, (extra, routes)), grads = jax.jit(
        jax.value_and_grad(program, has_aux=True))(params)

    want, want_grads = _reference_side(kv_heads)
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
    return driver.numbers(run, job, want, step, chosen, errors)


def failed(compared) -> set:
    return {name for name, c in compared.items() if not c["value"] <= c["limit"]}


@pytest.mark.parametrize("kv_heads", [1, 2])
def test_the_sound_program_is_within_every_limit(kv_heads):
    assert failed(compared_for(kv_heads)) == set()


def _off_by_one(real):
    """``t - s <= window``: one key too many."""
    return lambda q, k, v, impl, dtype, mesh, window: real(
        q, k, v, impl, dtype, mesh, None if window is None else window + 1)


def _no_window(real):
    """The chip run's planted fault: plain causal in the sliding layers."""
    return lambda q, k, v, impl, dtype, mesh, window: real(
        q, k, v, impl, dtype, mesh, None)


def _by_remainder(real):
    """Query head h reading key/value head h % G instead of h // group (4
    heads on 2: k and v arrive repeated, 0, 0, 1, 1)."""
    wrong = jnp.asarray([(h % 2) * 2 for h in range(4)])
    return lambda q, k, v, *rest: real(q, k[:, wrong], v[:, wrong], *rest)


@pytest.mark.parametrize("fault, kv_heads", [
    (_off_by_one, 1), (_no_window, 1), (_by_remainder, 2)],
    ids=["window_off_by_one", "no_window", "kv_heads_by_remainder"])
def test_a_fault_in_the_attention_entry_fails_a_limit(fault, kv_heads, monkeypatch):
    """``models/lfm2_moe._attend`` is the one attention entry both kinds of
    layer call."""
    from distribuuuu_tpu.models import lfm2_moe

    monkeypatch.setattr(lfm2_moe, "_attend", fault(lfm2_moe._attend))
    assert failed(compared_for(kv_heads)) >= {"gradient"}


def test_rotary_applied_in_the_full_layer_fails_a_limit(monkeypatch):
    from distribuuuu_tpu.models import afmoe, lfm2_moe

    def rotated(*args, **kw):
        return lfm2_moe.Attention(*args, **{**kw, "rotary": True})

    monkeypatch.setattr(afmoe, "Attention", rotated)
    assert failed(compared_for()) >= {"gradient"}


def test_the_gate_left_out_fails_a_limit(monkeypatch):
    """``out = concat(heads) W_o``: the gate's projection is still there and
    reads as 30, whose sigmoid is 1, so the product leaves the heads as they
    are and the gate's own leaf gets no gradient."""
    from distribuuuu_tpu.models import lfm2_moe

    real = lfm2_moe._dense

    def dense(width, dtype, name):
        layer = real(width, dtype, name)
        return (lambda x: layer(x) * 0 + 30.0) if name == "gate_proj" else layer

    monkeypatch.setattr(lfm2_moe, "_dense", dense)
    assert failed(compared_for()) >= {"gradient"}


def test_the_drivers_mixtures_and_gradient_classes_for_this_model():
    """The accepted driver, unedited, on this model's names: the mixtures
    from ``num_dense_layers`` on, and the mixtures' pre-norms among THE REST
    (the shared expert carries every token whatever the router chose, as in
    GLM's cell; LFM2's ``ffn_norm`` is the driver's own case)."""
    driver = CATALOG.driver("lm_pattern_train_step")
    share = CATALOG.driver("lm_share_train_step")
    body = CATALOG.config("trinity_mini")
    assert share.mixture_names(driver.share_architecture(body["architecture"])) == [
        "Block_1", "Block_2", "Block_3", "Block_4"]
    assert share.mixture_names(driver.share_architecture(
        body["rehearse"]["architecture"])) == ["Block_2", "Block_3"]
    paths = ["['Block_1']['pre_mlp_norm']['scale']", "['Block_1']['post_mlp_norm']['scale']",
             "['Block_1']['moe']['w_up']", "['Block_1']['moe']['router']",
             "['Block_1']['moe']['shared']['up_proj']['kernel']",
             "['Block_1']['attn']['gate_proj']['kernel']", "['head']"]
    classes = driver.gradient_classes(share, body["architecture"], dict.fromkeys(paths))
    assert classes == {
        "gradient_router": [paths[3]], "gradient_experts": [paths[2]],
        "gradient": [paths[0], paths[1], paths[4], paths[5], paths[6]]}


def test_the_cell_compiles_for_the_chip_here_under_its_scopes(monkeypatch):
    """What ``benchmark/rehearse_compile.py`` does with the cell (the
    configuration's own overrides into the accepted driver's
    ``compile_only``), here in this process and on ONE compile for both of
    its questions: the real-size step (published widths, 2 x 8192 tokens, 16
    of 128 experts and 25,024 vocabulary rows held, every block recomputed)
    compiled by the installed XLA:TPU and Mosaic for a described v5e, with no
    chip, at a sliding mixture and the full-attention mixture (published
    layers 2..3; the whole cell is the builder's to run: PERF.md). It fits,
    reduces nothing across chips and holds no ``while``; and the benchmark's
    readers find in it every scope they sum, ``attn_window`` and
    ``attn_gate`` inside ``attn``, the flash kernels of the sliding layer
    under ``attn_window`` and the full layer's under ``attn`` alone, the
    gate's matmul under ``attn_gate``; both kinds of block run again in the
    backward, without the flash forward kernel."""
    import time

    from jax.experimental import topologies

    import distribuuuu_tpu.config as program_config
    from benchmark.harness import cli
    from benchmark.harness.trace import in_scope, op_names_from_hlo
    from distribuuuu_tpu import models
    from distribuuuu_tpu.ops import pallas as kernel_tier
    from test_tpu_lowering import _movers_of_held_mixtures

    # tests/test_tpu_lowering.py's worker describes a v5e too
    monkeypatch.setenv("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        chip = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    monkeypatch.setattr(kernel_tier, "interpret_mode", lambda: False)
    monkeypatch.setattr(kernel_tier, "compiled_across_devices", lambda: False)
    # rehearse_compile's child shows the program as many devices as the cell has chips
    monkeypatch.setattr(jax, "device_count", lambda *backend: 1)
    body = CATALOG.config("trinity_mini")
    overrides = {**body["program"]["overrides"], "LM.FIRST_LAYER": 2, "LM.LAYERS": 2,
                 "KERNELS.OPT_UPDATE": "pallas"}
    run = cli.Run(CATALOG, CATALOG.cell(CELL), [
        "--workload", CELL, "--set", "program.overrides=" + json.dumps(overrides),
        "--set", f'architecture.layer_types=["{SLIDING}", "{FULL}"]',
    ], time.perf_counter())
    try:
        compiled = CATALOG.driver(run.traffic["driver"]).compile_only(
            run, [chip])["train_step"]
    finally:
        program_config.reset_cfg()
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
            - m.alias_size_in_bytes) < 15.75 * 2**30
    text = compiled.as_text()
    assert " all-reduce(" not in text and " all-reduce-start(" not in text
    assert " while(" not in text and " conditional(" not in text
    assert "ragged-dot" not in text  # the held experts run the Pallas kernels
    assert "s32[2,8192]" in text  # the cell's batch
    paths = list(op_names_from_hlo(text).values())
    for scope in ("fwd", "bwd", "attn", "attn_window", "attn_gate", "moe",
                  "moe_route", "moe_experts", "moe_shared", "lm_head",
                  "optimizer_update", "opt_kernel", "rematted_computation"):
        assert any(in_scope(p, scope) for p in paths), scope
    for inner in ("attn_window", "attn_gate"):
        assert all(in_scope(p, "attn") for p in paths if in_scope(p, inner)), inner
    assert any(in_scope(p, "attn_gate") and p.endswith("gate_proj/dot_general")
               for p in paths)
    # the gate of the full layer lies under no attn_window
    assert any(in_scope(p, "attn_gate") and not in_scope(p, "attn_window")
               and "Block_1" in p for p in paths)
    calls = {}
    for line in text.splitlines():
        if "custom-call(" in line and "dtpu_" in line:
            name = line.split(" = ")[0].split()[-1].lstrip("%").rsplit(".", 1)[0]
            calls.setdefault(name, []).append(line.split('op_name="')[1].split('"')[0])
    for kernel in ("dtpu_flash_fwd", "dtpu_flash_bwd"):
        windowed = [in_scope(p, "attn_window") for p in calls[kernel]]
        assert len(windowed) == 2 and sum(windowed) == 1, kernel
        assert all(("Block_1" in p) != w for p, w in zip(calls[kernel], windowed))
    assert not any(in_scope(p, "rematted_computation") or in_scope(p, "bwd")
                   for p in calls["dtpu_flash_fwd"])
    recomputed = [p for p in paths if in_scope(p, "rematted_computation")]
    assert any(in_scope(p, "attn_gate") for p in recomputed)
    assert any(in_scope(p, "moe_shared") for p in recomputed)
    assert not any("v_proj/dot_general" in p for p in recomputed)
    gmm = {k: len(v) for k, v in calls.items() if "moe_gmm" in k}
    assert gmm == {
        "dtpu_moe_gmm_gate_up": 4, "dtpu_moe_gmm_fwd": 4, "dtpu_moe_gmm_act_bwd": 2,
        "dtpu_moe_gmm_dx_gate_up": 2, "dtpu_moe_gmm_dw_down": 2,
        "dtpu_moe_gmm_dw_gate_up": 2}
    _movers_of_held_mixtures(calls, in_scope, mixtures=2, normed_after=True)
    # an AdamW call a leaf of the stage the cell's overrides build
    stage = models.build_model(
        "trinity_mini", first_layer=2, depth=2, share_chips=8, recompute=True)
    assert stage.layer_kinds == (SLIDING, FULL) and stage.dense_here == 0
    assert stage.held == (0, 16) and stage.vocab_held == 25024
    leaves = jax.tree.leaves(jax.eval_shape(
        stage.init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert len(calls["dtpu_opt_update_adamw"]) == len(leaves)
