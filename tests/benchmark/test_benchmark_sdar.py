"""The SDAR-30B-A3B-Chat configuration, its cell, its costs, its reference's
blocks, its driver and its two readers: what the files state against what
the program builds, the readers on synthetic events (and on a program
without the scope or the metric), planted faults against the driver's
limits, each failing a stated one, and the cell through the real command at
its rehearsal size and, two layers of it, through the compile-only
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

CELL = "sdar_30b_a3b.train_seq8192"
NEW = ("models.diffusion_noise_ms_per_step", "models.diffusion_masked_share")
CATALOG = Catalog()
TERMS = ("ce", "load_balance", "loss")
SOURCE = "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"


def published() -> dict:
    """``config.json`` of JetLM/SDAR-30B-A3B-Chat as the catalog beside the
    ``model-configs`` guide holds it, or the same keys by hand where the
    guides are not installed."""
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
            rows = [json.loads(line) for line in f]
        return next(r for r in rows if r["name"] == "SDAR-30B-A3B-Chat")["config"]
    except (OSError, StopIteration):
        return {
            "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
            "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
            "max_position_embeddings": 32768, "max_window_layers": 48,
            "mlp_only_layers": [], "model_type": "sdar_moe",
            "moe_intermediate_size": 768, "norm_topk_prob": True,
            "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
            "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
            "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
            "tie_word_embeddings": False, "use_sliding_window": False,
            "vocab_size": 151936,
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
    body = CATALOG.config("sdar_30b_a3b")
    entry = [c for c in CATALOG.benchmark["configs"] if c["name"] == "sdar_30b_a3b"][0]
    assert entry["reduced"] == body["reduced"] == ["layers", "experts_held", "vocab_held"]
    assert entry["source"] == body["source"] == SOURCE
    assert entry["file"] == "benchmark/configs/sdar_30b_a3b.json"
    want = published()
    assert (want["num_hidden_layers"], want["num_experts"], want["vocab_size"],
            want["norm_topk_prob"], want["mlp_only_layers"]) == (48, 128, 151936, True, [])
    for key, value in want.items():
        assert body[key] == value, key  # config.json's keys at the top level, verbatim
    arch = body["architecture"]
    # no width, no router output, no count per token differs in what is run
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim",
                "num_attention_heads", "num_key_value_heads", "num_experts",
                "num_experts_per_tok", "norm_topk_prob", "decoder_sparse_step",
                "mlp_only_layers", "attention_bias", "rms_norm_eps", "rope_theta",
                "rope_scaling", "sliding_window", "hidden_act", "tie_word_embeddings",
                "vocab_size", "max_position_embeddings", "model_type"):
        assert arch[key] == want[key], key
    # the cut: the published 48 / 128 / 151,936 beside the held 6 / 16 / 18,992
    assert (arch["layers"], arch["experts_held"], arch["vocab_held"]) == (
        body["layers"], body["experts_held"], body["vocab_held"]) == (6, 16, 18992)
    assert arch["layers"] >= 4  # the guide's floor
    assert (arch["share_chips"], arch["share_rank"]) == (8, 0)
    assert arch["experts_held"] == want["num_experts"] // arch["share_chips"] >= 8
    assert arch["vocab_held"] == want["vocab_size"] // arch["share_chips"]
    assert arch["vocab_held"] * 8 >= want["vocab_size"]
    # the objective's assumed sizes
    assert (arch["block_length"], arch["noise_eps"], arch["balance_loss_weight"]) == (
        4, 1e-3, 1e-3)
    assert arch["mask_id"] == arch["vocab_held"] - 1  # the last held row
    job = body["train_job"]
    assert job["seq_len"] == arch["train_context"] == 8192
    assert job["sequences_per_chip"] == 1 and arch["train_context"] % arch["block_length"] == 0
    assert set(job["reference_tolerance"]) == {
        *TERMS, "held_row_share", "masked_share", "gradient", "gradient_experts",
        "gradient_router", "update", "second_moment"}
    assert job["adamw"] == {"b1": 0.9, "b2": 0.95, "eps": 1e-08, "weight_decay": 0.1}
    assert {"dtpu_flash_fwd", "dtpu_flash_bwd", "dtpu_moe_gmm_gate_up",
            "dtpu_opt_update_adamw"} <= set(job["trace_kernels"])
    assert "8 chips" in body["deployment"] and "rank 0" in body["deployment"]
    assumed = body["assumed"]
    for name in ("architecture.layers", "architecture.experts_held",
                 "architecture.vocab_held", "architecture.block_length", "noise",
                 "architecture.mask_id", "labels", "attention.qk_norm", "positions",
                 "rotary", "router", "architecture.balance_loss_weight",
                 "intermediate_size", "architecture.train_context", "optimizer",
                 "initialiser", "train_job.sequences_per_chip"):
        assert len(assumed[name]) > 40, name
    # what the model class gives and config.json does not: said so
    for name in ("labels", "attention.qk_norm"):
        assert "from memory" in assumed[name] and "config.json does not" in assumed[name]
    # the draws are stated as a rule a reference can follow
    for said in ("fold_in(state.key, state.step)", "make_rng('diffusion')",
                 "jax.random.split", "jax.random.key(0)", "1 - (1 - noise_eps) U_b"):
        assert said in assumed["noise"], said
    # the equations
    for said in ("n_i -> n_j iff b(i) = b(j)", "n_i -> c_j iff b(j) < b(i)",
                 "c_i -> c_j iff b(j) <= b(i)", "c_i -> n_j never", "no shift by one",
                 "p_i / sum of the 8 chosen p"):
        assert said in body["description"], said
    assert body["costs"] == body["reference"] == "sdar_moe"


def test_the_cell_is_one_chip_on_its_own_traffic_and_driver_by_appended_entries():
    cell = CATALOG.cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "train_device_tokens_diffusion"
    assert cell.traffic["driver"] == "lm_diffusion_train_step"
    # the accepted share traffic's numbers
    share = CATALOG.traffic("train_device_tokens_share")
    for key in ("warmup_steps", "chunk_steps", "trace_steps"):
        assert cell.traffic[key] == share[key]
    assert CELL in [w["name"] for w in CATALOG.benchmark["workloads"]]
    assert {m["name"] for m in cell.end_to_end} == {
        "train_items_per_s_per_chip", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert names >= {
        *NEW, "models.mfu", "models.fwd_bwd_ms_per_step", "models.fwd_ms_per_step",
        "models.bwd_ms_per_step", "kernels.opt_update_ms_per_step",
        "kernels.opt_update_roofline", "kernels.opt_kernel_ms_per_step",
        "entry.lower_s", "entry.init_state_s", "entry.compiles_in_window",
        "device.idle_frac", "device.hbm_peak_frac", "models.attn_ms_per_step",
        "models.lm_head_ms_per_step", "models.recompute_ms_per_step",
        "kernels.flash_attn_roofline", "models.moe_ms_per_step",
        "models.moe_load_max_over_mean", "models.moe_held_row_share",
        "kernels.moe_held_roofline"}
    # the mask is a mode of the two accepted kernels: no second roofline name
    assert not [m for m in CATALOG.benchmark["per_layer"]
                if "roofline" in m["name"] and "diffusion" in m["name"]]
    mine = [m for m in CATALOG.benchmark["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in mine] == list(NEW)
    for m in mine:
        assert CELL in m["workloads"] and m["layer"] == "models"
        assert m["moves"] == "train_items_per_s_per_chip"
    # the cells the benchmark had report neither of the new two
    for other in ("resnet50.train", "olmoe_1b_7b.train_seq4096",
                  "glm_4_7_flash.train_seq8192", "trinity_mini.train_seq8192"):
        assert not {m["name"] for m in CATALOG.cell(other).per_layer} & set(NEW)
    why = [w for w in CATALOG.benchmark["workloads"] if w["name"] == CELL][0]["why"]
    assert len(why) <= 200 and "1/8" in why and "16,384 rows" in why and "[MASK]" in why
    # the cell asks for no second four-chip cell: the one there stays
    four = [w["name"] for w in CATALOG.benchmark["workloads"] if w["chips"] == 4]
    assert CELL not in four and "resnet50.train_dp4" in four


def test_the_configuration_states_the_sizes_the_program_builds():
    """The parameter count, the share, the block length, the mask's id and
    every width of the file equal the program's module at the cell's own
    settings (config file + overrides)."""
    import distribuuuu_tpu.config as program_config
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.config import cfg

    body = CATALOG.config("sdar_30b_a3b")
    arch = body["architecture"]
    program_config.reset_cfg()
    program_config.merge_from_file(f"{REPO}/{body['program']['cfg_file']}")
    assert (cfg.LM.SHARE_CHIPS, cfg.LM.SHARE_RANK, cfg.MODEL.NUM_CLASSES) == (8, 0, 151936)
    cfg.merge_from_list([str(x) for kv in body["program"]["overrides"].items() for x in kv])
    assert cfg.OPTIM.BASE_LR == 3e-4  # the yaml's, from scratch
    # the cell's own rate, handed over as lm_train_step.configure does: "3e-05"
    cfg.merge_from_list(["OPTIM.BASE_LR", str(body["train_job"]["lr"])])
    cfg.MESH.DATA = 8
    try:
        model = trainer.build_model_from_cfg()
        assert (cfg.OPTIM.OPTIMIZER, cfg.OPTIM.BETA1, cfg.OPTIM.BETA2,
                cfg.OPTIM.WEIGHT_DECAY, cfg.OPTIM.BASE_LR) == (
            "adamw", 0.9, 0.95, 0.1, 3e-5)
        assert cfg.LM.SEQ_LEN == arch["train_context"]
        assert cfg.MODEL.MOE.AUX_WEIGHT == arch["balance_loss_weight"]
    finally:
        program_config.reset_cfg()
    built = {
        "layers": len(model.layer_kinds), "hidden_size": model.dim,
        "intermediate_size": model.mlp_hidden,
        "moe_intermediate_size": model.expert_hidden,
        "num_attention_heads": model.num_heads, "num_key_value_heads": model.kv_heads,
        "head_dim": model.head_dim, "num_experts": model.num_experts,
        "num_experts_per_tok": model.top_k, "vocab_size": model.vocab_size,
        "train_context": model.seq_len, "rms_norm_eps": model.norm_eps,
        "rope_theta": model.rope_theta, "share_chips": model.share_chips,
        "share_rank": model.share_rank, "experts_held": model.held[1],
        "vocab_held": model.vocab_held, "balance_loss_weight": model.aux_weight,
        "block_length": model.block_length, "noise_eps": model.noise_eps,
        "mask_id": model.mask_token,
    }
    assert built == {key: arch[key] for key in built}
    assert model.recompute is True and model.dense_here == 0
    assert len(type(model)().layer_types) == body["num_hidden_layers"]
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    params = shapes["params"]

    def count(tree):
        return sum(x.size for x in jax.tree.leaves(tree))

    # the configuration's table, row by row
    assert count(params["Block_0"]["attn"]) == 18_874_624
    assert count(params["Block_0"]["moe"]) == 262_144 + 16 * 4_718_592
    assert [count(params[f"Block_{i}"]) for i in range(6)] == [94_638_336] * 6
    assert count(params["tok_embed"]) == count(params["head"]) == 38_895_616
    assert count(params) == arch["parameters"] == 645_623_296
    assert "batch_stats" not in shapes  # this router has no bias: no other state
    assert params["Block_1"]["moe"]["w_gate"].shape == (16, 2048, 768)
    assert params["Block_1"]["moe"]["router"].shape == (2048, 128)
    attn = params["Block_2"]["attn"]
    assert attn["q_proj"]["kernel"].shape == (2048, 4096)
    assert attn["k_proj"]["kernel"].shape == (2048, 512)
    assert params["head"].shape == (2048, 18992)


def test_costs_count_what_the_issue_counts_and_a_hand_count_at_the_tiny_size():
    costs = CATALOG.costs("sdar_moe")
    body = CATALOG.config("sdar_30b_a3b")
    arch = body["architecture"]
    assert costs.mixtures(arch) == 6
    # both rows of a data token through the four projections
    assert costs.projection_macs_per_token(arch) == 6 * 2 * 18_874_368 == 226_492_416
    # the pairs the mask KEEPS, never the tiles a kernel visits: S + B keys a
    # data token over its two rows, (S - B) / 2 + B and (S + B) / 2
    assert costs.keys_per_token(arch) == 8196 == (8192 - 4) / 2 + 4 + (8192 + 4) / 2
    assert costs.attention_macs_per_token(arch) == 6 * 8196 * 32 * 256 == 402_849_792
    assert costs.expert_macs_per_row(arch) == 4_718_592
    assert costs.held_expert_macs_per_token(arch) == 6 * 2 * 8 * 4_718_592 / 8
    assert costs.held_expert_macs_per_token(arch, 0.25) == 6 * 2 * 8 * 4_718_592 / 4
    total = costs.forward_macs_per_item(arch)
    assert total == 226_492_416 + 402_849_792 + 6 * 2 * 262_144 + 56_623_104 + (
        38_895_616) == 728_006_656
    shares = {"mask": 402_849_792 / total, "projections": 226_492_416 / total,
              "experts": 56_623_104 / total, "head": 38_895_616 / total}
    assert {k: round(100 * v) for k, v in shares.items()} == {
        "mask": 55, "projections": 31, "experts": 8, "head": 5}
    # 35.8 TFLOP a step of 8192 DATA tokens
    flops = CATALOG.costs("common").train_flops(total)
    assert 8192 * flops == pytest.approx(35.78e12, rel=1e-3)
    # brute force at a small size: the pairs the four rules keep
    seq, block = 96, 8
    rules = CATALOG.reference("sdar_moe").kept(jnp.arange(2 * seq), seq, block)
    small = {**arch, "train_context": seq, "block_length": block}
    assert int(rules.sum()) == seq * costs.keys_per_token(small) == seq * (seq + block)
    # by hand at the rehearsal's size: 4 layers of 64, 4 heads on 1 of 32,
    # blocks of 4 in 128 positions, 8 experts of 32 with 2 a row and 4 held
    tiny = body["rehearse"]["architecture"]
    assert costs.projection_macs_per_token(tiny) == 4 * 2 * (2 * 64 * 128 + 2 * 64 * 32)
    assert costs.attention_macs_per_token(tiny) == 4 * 132 * 4 * 64
    assert costs.held_expert_macs_per_token(tiny) == 4 * 2 * 2 * 0.5 * 3 * 64 * 32
    assert costs.forward_macs_per_item(tiny) == 163_840 + 135_168 + 4 * 2 * 512 + (
        49_152) + 64 * 256 == 368_640


PRE = "jit(train_step)/jvp(fwd)/SDARMoE/"
BACK = "jit(train_step)/bwd/transpose(jvp(fwd))/SDARMoE/"
AGAIN = BACK + "jvp(fwd)/SDARMoE/checkpoint/rematted_computation/"
NOISE = "diffusion_noise/threefry2x32"
NOISED = "diffusion_noise/select_n"
Q_PROJ = "Block_1/attn/attn/attn_diffusion/q_proj/dot_general"
FLASH = "Block_1/attn/attn/attn_diffusion/dtpu_flash_fwd/pallas_call"
FLASH_BWD = "Block_1/attn/attn/attn_diffusion/dtpu_flash_bwd/pallas_call"
ROUTE = "Block_1/moe/moe/moe_route/sort"
EXPERTS = "Block_1/moe/moe/moe_experts/dtpu_moe_gmm_gate_up/pallas_call"
HEAD = ("jit(train_step)/jvp(fwd)/SDARMoE.head_loss/lm_head/head_loss_fp32/"
        "bcd,dv->bcv/dot_general")
UPDATE = "jit(train_step)/optimizer_update/opt_kernel/dtpu_opt_update_adamw/pallas_call"


def test_the_two_readers_and_the_accepted_ones_on_synthetic_events():
    """Two steps; per step, ms: the draws 0.3 and the noised copy 0.1; a
    q projection 4 forward and 4 again, the flash forward 50 and backward 100,
    routing 2, the held experts' kernel 4, head 7, update 5."""
    events, t = [], 0
    for _step in range(2):
        for name, dur, op_name in (
            ("fusion.1", 0.3e6, PRE + NOISE), ("fusion.2", 0.1e6, PRE + NOISED),
            ("fusion.3", 4e6, PRE + Q_PROJ),
            ("dtpu_flash_fwd.1", 50e6, PRE + FLASH),
            ("fusion.4", 2e6, PRE + ROUTE),
            ("dtpu_moe_gmm_gate_up.1", 4e6, PRE + EXPERTS),
            ("fusion.5", 7e6, HEAD), ("fusion.6", 4e6, AGAIN + Q_PROJ),
            ("dtpu_flash_bwd.1", 100e6, BACK + FLASH_BWD),
            ("dtpu_opt_update_adamw.1", 5e6, UPDATE),
        ):
            events.append(op(name, t, dur, op_name))
            t += dur
    observed = observed_for(events, {
        "trace_steps": 2, "tokens_per_step": 8192, "moe_held_row_share": 0.2,
        "diffusion_masked_share": 0.4987,
    })
    peaks = CATALOG.peaks("TPU v5 lite")
    assert read_new(observed) == {
        "models.diffusion_noise_ms_per_step": pytest.approx(0.4),
        "models.diffusion_masked_share": 0.4987,
    }
    # the accepted readers the cell lists read this program too
    assert reader("models.fwd_bwd_ms_per_step").read(observed) == pytest.approx(171.4)
    assert reader("models.bwd_ms_per_step").read(observed) == pytest.approx(104.0)
    assert reader("models.attn_ms_per_step").read(observed) == pytest.approx(158.0)
    assert reader("models.lm_head_ms_per_step").read(observed) == pytest.approx(7.0)
    assert reader("models.moe_ms_per_step").read(observed) == pytest.approx(6.0)
    assert reader("kernels.opt_update_ms_per_step").read(observed) == pytest.approx(5.0)
    assert reader("models.recompute_ms_per_step").read(observed) == pytest.approx(4.0)
    assert reader("models.moe_held_row_share").read(observed) == 0.2
    # per DATA token: both rows, the pairs the mask keeps, over every flash call
    flash = reader("kernels.flash_attn_roofline").read(observed)
    assert flash == pytest.approx(
        100 * 6 * 402_849_792 * 8192 / peaks["bf16_flops_per_s"] / 0.150)
    assert 60 < flash < 70
    assert reader("kernels.moe_held_roofline").read(observed) == pytest.approx(
        100 * 6 * 6 * 2 * 8 * 0.2 * 4_718_592 * 8192 / peaks["bf16_flops_per_s"] / 0.004)
    # models.mfu counts DATA tokens: 1.0 item/s here
    assert reader("models.mfu").read(observed) == pytest.approx(
        6 * 728_006_656 / peaks["bf16_flops_per_s"])


def test_the_readers_find_nothing_in_a_program_without_the_scope_or_the_metric():
    """The parent's kind of program (Trinity-Mini's step, GLM's, OLMoE's, a
    conv net's), read in ITS cell and in this one: each new reader returns
    None and raises nothing, with and without a trace."""
    events = [
        op("fusion.1", 0, 10e6, "jit(train_step)/jvp(fwd)/GLMMoE/Block_1/moe/moe/moe_route/sort"),
        op("dtpu_flash_fwd.1", 10e6, 5e6,
           "jit(train_step)/jvp(fwd)/AfMoE/Block_1/attn/attn/attn_window/dtpu_flash_fwd/pallas_call"),
        op("fusion.2", 15e6, 10e6,
           "jit(train_step)/jvp(fwd)/ResNet/ConvBN_0/conv_general_dilated"),
        op("dtpu_opt_update_sgd.1", 25e6, 5e6,
           "jit(train_step)/optimizer_update/opt_kernel/dtpu_opt_update_sgd/pallas_call"),
    ]
    counters = {"trace_steps": 1, "tokens_per_step": 16384, "moe_held_row_share": 0.2}
    for cell in (CELL, "trinity_mini.train_seq8192", "glm_4_7_flash.train_seq8192",
                 "olmoe_1b_7b.train_seq4096", "resnet50.train"):
        assert read_new(observed_for(events, counters, cell)) == dict.fromkeys(NEW), cell
        assert read_new(observed_for(None, {}, cell)) == dict.fromkeys(NEW), cell


def test_a_program_without_the_arch_is_refused_before_the_device(tmp_path):
    """What the parent of this configuration's PR does on the cell where the
    benchmark's files are laid over it: a DiscoveryError and a non-zero exit,
    at once."""
    root = make_root(tmp_path)
    path = f"{root}/benchmark/configs/sdar_30b_a3b.json"
    with open(path) as f:
        body = json.load(f)
    body["rehearse"]["program"]["arch"] = "sdar_of_a_later_pr"
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


def test_rehearsal_runs_the_driver_end_to_end(rehearsal):
    code, out, err = rehearsal
    assert code == 0, err[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 4
    for term in (*TERMS, "masked_share", "held_row_share"):
        assert f"reference: {term} step" in out
    compared = line["compared"]
    assert list(line)[-1] == "compared"
    forward = {*TERMS, "held_row_share", "masked_share", "experts_disagreeing",
               "expert_tie_margin", "noise_disagreeing"}
    # the draw's step and first gradient, then the timed (sharpened) state's step
    assert set(compared) == {
        *forward, *(f"timed_{name}" for name in forward), "gradient",
        "gradient_experts", "gradient_router", "update", "second_moment",
        "losses_not_finite", "loss_did_not_fall", "rows_dropped",
        "traced_kernels_missing"}
    assert all(c["value"] <= c["limit"] for c in compared.values())
    for prefix in ("", "timed_"):
        assert compared[prefix + "noise_disagreeing"] == {"value": 0.0, "limit": 0}
        assert compared[prefix + "experts_disagreeing"]["value"] == 0
        assert f"reference: {prefix}ce step" in out
    said = [ln for ln in err.splitlines() if ln.startswith("compared ")]
    assert len(said) == len(compared) and err.rstrip().endswith(said[-1])
    assert "DISAGREES" not in out
    # items are DATA tokens: 2 sequences of 128, not their 512 rows
    assert "steps of 256 data tokens" in out
    assert "moe_dropped max 0;" in out and "share of the positions masked 0." in out
    assert "router_bias" not in out  # no bias anywhere in this program's state


def test_the_tolerances_have_teeth(rehearsal):
    """The reference computed in bfloat16 throughout fails the rehearsal's
    limits. The same reading at the published widths is a chip run's
    (PERF.md)."""
    _code, out, _err = rehearsal
    teeth = [ln for ln in out.splitlines() if "teeth:" in ln]
    assert sum("fails, as it must" in ln for ln in teeth) >= 6
    # once for the draw's step, once for the timed state's
    verdicts = [ln for ln in teeth if "throughout fails" in ln]
    assert len(verdicts) == 2 and "timed_limits" in verdicts[1]
    assert all(" 0 of " not in ln for ln in verdicts)
    # the draws are float32 whatever the precision: a position is masked or not
    assert any("masked_share of the reference in bfloat16 0.000e+00" in ln for ln in teeth)


STEP_KEY = 9  # the key of the tiny step below


@functools.lru_cache(maxsize=None)
def _tiny_state(seed, layers, ids):
    """Weights and batch of the tiny model: a planted fault changes what the
    model computes, never what it holds."""
    from distribuuuu_tpu import models

    model = models.build_model("sdar_moe_tiny", dtype=jnp.float32, depth=layers)
    k_init, k_tok = jax.random.split(jax.random.key(seed))
    variables = flax.linen.meta.unbox(
        jax.jit(model.init)(k_init, jnp.zeros((1, 8), jnp.int32)))
    return variables["params"], jax.random.randint(k_tok, (2, 64), 0, ids, jnp.int32)


def tiny(seed=5):
    """(model, reference, architecture, params, tokens) at the rehearsal
    size, two sequences of 64 tokens drawn from every held row but the
    mask's. The model is built NOW, under whatever a test has planted."""
    from distribuuuu_tpu import models

    arch = CATALOG.config("sdar_30b_a3b")["rehearse"]["architecture"]
    model = models.build_model("sdar_moe_tiny", dtype=jnp.float32, depth=arch["layers"])
    return (model, CATALOG.reference("sdar_moe"), arch,
            *_tiny_state(seed, arch["layers"], arch["mask_id"]))


@functools.lru_cache(maxsize=None)
def _reference_side():
    """(terms, routing and draws; gradient) of the float32 reference on the
    tiny batch under the step's key: the same for the sound program and for
    every planted fault."""
    driver = CATALOG.driver("lm_diffusion_train_step")
    _, reference, arch, params, tokens = tiny()
    key = driver.stream_key(jax.random.key(STEP_KEY))
    want, want_grads = jax.jit(lambda p: driver.reference_terms(
        reference, arch, p, tokens, key))(params)
    return jax.device_get(want), want_grads


def compared_for():
    """The driver's numbers (``numbers``: terms, held and masked share,
    routing, the draws, the three gradient classes) for the tiny model's
    step, built NOW (under whatever a test has planted), against the float32
    reference, at the rehearsal's limits."""
    driver = CATALOG.driver("lm_diffusion_train_step")
    body = CATALOG.config("sdar_30b_a3b")
    job = {**body["train_job"], **body["rehearse"]["train_job"]}
    model, _, arch, params, tokens = tiny()

    def program(p):
        outputs, sown = model.apply(
            {"params": p}, tokens, train=True, hidden_only=True,
            rngs={driver.NOISE_STREAM: jax.random.key(STEP_KEY)},
            mutable=["moe_route", "diffusion_noise"])
        loss, _, extra = model.head_loss(outputs, model.head_kernel(p), tokens, topk=(1, 5))
        return loss, (extra, sown)

    (loss, (extra, sown)), grads = jax.jit(
        jax.value_and_grad(program, has_aux=True))(params)
    want, want_grads = _reference_side()
    errors = {
        jax.tree_util.keystr(path): {
            "gradient": float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)),
            "update": 0.0, "second_moment": 0.0}
        for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                                jax.tree.leaves(want_grads), strict=True)}
    chosen = np.stack([
        np.asarray(sown["moe_route"][name]["moe"]["experts"][0]).reshape(
            -1, arch["num_experts_per_tok"])
        for name in driver.mixture_names(arch)])
    draws = {k: np.asarray(v[0]) for k, v in sown["diffusion_noise"].items()}
    step = {"ce": extra["ce"], "load_balance": extra["moe_aux"], "loss": loss,
            "held_row_share": extra["moe_held_row_share"],
            "masked_share": extra["diffusion_masked_share"]}
    run = types.SimpleNamespace(
        catalog=CATALOG, section=lambda name: {"architecture": arch}[name])
    return driver.numbers(run, job, want, step, chosen, errors, draws)


def failed(compared) -> set:
    return {name for name, c in compared.items() if not c["value"] <= c["limit"]}


def test_the_sound_program_is_within_every_limit():
    compared = compared_for()
    assert failed(compared) == set()
    assert compared["noise_disagreeing"]["value"] == 0.0


def _mask_fault(change):
    """A block-diffusion mask with one of its four rules changed: the dense
    path of ``models/olmoe._attend`` (this size's) asks
    ``ops/flash_attention.diffusion_mask`` for it."""
    def wrong(rows, block):
        row = jnp.arange(rows)
        clean, blk = row >= rows // 2, row % (rows // 2) // block
        q_clean, k_clean = clean[:, None], clean[None, :]
        qb, kb = blk[:, None], blk[None, :]
        rules = {"nn": kb == qb, "nc": kb < qb, "cc": kb <= qb,
                 "cn": jnp.zeros((rows, rows), bool), **change(qb, kb)}
        return jnp.where(q_clean, jnp.where(k_clean, rules["cc"], rules["cn"]),
                         jnp.where(k_clean, rules["nc"], rules["nn"]))
    return wrong


MASK_FAULTS = {
    # clean rows reading the noised keys of their own and earlier blocks
    "clean_rows_read_noised_keys": lambda qb, kb: {"cn": kb <= qb},
    # a noised row reading its own block's clean keys: the answer
    "a_noised_row_reads_its_blocks_answer": lambda qb, kb: {"nc": kb <= qb},
    # the block diagonal missing: a noised row reads only its own key there
    "the_block_diagonal_missing": lambda qb, kb: {
        "nn": jnp.eye(qb.shape[0], dtype=bool)},
}


@pytest.mark.parametrize("fault", sorted(MASK_FAULTS))
def test_a_changed_rule_of_the_mask_fails_a_limit(fault, monkeypatch):
    from distribuuuu_tpu.ops import flash_attention as fa

    sound = np.asarray(fa.diffusion_mask(128, 4))
    np.testing.assert_array_equal(_mask_fault(lambda qb, kb: {})(128, 4), sound)
    monkeypatch.setattr(fa, "diffusion_mask", _mask_fault(MASK_FAULTS[fault]))
    assert failed(compared_for()) >= {"ce", "loss", "gradient"}


def test_a_plain_causal_mask_over_the_rows_fails_a_limit(monkeypatch):
    """The chip run's planted fault: the 2S rows under the mask every other
    decoder here runs."""
    from distribuuuu_tpu.ops import flash_attention as fa

    monkeypatch.setattr(
        fa, "diffusion_mask", lambda rows, block: jnp.tril(jnp.ones((rows, rows), bool)))
    assert failed(compared_for()) >= {"ce", "loss", "gradient"}


def test_positions_that_run_on_through_the_clean_copy_fail_a_limit(monkeypatch):
    """Positions ``0..2S-1`` in place of ``0..S-1`` twice."""
    from distribuuuu_tpu.models import lfm2_moe, sdar_moe

    class RunsOn(lfm2_moe.Attention):
        def __call__(self, x, positions):
            return super().__call__(x, jnp.arange(x.shape[1], dtype=jnp.int32))

    monkeypatch.setattr(sdar_moe, "Attention", RunsOn)
    assert failed(compared_for()) >= {"gradient"}


LOSS_FAULTS = {
    # the 1 / t weight dropped: a plain sum over the masked positions
    "the_weight_dropped": lambda noise: noise["masked"].astype(jnp.float32),
    # the loss over every position, masked or not
    "every_position": lambda noise: 1.0 / noise["level"],
}


@pytest.mark.parametrize("fault", sorted(LOSS_FAULTS))
def test_a_changed_weight_of_the_loss_fails_a_limit(fault, monkeypatch):
    from distribuuuu_tpu.models import sdar_moe

    monkeypatch.setattr(
        sdar_moe.SDARMoE, "loss_weights", staticmethod(LOSS_FAULTS[fault]))
    assert failed(compared_for()) >= {"ce", "loss", "gradient"}


def test_labels_shifted_by_one_fail_a_limit(monkeypatch):
    """Next-token labels, as every other decoder here is trained."""
    from distribuuuu_tpu.models import sdar_moe

    own = sdar_moe.SDARMoE.head_labels
    monkeypatch.setattr(
        sdar_moe.SDARMoE, "head_labels",
        lambda self, labels: own(self, jnp.roll(labels, -1, axis=1)))
    assert failed(compared_for()) >= {"ce", "loss", "gradient"}


def test_weights_that_are_not_renormalised_fail_a_limit(monkeypatch):
    """``norm_topk_prob`` false: the chosen probabilities as they are."""
    from distribuuuu_tpu.ops import moe as moe_ops

    real = moe_ops.softmax_route
    monkeypatch.setattr(
        moe_ops, "softmax_route", lambda *a, renormalise=False, **kw: real(*a, **kw))
    assert failed(compared_for()) >= {"gradient", "gradient_router"}


def test_draws_of_another_rule_fail_the_noise_agreement(monkeypatch):
    """The program drawing from another fold of the step's key than the rule
    states: other levels, other positions masked; the terms then differ too,
    but ``noise_disagreeing`` says why."""
    from distribuuuu_tpu.models import sdar_moe

    real = sdar_moe.draw_noise
    monkeypatch.setattr(
        sdar_moe, "draw_noise",
        lambda key, *sizes: real(jax.random.fold_in(key, 1), *sizes))
    compared = compared_for()
    assert compared["noise_disagreeing"]["value"] > 0.3
    assert failed(compared) >= {"noise_disagreeing", "masked_share", "ce"}


def test_the_references_blocks_change_no_value(monkeypatch):
    """On the chip the reference takes 512 query rows and 2048 head rows at
    a time so that 16,384 rows fit; blocks of 32 query rows and 32 head rows
    at the CPU's size give the unblocked terms and gradient."""
    driver = CATALOG.driver("lm_diffusion_train_step")
    _, reference, arch, params, tokens = tiny()
    assert reference.QUERY_BLOCK >= 2 * 64 and reference.ROW_BLOCK >= 2 * 64
    whole, grads = _reference_side()
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)
    monkeypatch.setattr(reference, "ROW_BLOCK", 32)
    key = driver.stream_key(jax.random.key(STEP_KEY))
    blocked, blocked_grads = jax.jit(lambda p: driver.reference_terms(
        reference, arch, p, tokens, key))(params)
    for term in (*TERMS, "held_row_share", "masked_share"):
        np.testing.assert_allclose(blocked[term], whole[term], rtol=1e-6, err_msg=term)
    np.testing.assert_array_equal(blocked["experts"], whole["experts"])
    np.testing.assert_array_equal(blocked["masked"], whole["masked"])
    for a, b in zip(jax.tree.leaves(blocked_grads), jax.tree.leaves(grads), strict=True):
        assert float(jnp.linalg.norm(a - b)) <= 1e-5 * float(jnp.linalg.norm(b))


def test_the_drivers_batch_mixtures_and_gradient_classes_for_this_model():
    """Labels are the inputs, unshifted, and never the mask; every block is a
    mixture; the mixtures' pre-norms count among the EXPERTS (no shared
    expert carries the rows a flip moved, as in LFM2's cell)."""
    driver = CATALOG.driver("lm_diffusion_train_step")
    share = CATALOG.driver("lm_share_train_step")
    body = CATALOG.config("sdar_30b_a3b")
    avals = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32, sharding=jax.sharding.
                                     SingleDeviceSharding(jax.devices()[0]))
             for k in ("image", "label")}
    batch = driver.make_batch(jax.random.key(3), avals, 0, 18991)
    np.testing.assert_array_equal(batch["image"], batch["label"])
    assert 0 <= int(batch["image"].min()) and int(batch["image"].max()) < 18991
    assert driver.mixture_names(body["architecture"]) == [f"Block_{i}" for i in range(6)]
    paths = ["['Block_1']['input_norm']['scale']",
             "['Block_1']['post_attention_norm']['scale']",
             "['Block_1']['moe']['w_up']", "['Block_1']['moe']['router']",
             "['Block_1']['attn']['q_proj']['kernel']", "['head']"]
    classes = driver.gradient_classes(share, body["architecture"], dict.fromkeys(paths))
    assert classes == {
        "gradient_router": [paths[3]], "gradient_experts": [paths[2], paths[1]],
        "gradient": [paths[0], paths[4], paths[5]]}
    # flax's fold of the stream, not the key itself
    key = jax.random.key(1)
    assert not np.array_equal(jax.random.key_data(driver.stream_key(key)),
                              jax.random.key_data(key))


def test_the_cells_weights_are_the_draw_with_the_head_norms_scales_alone_changed():
    """``sharpened``: every q and k head norm's scale times
    ``train_job.head_norm_scale``, at the cell's size and the rehearsal's
    alike, and no other leaf touched (the block norms' scales least of all)."""
    driver = CATALOG.driver("lm_diffusion_train_step")
    body = CATALOG.config("sdar_30b_a3b")
    scale = body["train_job"]["head_norm_scale"]
    assert scale == body["rehearse"]["train_job"]["head_norm_scale"] == 3.0
    assert "train_job.head_norm_scale" in body["assumed"]
    # the timed state's step is held to limits of its own, forward numbers only
    for job in (body["train_job"], body["rehearse"]["train_job"]):
        assert set(job["timed"]) == {
            "reference_tolerance", "expert_agreement_min", "expert_tie_margin"}
        assert set(job["timed"]["reference_tolerance"]) == {
            *TERMS, "held_row_share", "masked_share"}
    leaf = jnp.ones((4,))
    params = {"Block_0": {"attn": {"q_norm": {"scale": leaf}, "k_norm": {"scale": leaf},
                                   "q_proj": {"kernel": leaf}},
                          "input_norm": {"scale": leaf}, "moe": {"router": leaf}},
              "final_norm": {"scale": leaf}, "head": leaf}
    out = driver.sharpened(params, scale)
    changed = {jax.tree_util.keystr(path) for path, x in
               jax.tree_util.tree_leaves_with_path(out) if not np.array_equal(x, leaf)}
    assert changed == {"['Block_0']['attn']['q_norm']['scale']",
                       "['Block_0']['attn']['k_norm']['scale']"}
    np.testing.assert_array_equal(out["Block_0"]["attn"]["q_norm"]["scale"], leaf * scale)


def test_the_cell_compiles_for_the_chip_here_under_its_scopes(monkeypatch):
    """What ``benchmark/rehearse_compile.py`` does with the cell (the
    configuration's own overrides into the driver's ``compile_only``), here
    in this process: the real-size step (published widths, 1 x 8192 data
    tokens = 16,384 rows, 16 of 128 experts and 18,992 vocabulary rows held,
    every block recomputed) at TWO of its six layers (the whole cell is the
    builder's to run: PERF.md), compiled by the installed XLA:TPU and Mosaic
    for a described v5e, with no chip. It fits, reduces nothing across chips,
    holds no ``while`` (the TPU unrolls threefry's rounds) and no ``[2S, 2S]``
    array; the benchmark's readers find in it every scope they sum,
    ``attn_diffusion`` inside ``attn`` with both flash kernels under it, the
    draws under ``diffusion_noise``; the blocks run again in the backward
    without the flash forward kernel, ``o_proj``, ``v_proj`` or the
    combine."""
    import time

    from jax.experimental import topologies

    import distribuuuu_tpu.config as program_config
    from benchmark.harness import cli
    from benchmark.harness.trace import in_scope, op_names_from_hlo
    from distribuuuu_tpu import models
    from distribuuuu_tpu.ops import pallas as kernel_tier

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
    body = CATALOG.config("sdar_30b_a3b")
    overrides = {**body["program"]["overrides"], "LM.LAYERS": 2,
                 "KERNELS.OPT_UPDATE": "pallas"}
    run = cli.Run(CATALOG, CATALOG.cell(CELL), [
        "--workload", CELL, "--set", "program.overrides=" + json.dumps(overrides),
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
    assert "s32[1,8192]" in text  # the cell's batch: DATA tokens
    assert "16384,16384" not in text  # no [2S, 2S] array of any type
    paths = list(op_names_from_hlo(text).values())
    for scope in ("fwd", "bwd", "diffusion_noise", "attn", "attn_diffusion", "moe",
                  "moe_route", "moe_experts", "lm_head", "optimizer_update",
                  "opt_kernel", "rematted_computation"):
        assert any(in_scope(p, scope) for p in paths), scope
    assert all(in_scope(p, "attn") for p in paths if in_scope(p, "attn_diffusion"))
    assert not any(in_scope(p, "moe_shared") for p in paths)
    noise = [p for p in paths if in_scope(p, "diffusion_noise")]
    assert not any(in_scope(p, "bwd") or in_scope(p, "attn") for p in noise)
    calls = {}
    for line in text.splitlines():
        if "custom-call(" in line and "dtpu_" in line:
            name = line.split(" = ")[0].split()[-1].lstrip("%").rsplit(".", 1)[0]
            calls.setdefault(name, []).append(line.split('op_name="')[1].split('"')[0])
    for kernel in ("dtpu_flash_fwd", "dtpu_flash_bwd"):
        assert len(calls[kernel]) == 2, kernel  # a call a layer
        assert all(in_scope(p, "attn_diffusion") for p in calls[kernel])
    assert not any(in_scope(p, "rematted_computation") or in_scope(p, "bwd")
                   for p in calls["dtpu_flash_fwd"])
    recomputed = [p for p in paths if in_scope(p, "rematted_computation")]
    assert any("q_proj/dot_general" in p for p in recomputed)  # its head norm's backward
    assert not any("v_proj/dot_general" in p or "o_proj/dot_general" in p
                   for p in recomputed)
    assert not any(in_scope(p, "rematted_computation")
                   for p in calls["dtpu_moe_rows_combine"])
    gmm = {k: len(v) for k, v in calls.items() if "moe_gmm" in k}
    assert gmm == {
        "dtpu_moe_gmm_gate_up": 4, "dtpu_moe_gmm_fwd": 4, "dtpu_moe_gmm_act_bwd": 2,
        "dtpu_moe_gmm_dx_gate_up": 2, "dtpu_moe_gmm_dw_down": 2,
        "dtpu_moe_gmm_dw_gate_up": 2}
    # an AdamW call a leaf of the stage the cell's overrides build
    stage = models.build_model("sdar_30b_a3b", depth=2, share_chips=8)
    leaves = jax.tree.leaves(jax.eval_shape(
        stage.init, jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"])
    assert len(calls["dtpu_opt_update_adamw"]) == len(leaves)
