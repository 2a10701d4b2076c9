"""The Ouro configuration, its cell, its costs, its driver and its four
readers: what the files state against what the program builds, the readers on
synthetic events (and on a program without the scopes), and the cell's driver
at its rehearsal size through the real command."""

import json

import jax
import jax.numpy as jnp
import pytest

from benchmark.harness import trace
from benchmark.harness.discovery import Catalog
from benchmark.harness.observation import Observed
from benchmark.harness.trace import Reduction
from benchmark_testlib import REPO, finish, make_root, start_run

CELL = "ouro_2_6b.train_seq4096"
NEW = ("models.mlp_ms_per_step", "models.recompute_ms_per_step",
       "models.exit_gate_ms_per_step", "models.exit_step_mean")
CATALOG = Catalog()
TERMS = ("ce", "exit_entropy", "exit_step_mean", "loss", "ce_pass")


def published() -> dict:
    """``config.json`` of ByteDance/Ouro-2.6B as the catalog beside the
    ``model-configs`` guide holds it, or the same keys by hand where the
    guides are not installed."""
    try:
        with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
            rows = [json.loads(line) for line in f]
        return next(r for r in rows if r["name"] == "Ouro-2.6B")["config"]
    except (OSError, StopIteration):
        return {
            "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
            "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
            "max_position_embeddings": 65536, "max_window_layers": 48,
            "model_type": "ouro", "num_attention_heads": 16, "num_hidden_layers": 48,
            "num_key_value_heads": 16, "rms_norm_eps": 1e-06, "rope_scaling": None,
            "rope_theta": 1000000, "sliding_window": None,
            "tie_word_embeddings": False, "total_ut_steps": 4,
            "early_exit_threshold": 1, "use_sliding_window": False,
            "vocab_size": 49152,
        }


def op(name, start, dur, op_name=""):
    _, opcode = trace.parse_instruction(name)
    return {"plane": "/device:TPU:0", "line": "XLA Ops", "name": name,
            "opcode": opcode, "op_name": op_name, "start_ns": float(start),
            "dur_ns": float(dur)}


def observed_for(events, counters):
    cell = CATALOG.cell(CELL)
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


def test_the_configuration_is_the_published_one_cut_in_depth_only():
    body = CATALOG.config("ouro_2_6b")
    entry = [c for c in CATALOG.benchmark["configs"] if c["name"] == "ouro_2_6b"][0]
    assert entry["reduced"] == body["reduced"] == ["layers"]
    assert entry["source"] == body["source"] == (
        "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json")
    assert body["architecture"]["layers"] == body["layers"] == 8
    want = published()
    assert want["num_hidden_layers"] == 48 and want["total_ut_steps"] == 4
    for key, value in want.items():
        assert body[key] == value, key  # config.json's keys at the top level
    arch = body["architecture"]
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "vocab_size", "total_ut_steps",
                "rms_norm_eps", "rope_theta", "max_position_embeddings",
                "hidden_act", "tie_word_embeddings", "rope_scaling", "model_type"):
        assert arch[key] == want[key], key  # no width, no count, no pass cut
    job = body["train_job"]
    assert job["seq_len"] == arch["train_context"] == 4096
    assert job["sequences_per_chip"] == 1
    assert set(job["reference_tolerance"]) == {*TERMS, "gradient", "update",
                                               "second_moment"}
    assert job["adamw"] == {"b1": 0.9, "b2": 0.95, "eps": 1e-08, "weight_decay": 0.1}
    assert body["deployment"]
    for name in ("architecture.layers", "norms", "attention", "exit_gate", "loss",
                 "architecture.train_context", "optimizer", "initialiser", "costs",
                 "weights", "batch"):
        assert body["assumed"][name], name


def test_the_cell_is_one_chip_and_reports_what_the_issue_lists():
    cell = CATALOG.cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "train_device_tokens_dense"
    assert {k: cell.traffic[k] for k in ("driver", "warmup_steps", "chunk_steps",
                                         "trace_steps")} == {
        "driver": "lm_dense_train_step", "warmup_steps": 2, "chunk_steps": 3,
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
        # OLMoE's readers that read this program as it stands (below; PR 40)
        "models.attn_ms_per_step", "models.lm_head_ms_per_step",
        "kernels.flash_attn_roofline"}
    # the four new ones list this cell; a later decoder's cell may be
    # appended (`in`, not `==`)
    for m in CATALOG.benchmark["per_layer"]:
        if m["name"] in NEW:
            assert CELL in m["workloads"]
    # and the cells the benchmark had report what they reported
    for other in ("resnet50.train", "regnety_160.train", "resnet50.train_dp4",
                  "olmoe_1b_7b.train_seq4096"):
        assert not {m["name"] for m in CATALOG.cell(other).per_layer} & set(NEW)


def test_the_configuration_states_the_sizes_the_program_builds():
    """The parameter count and every width of the file equal the program's
    module at the cell's own settings (config file + overrides)."""
    import distribuuuu_tpu.config as program_config
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.config import cfg

    body = CATALOG.config("ouro_2_6b")
    arch = body["architecture"]
    program_config.reset_cfg()
    program_config.merge_from_file(f"{REPO}/{body['program']['cfg_file']}")
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
        "layers": model.depth, "total_ut_steps": model.passes,
        "hidden_size": model.dim, "intermediate_size": model.mlp_hidden,
        "num_attention_heads": model.num_heads, "vocab_size": model.vocab_size,
        "train_context": model.seq_len, "rms_norm_eps": model.rms_norm_eps,
        "rope_theta": model.rope_theta, "exit_entropy_weight": model.exit_beta,
    } == {key: arch[key] for key in (
        "layers", "total_ut_steps", "hidden_size", "intermediate_size",
        "num_attention_heads", "vocab_size", "train_context", "rms_norm_eps",
        "rope_theta", "exit_entropy_weight")}
    assert model.dim // model.num_heads == arch["head_dim"]
    shapes = jax.eval_shape(lambda: model.clone(passes=1).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    assert sum(x.size for x in jax.tree.leaves(shapes)) == arch["parameters"] == 612_438_017
    # the matrices the costs count are the tree's matrices: per layer 4 d^2 + 3 d f
    layer = shapes["Block_0"]
    matrices = sum(x.size for x in jax.tree.leaves(layer) if x.ndim == 2)
    assert matrices == 4 * 2048 ** 2 + 3 * 2048 * 5632
    assert shapes["head"].shape == (2048, 49152)
    assert shapes["exit_gate"]["kernel"].shape == (2048, 1)


def test_costs_count_what_the_issue_counts():
    costs = CATALOG.costs("ouro")
    arch = CATALOG.config("ouro_2_6b")["architecture"]
    assert costs.block_applications(arch) == 32
    assert costs.attention_macs_per_token(arch) == 268_435_456 == 32 * 2 * 2048 * 2048
    assert costs.mlp_macs_per_token(arch) == 32 * 3 * 2048 * 5632
    assert costs.forward_macs_per_item(arch) == 2_315_264_000 == (
        32 * (16_777_216 + 34_603_008) + 268_435_456 + 4 * 100_663_296 + 4 * 2048)
    # 13.89 GFLOP a token a step, 56.9 TFLOP a step of 4096 tokens
    flops = CATALOG.costs("common").train_flops(costs.forward_macs_per_item(arch))
    assert flops == pytest.approx(13.89e9, rel=1e-3)
    assert 4096 * flops == pytest.approx(56.9e12, rel=1e-3)
    # the balance the issue names: attention and MLP 83 %, the heads 17 %
    assert 4 * 100_663_296 / costs.forward_macs_per_item(arch) == pytest.approx(0.174, abs=1e-3)
    full = dict(arch, layers=48)  # every per-block term scales with depth
    assert costs.forward_macs_per_item(full) - costs.forward_macs_per_item(arch) == (
        40 * 4 * (51_380_224 + 2 * 2048 * 2048))


MLP = "jit(train_step)/jvp(fwd)/Ouro/loop_pass/Block_0/mlp/mlp/up_proj/dot_general"
MLP_AGAIN = ("jit(train_step)/bwd/transpose(jvp(fwd))/Ouro/loop_pass/jvp(fwd)/Ouro/loop_pass/"
             "checkpoint/rematted_computation/Block_0/mlp/mlp/up_proj/dot_general")
MLP_BWD = ("jit(train_step)/bwd/transpose(jvp(fwd))/Ouro/loop_pass/jvp(fwd)/Ouro/loop_pass/"
           "checkpoint/Block_0/mlp/mlp/up_proj/transpose")
FLASH_AGAIN = ("jit(train_step)/bwd/transpose(jvp(fwd))/Ouro/loop_pass/jvp(fwd)/Ouro/loop_pass/"
               "checkpoint/rematted_computation/Block_0/attn/attn/dtpu_flash_fwd/pallas_call")
GATE = "jit(train_step)/jvp(fwd)/Ouro/exit_gate/exit_gate/reduce_sum"
GATE_LOSS = "jit(train_step)/jvp(fwd)/Ouro.head_loss/exit_gate/jit(log_sigmoid)/log"
GATE_BWD = "jit(train_step)/bwd/transpose(jvp(fwd))/Ouro.head_loss/exit_gate/mul"
HEAD = "jit(train_step)/jvp(fwd)/Ouro.head_loss/lm_head/head_loss_fp32/bcd,dv->bcv/dot_general"
UPDATE = "jit(train_step)/optimizer_update/opt_kernel/dtpu_opt_update_adamw/pallas_call"


def test_the_four_readers_on_synthetic_events():
    """Two steps; per step: the MLP 5 forward + 5 again + 11 backward, the
    flash forward kernel's second run 4, the gate 1 + 0.5 + 0.5, head 7,
    update 3."""
    events, t = [], 0
    for _step in range(2):
        for name, dur, op_name in (
            ("fusion.1", 5e6, MLP), ("fusion.7", 1e6, GATE), ("fusion.8", 5e5, GATE_LOSS),
            ("fusion.3", 7e6, HEAD), ("fusion.9", 5e5, GATE_BWD),
            ("fusion.2", 5e6, MLP_AGAIN), ("dtpu_flash_fwd.1", 4e6, FLASH_AGAIN),
            ("fusion.4", 11e6, MLP_BWD), ("dtpu_opt_update_adamw.1", 3e6, UPDATE),
        ):
            events.append(op(name, t, dur, op_name))
            t += dur
    observed = observed_for(events, {
        "trace_steps": 2, "tokens_per_step": 4096, "exit_step_mean": 1.9,
    })
    assert read_new(observed) == {
        "models.mlp_ms_per_step": pytest.approx(21.0),
        "models.recompute_ms_per_step": pytest.approx(9.0),
        "models.exit_gate_ms_per_step": pytest.approx(2.0),
        "models.exit_step_mean": 1.9,
    }
    # the readers the cell shares with OLMoE's read this program's names too:
    # the head under the model's own hook, the recomputed kernel among flash's
    assert reader("models.lm_head_ms_per_step").read(observed) == pytest.approx(7.0)
    assert reader("models.fwd_bwd_ms_per_step").read(observed) == pytest.approx(34.0)
    assert reader("models.bwd_ms_per_step").read(observed) == pytest.approx(20.5)
    assert reader("models.fwd_ms_per_step").read(observed) == pytest.approx(13.5)
    peak = CATALOG.peaks("TPU v5 lite")["bf16_flops_per_s"]
    assert reader("kernels.flash_attn_roofline").read(observed) == pytest.approx(
        100 * 6 * 268_435_456 * 4096 / peak / 0.004)


def test_the_readers_find_nothing_in_a_program_without_the_scopes():
    """The parent's kind of program (OLMoE's step, a conv net's): every
    reader returns None and raises nothing, with and without a trace."""
    events = [
        op("fusion.1", 0, 10e6, "jit(train_step)/jvp(fwd)/OLMoE/Block_0/moe/moe/moe_route/sort"),
        op("fusion.2", 10e6, 10e6,
           "jit(train_step)/jvp(fwd)/ResNet/ConvBN_0/conv_general_dilated"),
        op("dtpu_opt_update_sgd.1", 20e6, 5e6,
           "jit(train_step)/optimizer_update/opt_kernel/dtpu_opt_update_sgd/pallas_call"),
    ]
    assert read_new(observed_for(events, {"trace_steps": 1})) == dict.fromkeys(NEW)
    assert read_new(observed_for(None, {})) == dict.fromkeys(NEW)


def test_a_program_without_the_arch_is_refused_before_the_device(tmp_path):
    """What the parent of this configuration's PR does on the cell: a
    DiscoveryError and a non-zero exit, at once."""
    root = make_root(tmp_path)
    path = f"{root}/benchmark/configs/ouro_2_6b.json"
    with open(path) as f:
        body = json.load(f)
    body["rehearse"]["program"]["arch"] = "ouro_of_a_later_pr"
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
    # its first step: the gradient on every leaf, the AdamW arithmetic
    for kind in ("gradient", "update", "second_moment"):
        assert f"reference: {kind} of the first step against" in out
    assert "worst of 38 leaves" in out  # 3 x 11 a block, embedding, head, norm, gate x 2
    assert out.count("agrees") == 8 and "DISAGREES" not in out
    assert "mean exit step 1." in out


def test_the_tolerances_have_teeth(rehearsal):
    """The reference computed in bfloat16 throughout (the nearest precision
    below the float32 the configuration states for residual stream, norms,
    gate, softmaxes and loss) fails every term's tolerance of the rehearsal,
    the worst 10x and more outside (where a float32 value falls on
    bfloat16's grid is luck, term by term). The same reading at the
    published widths is a chip run's (PERF.md)."""
    _code, out, _err = rehearsal
    teeth = [ln for ln in out.splitlines() if "teeth:" in ln]
    assert len(teeth) == 5 and all("fails, as it must" in ln for ln in teeth)
    assert max(float(ln.split("(relative ")[1].split(",")[0]) for ln in teeth) > 10 * 1e-5


def tiny():
    """(driver, reference, architecture, params, tokens, labels) at the
    rehearsal size, two sequences of 32 tokens."""
    import flax

    from distribuuuu_tpu import models

    arch = CATALOG.config("ouro_2_6b")["rehearse"]["architecture"]
    model = models.build_model("ouro_tiny", dtype=jnp.float32)
    k_init, k_tok = jax.random.split(jax.random.key(5))
    params = flax.linen.meta.unbox(
        model.init(k_init, jnp.zeros((1, 8), jnp.int32))["params"])
    ids = jax.random.randint(k_tok, (2, 33), 0, arch["vocab_size"], jnp.int32)
    return (CATALOG.driver("lm_dense_train_step"), CATALOG.reference("ouro"), arch,
            params, ids[:, :-1], ids[:, 1:])


def test_the_walk_over_sequences_is_the_whole_batch():
    """The driver takes the reference one sequence at a time: its terms and
    its gradient are the reference's on the whole batch at once."""
    driver, reference, arch, params, tokens, labels = tiny()

    def whole(p):
        terms = reference.loss(p, tokens, labels, architecture=arch)
        return terms["loss"], terms

    (_, want), grads = jax.value_and_grad(whole, has_aux=True)(params)
    got, walked = driver.reference_terms(reference, arch, params, tokens, labels)
    assert set(got) == set(TERMS)
    errors = driver.term_errors(got, want)
    assert set(errors) == set(TERMS) and max(errors.values()) < 1e-6
    for a, b in zip(jax.tree.leaves(walked), jax.tree.leaves(grads), strict=True):
        assert float(jnp.linalg.norm(a - b)) <= 1e-5 * float(jnp.linalg.norm(b))


def test_term_errors_name_the_worst_pass():
    driver = CATALOG.driver("lm_dense_train_step")
    want = {"ce": 10.0, "exit_entropy": 0.5, "exit_step_mean": 1.9, "loss": 9.94,
            "ce_pass": [10.0, 10.0, 10.0, 10.0]}
    got = dict(want, ce=10.01, ce_pass=[10.0, 10.0, 10.2, 10.0], exit_entropy=0.501)
    errors = driver.term_errors(got, want)
    assert errors["ce"] == pytest.approx(1e-3) and errors["ce_pass"] == pytest.approx(2e-2)
    # a term under 1 is held to an absolute error: relative to at least 1
    assert errors["exit_entropy"] == pytest.approx(1e-3)
    assert errors["loss"] == errors["exit_step_mean"] == 0.0
