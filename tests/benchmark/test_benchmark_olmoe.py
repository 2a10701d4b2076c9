"""The OLMoE configuration, its cell, its costs and its six readers: what the
files state against what the program builds, the readers on synthetic events
(and on a program without the scopes), and the cell's driver at its rehearsal
size through the real command."""

import json

import jax
import jax.numpy as jnp
import pytest

from benchmark.harness import trace
from benchmark.harness.discovery import Catalog
from benchmark.harness.observation import Observed
from benchmark.harness.trace import Reduction
from benchmark_testlib import REPO, finish, start_run

CELL = "olmoe_1b_7b.train_seq4096"
NEW = ("models.attn_ms_per_step", "models.moe_ms_per_step",
       "models.lm_head_ms_per_step", "kernels.moe_experts_roofline",
       "kernels.flash_attn_roofline", "models.moe_load_max_over_mean")
CATALOG = Catalog()
PUBLISHED = {  # config.json of allenai/OLMoE-1B-7B-0125-Instruct
    "hidden_size": 2048, "intermediate_size": 1024, "num_attention_heads": 16,
    "num_key_value_heads": 16, "num_experts": 64, "num_experts_per_tok": 8,
    "vocab_size": 50304, "max_position_embeddings": 4096, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "norm_topk_prob": False, "hidden_act": "silu",
    "tie_word_embeddings": False, "attention_bias": False, "clip_qkv": None,
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


def read_new(observed):
    by_name = {m["name"]: m for m in CATALOG.benchmark["per_layer"]}
    return {n: CATALOG.layer_metric(by_name[n]).read(observed) for n in NEW}


def test_the_configuration_is_the_published_one_cut_in_depth_only():
    body = CATALOG.config("olmoe_1b_7b")
    entry = [c for c in CATALOG.benchmark["configs"] if c["name"] == "olmoe_1b_7b"][0]
    assert entry["reduced"] == body["reduced"] == ["layers"]
    assert body["architecture"]["layers"] == body["layers"] == 1
    assert body["num_hidden_layers"] == 16  # the source's own, verbatim
    for key, value in PUBLISHED.items():
        assert body["architecture"][key] == value, key
        assert body[key] == value, key  # config.json's keys at the top level
    job = body["train_job"]
    assert job["seq_len"] == body["architecture"]["max_position_embeddings"]
    assert set(job["reference_tolerance"]) == {
        "ce", "load_balance", "router_z", "loss", "gradient", "update",
        "second_moment"}
    # the loss and the plain AdamW step the reference side computes are the
    # recipe under `assumed`, stated as numbers
    assert job["loss_weights"] == {"load_balance": 0.01, "router_z": 0.001}
    assert job["adamw"] == {"b1": 0.9, "b2": 0.95, "eps": 1e-08, "weight_decay": 0.1}
    assert body["assumed"] and body["deployment"]


def test_the_cell_is_one_chip_and_reports_what_the_issue_lists():
    cell = CATALOG.cell(CELL)
    assert cell.chips == 1 and cell.traffic_name == "train_device_tokens"
    assert {k: cell.traffic[k] for k in ("driver", "warmup_steps", "chunk_steps",
                                         "trace_steps")} == {
        "driver": "lm_train_step", "warmup_steps": 3, "chunk_steps": 5,
        "trace_steps": 10}
    assert cell.config["train_job"]["sequences_per_chip"] in (2, 4, 8)
    assert [m["name"] for m in cell.end_to_end] == [
        "train_items_per_s_per_chip", "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names
    assert {"models.mfu", "models.fwd_bwd_ms_per_step",
            "kernels.opt_update_ms_per_step",
            "kernels.opt_update_roofline", "kernels.opt_kernel_ms_per_step",
            "device.idle_frac",
            "device.hbm_peak_frac", "entry.lower_s", "entry.init_state_s",
            "entry.compiles_in_window",
            # since PR 29 the experts are the repo's own grouped matmuls,
            # which keep their scope forward and backward (XLA:TPU dropped
            # it from its `ragged-dot` kernels, a quarter of `fwd_bwd`): the
            # fwd/bwd split covers the step (PR 40)
            "models.fwd_ms_per_step", "models.bwd_ms_per_step"} <= names
    # nothing runs under `opt_tile` here (13 leaves, each updated where it
    # rests), so that reader finds nothing and the cell is not on its list
    assert "kernels.opt_tile_ms_per_step" not in names
    # the six list this cell; a later decoder's cell may be appended (`in`,
    # not `==`: Ouro's and GLM's cells read three of them since PR 40)
    for m in CATALOG.benchmark["per_layer"]:
        if m["name"] in NEW:
            assert CELL in m["workloads"]


def test_the_configuration_states_the_sizes_the_program_builds():
    """The parameter count and every width of the file equal the program's
    module at the cell's own settings (config file + overrides)."""
    import distribuuuu_tpu.config as program_config
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.config import cfg

    body = CATALOG.config("olmoe_1b_7b")
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
        assert (cfg.MODEL.MOE.AUX_WEIGHT, cfg.MODEL.MOE.Z_WEIGHT) == (0.01, 0.001)
    finally:
        program_config.reset_cfg()
    assert {
        "layers": model.depth, "hidden_size": model.dim,
        "intermediate_size": model.expert_hidden,
        "num_attention_heads": model.num_heads, "num_experts": model.num_experts,
        "num_experts_per_tok": model.top_k, "vocab_size": model.vocab_size,
        "max_position_embeddings": model.seq_len, "rms_norm_eps": model.rms_norm_eps,
        "rope_theta": model.rope_theta,
    } == {key: arch[key] for key in (
        "layers", "hidden_size", "intermediate_size", "num_attention_heads",
        "num_experts", "num_experts_per_tok", "vocab_size",
        "max_position_embeddings", "rms_norm_eps", "rope_theta")}
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )
    count = sum(x.size for x in jax.tree.leaves(shapes["params"]))
    assert count == arch["parameters"] == 625_616_896


def test_costs_count_what_the_issue_counts():
    costs = CATALOG.costs("olmoe")
    arch = CATALOG.config("olmoe_1b_7b")["architecture"]
    assert costs.attention_macs_per_token(arch) == 8_388_608
    assert costs.expert_macs_per_token(arch) == 50_331_648
    assert costs.forward_macs_per_item(arch) == 178_651_136 == (
        16_777_216 + 8_388_608 + 131_072 + 50_331_648 + 103_022_592)
    full = dict(arch, layers=16)  # every per-layer term scales with depth
    assert costs.forward_macs_per_item(full) == 16 * 75_628_544 + 103_022_592
    # the tilt the cell's `why` names: head 58 % and experts 28 % at depth 1
    assert 103_022_592 / costs.forward_macs_per_item(arch) == pytest.approx(0.577, abs=1e-3)
    assert costs.expert_macs_per_token(arch) / costs.forward_macs_per_item(arch) == pytest.approx(0.282, abs=1e-3)
    assert costs.expert_macs_per_token(full) / costs.forward_macs_per_item(full) == pytest.approx(0.613, abs=1e-3)


ATTN = "jit(train_step)/jvp(fwd)/OLMoE/Block_0/attn/attn/q_proj/dot_general"
ATTN_BWD = "jit(train_step)/bwd/transpose(jvp(fwd))/OLMoE/Block_0/attn/attn/q_proj/dot_general"
FLASH = "jit(train_step)/jvp(fwd)/OLMoE/Block_0/attn/attn/dtpu_flash_fwd/pallas_call"
FLASH_BWD = "jit(train_step)/bwd/transpose(jvp(fwd))/OLMoE/Block_0/attn/attn/dtpu_flash_dq/pallas_call"
ROUTE = "jit(train_step)/jvp(fwd)/OLMoE/Block_0/moe/moe/moe_route/sort"
ACTIVATION = "jit(train_step)/jvp(fwd)/OLMoE/Block_0/moe/moe/moe_experts/mul"
GROUPED = "ragged-dot-none"  # XLA:TPU names the kernel and drops the scope
HEAD = "jit(train_step)/jvp(fwd)/lm_head/head_loss_fp32/bcd,dv->bcv/dot_general"
HEAD_BWD = "jit(train_step)/bwd/transpose(jvp(fwd))/lm_head/checkpoint/head_loss_fp32/dot_general"
UPDATE = "jit(train_step)/optimizer_update/opt_kernel/dtpu_opt_update_adamw/pallas_call"


def test_the_six_readers_on_synthetic_events():
    """Two steps; per step: attention 3 + 6 of projections around 4 + 8 of
    flash kernels, routing 2, the experts' activation 1 and grouped matmuls
    9 + 20 (scope-less, found by their kernel name), head 7 + 14, update 5."""
    events, t = [], 0
    for _step in range(2):
        for name, dur, op_name in (
            ("fusion.1", 3e6, ATTN), ("dtpu_flash_fwd.1", 4e6, FLASH),
            ("fusion.2", 2e6, ROUTE), ("fusion.6", 1e6, ACTIVATION),
            ("ragged-dot-none.1", 9e6, GROUPED),
            ("fusion.3", 7e6, HEAD), ("fusion.4", 14e6, HEAD_BWD),
            ("ragged-dot-none.2", 20e6, GROUPED), ("dtpu_flash_dq.1", 8e6, FLASH_BWD),
            ("fusion.5", 6e6, ATTN_BWD), ("dtpu_opt_update_adamw.1", 5e6, UPDATE),
        ):
            events.append(op(name, t, dur, op_name))
            t += dur
    observed = observed_for(events, {
        "trace_steps": 2, "tokens_per_step": 16384, "moe_load_max_over_mean": 1.25,
    })
    values = read_new(observed)
    peak = CATALOG.peaks("TPU v5 lite")["bf16_flops_per_s"]
    assert values == {
        "models.attn_ms_per_step": pytest.approx(21.0),
        "models.moe_ms_per_step": pytest.approx(32.0),
        "models.lm_head_ms_per_step": pytest.approx(21.0),
        "kernels.moe_experts_roofline": pytest.approx(
            100 * 6 * 50_331_648 * 16384 / peak / 0.030),
        "kernels.flash_attn_roofline": pytest.approx(
            100 * 6 * 8_388_608 * 16384 / peak / 0.012),
        "models.moe_load_max_over_mean": 1.25,
    }
    # the parts stay inside the whole; at these made-up times the experts
    # read 83.7 % of the MXU's peak
    by_name = {m["name"]: m for m in CATALOG.benchmark["per_layer"]}
    whole = CATALOG.layer_metric(by_name["models.fwd_bwd_ms_per_step"]).read(observed)
    assert whole == pytest.approx(74.0)
    assert sum(values[k] for k in NEW[:3]) <= whole
    assert values["kernels.moe_experts_roofline"] == pytest.approx(83.72, abs=0.01)


def test_the_readers_find_nothing_in_a_program_without_the_scopes():
    """The parent's kind of program (a conv net's step): every reader returns
    None and raises nothing, with and without a trace."""
    events = [
        op("fusion.1", 0, 10e6, "jit(train_step)/jvp(fwd)/ResNet/ConvBN_0/conv_general_dilated"),
        op("dtpu_opt_update_sgd.1", 10e6, 5e6,
           "jit(train_step)/optimizer_update/opt_kernel/dtpu_opt_update_sgd/pallas_call"),
    ]
    assert read_new(observed_for(events, {"trace_steps": 1})) == dict.fromkeys(NEW)
    assert read_new(observed_for(None, {})) == dict.fromkeys(NEW)


def test_a_program_without_the_arch_is_refused_before_the_device(tmp_path):
    """What the parent of this configuration's PR does on the cell: a
    DiscoveryError and a non-zero exit, at once."""
    import shutil

    from benchmark_testlib import make_root

    root = make_root(tmp_path)
    shutil.rmtree(f"{root}/benchmark/configs", ignore_errors=False)
    shutil.copytree(f"{REPO}/benchmark/configs", f"{root}/benchmark/configs")
    path = f"{root}/benchmark/configs/olmoe_1b_7b.json"
    with open(path) as f:
        body = json.load(f)
    body["rehearse"]["program"]["arch"] = "olmoe_of_a_later_pr"
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
        REPO, "--workload", CELL, "--seed", str(2**31 + 12345), "--seconds", "2",
        "--trace", "1", "--rehearse", "--set", "traffic.reference_teeth=true"))


def test_rehearsal_runs_the_driver_end_to_end(rehearsal):
    code, out, err = rehearsal
    assert code == 0, err[-3000:]
    line = json.loads(out.strip().splitlines()[-1])
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 4
    # the timed program's own first step, on the whole batch: its loss terms
    # and their sum, its gradient, its AdamW update
    for term in ("ce", "load_balance", "router_z", "loss"):
        assert f"reference: {term} step" in out
    for kind in ("gradient", "update", "second_moment"):
        assert f"reference: {kind} of the first step against" in out
    assert out.count("agrees") == 8 and "DISAGREES" not in out
    assert "experts chosen equal in 1.00000" in out and "within 0.0000 of a tie" in out
    assert "moe_dropped max 0" in out


def test_the_tolerances_have_teeth(rehearsal):
    """The reference computed in bfloat16 throughout (the nearest precision
    below the float32 the configuration states for router, norms, softmaxes
    and loss) fails every tolerance of the rehearsal, the computed terms 30x
    and more outside.
    The same reading at the published widths is a chip run's (PERF.md)."""
    _code, out, _err = rehearsal
    teeth = [ln for ln in out.splitlines() if "teeth:" in ln]
    assert len(teeth) == 5 and all("fails, as it must" in ln for ln in teeth)
    for ln in teeth[:4]:  # the loss terms and their sum; the fifth line is the experts'
        relative = float(ln.split("(relative ")[1].split(",")[0])
        # the balancing term is a count of discrete choices over the whole
        # batch, nearly as steady in bfloat16: it fails, by less
        assert relative > (1 if "load_balance" in ln else 30) * 1e-5, ln
    assert "experts of the reference in bfloat16" in teeth[4]


def tiny():
    """(driver, reference, architecture, loss weights, params, tokens,
    labels) at the rehearsal size, three sequences."""
    from distribuuuu_tpu import models

    body = CATALOG.config("olmoe_1b_7b")
    arch = body["rehearse"]["architecture"]
    model = models.build_model("olmoe_tiny", dtype=jnp.float32)
    k_init, k_tok = jax.random.split(jax.random.key(5))
    import flax

    params = flax.linen.meta.unbox(
        model.init(k_init, jnp.zeros((1, 8), jnp.int32))["params"])
    ids = jax.random.randint(k_tok, (3, 65), 0, arch["vocab_size"], jnp.int32)
    return (CATALOG.driver("lm_train_step"), CATALOG.reference("olmoe"), arch,
            body["train_job"]["loss_weights"], params, ids[:, :-1], ids[:, 1:])


def test_the_walk_over_sequences_is_the_whole_batch():
    """The driver walks the batch one sequence at a time, with the balancing
    term's shares taken over the whole batch: its terms and its gradient are
    the reference's on the whole batch at once."""
    driver, reference, arch, weights, params, tokens, labels = tiny()

    def whole(p):
        t = reference.loss(p, tokens, labels, architecture=arch)
        return t["ce"] + sum(w * t[k] for k, w in weights.items()), t

    (total, want), grads = jax.value_and_grad(whole, has_aux=True)(params)
    got = driver.reference_terms(
        reference, arch, weights, params, tokens, labels, jnp.float32)
    for term in ("ce", "load_balance", "router_z"):
        assert float(got[term]) == pytest.approx(float(want[term]), rel=1e-6)
    assert float(got["loss"]) == pytest.approx(float(total), rel=1e-6)
    assert (got["experts"] == want["experts"]).all()
    walked = driver.reference_grads(
        reference, arch, weights, params, tokens, labels, got["share"])
    for a, b in zip(jax.tree.leaves(walked), jax.tree.leaves(grads)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-5 * float(jnp.linalg.norm(b))


@pytest.mark.parametrize("fault,kinds", [
    (None, set()),
    ("weight_decay", {"update"}),          # a step that forgot to decay
    ("half_the_batch", {"gradient"}),      # a gradient of part of the batch
    ("second_moment", {"second_moment", "update"}),
])
def test_the_first_step_check_tells_a_wrong_step(fault, kinds):
    """``first_step_errors`` on an optax AdamW step from a fresh state: all
    three errors at rounding for the right step, and each fault shows in the
    error that is there for it."""
    import optax
    from flax.struct import dataclass as struct

    driver = CATALOG.driver("lm_train_step")
    adamw = CATALOG.config("olmoe_1b_7b")["train_job"]["adamw"]
    lr = 4e-4
    keys = jax.random.split(jax.random.key(2), 4)
    params = {"a": jax.random.normal(keys[0], (64, 32)) * 0.02, "scale": jnp.ones((32,))}
    grads = {"a": jax.random.normal(keys[1], (64, 32)) * 1e-4,
             "scale": jax.random.normal(keys[2], (32,)) * 1e-3}
    applied = grads
    if fault == "half_the_batch":
        applied = jax.tree.map(
            lambda g, k: g + 1e-4 * jax.random.normal(k, g.shape), grads,
            {"a": keys[3], "scale": keys[0]})
    tx = optax.adamw(lr, b1=adamw["b1"], b2=adamw["b2"], eps=adamw["eps"],
                     weight_decay=0.0 if fault == "weight_decay" else adamw["weight_decay"])
    updates, opt_state = tx.update(applied, tx.init(params), params)
    if fault == "second_moment":
        adam = opt_state[0]
        opt_state = (adam._replace(nu=jax.tree.map(lambda v: 2 * v, adam.nu)),
                     *opt_state[1:])
        updates = jax.tree.map(lambda u: u / 2**0.5, updates)

    @struct
    class State:
        params: dict
        opt_state: tuple

    errors = driver.first_step_errors(
        adamw, lr, params, grads, State(optax.apply_updates(params, updates), opt_state))
    assert set(errors) == {"['a']", "['scale']"}
    wrong = {kind for leaf in errors.values() for kind, e in leaf.items() if e > 1e-3}
    assert wrong == kinds, errors
