"""The two readers of the ``attn_prologue`` scope (PR 51) and their entries
in ``BENCHMARK.json`` ``per_layer``, which PR 52 appended as ``ENTRIES`` has
them (they waited here while an accepted test held the list's last two
entries to SDAR's own; ``PERF.md`` section 7). Their form, their sums on
synthetic events of a program that runs the ``dtpu_head_prologue_*`` kernels,
of one where XLA still runs the chain (LFM2's heads of 64, and any parent of
PR 51 laid over with these files) and of one without the scope, and the bytes
the roofline counts by itself."""

import pytest

from benchmark.harness import trace
from benchmark.harness.discovery import Catalog
from benchmark.harness.observation import Observed
from benchmark.harness.trace import Reduction

CATALOG = Catalog()
MS, ROOFLINE = "models.attn_prologue_ms_per_step", "kernels.attn_prologue_roofline"
LFM2, TRINITY, SDAR = (f"{name}.train_seq8192" for name in (
    "lfm2_24b_a2b", "trinity_mini", "sdar_30b_a3b"))
ENTRIES = {
    MS: {"name": MS, "unit": "ms", "better": "lower", "source": "device_trace",
         "layer": "models", "moves": "train_items_per_s_per_chip",
         "workloads": [LFM2, TRINITY, SDAR]},
    # LFM2's heads of 64 stay on XLA: a share of the scope's time alone could
    # read past the peak there
    ROOFLINE: {"name": ROOFLINE, "unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "train_items_per_s_per_chip",
               "workloads": [TRINITY, SDAR]},
}


def reader(name):
    return CATALOG.layer_metric(ENTRIES[name])


def op(name, start, dur, op_name=""):
    _, opcode = trace.parse_instruction(name)
    return {"plane": "/device:TPU:0", "line": "XLA Ops", "name": name,
            "opcode": opcode, "op_name": op_name, "start_ns": float(start),
            "dur_ns": float(dur)}


def observed_for(cell_name, events, counters):
    cell = CATALOG.cell(cell_name)
    return Observed(
        cell=cell, section=lambda name: cell.config[name], traffic=cell.traffic,
        end_to_end={"train_items_per_s_per_chip": 1.0, "setup_s": 1.0},
        counters=counters, device={"count": 1}, peaks=CATALOG.peaks("TPU v5 lite"),
        catalog=CATALOG, trace=None if events is None else Reduction(events),
    )


def test_the_two_entries_are_of_the_manifests_form_and_name_what_is_there():
    accepted = {m["name"]: m for m in CATALOG.benchmark["per_layer"]}
    layers = {m["layer"] for m in accepted.values() if m["name"] not in ENTRIES}
    for name, entry in ENTRIES.items():
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves",
                              "workloads"}
        assert accepted[name] == entry  # appended as it waited here (PR 52)
        assert entry["layer"] in layers
        assert callable(reader(name).read)  # declaration equals the entry
        for cell in entry["workloads"]:
            assert entry["moves"] in {m["name"] for m in CATALOG.cell(cell).end_to_end}
            assert name in {m["name"] for m in CATALOG.cell(cell).per_layer}
    # LFM2's cell reads the scope's time and is held to no share of a peak
    assert ROOFLINE not in {m["name"] for m in CATALOG.cell(LFM2).per_layer}


def test_the_roofline_counts_its_own_bytes_from_the_architecture():
    count = reader(ROOFLINE).bytes_per_token
    sdar, trinity, lfm2 = (CATALOG.config(n)["architecture"] for n in (
        "sdar_30b_a3b", "trinity_mini", "lfm2_24b_a2b"))
    # 5 elements a row: 2 forward, 3 backward; q's 32 and k's 4 heads of 128
    assert count(sdar, 2) == 6 * 2 * 5 * 36 * 128 * 2 == 552_960
    assert count(trinity, 2) == 5 * 5 * 36 * 128 * 2 == 230_400
    assert count(trinity, 4) == 2 * 230_400
    assert reader(ROOFLINE).attention_layers(lfm2) == 1
    assert count(lfm2, 2) == 5 * 40 * 64 * 2
    # a step of SDAR's cell at the roofline: 4.5 GB, 5.5 ms (ISSUE 51)
    assert count(sdar, 2) * 8192 / 819e9 == pytest.approx(5.53e-3, rel=1e-3)
    assert count(trinity, 2) * 16384 / 819e9 == pytest.approx(4.61e-3, rel=1e-3)


PRE = "jit(train_step)/jvp(fwd)/SDARMoE/"
BACK = "jit(train_step)/bwd/transpose(jvp(fwd))/SDARMoE/"
AGAIN = BACK + "jvp(fwd)/SDARMoE/checkpoint/rematted_computation/"
Q_PROJ = "Block_0/attn/attn/attn_diffusion/q_proj/dot_general"
PROLOGUE = "Block_0/attn/attn/attn_diffusion/q_norm/attn_prologue/"
FLASH = "Block_0/attn/attn/attn_diffusion/dtpu_flash_fwd/pallas_call"


def events_of(prologue, steps=2):
    events, t = [], 0
    for _step in range(steps):
        for name, dur, op_name in (
            ("fusion.1", 3e6, PRE + Q_PROJ), *((n, d, PRE + PROLOGUE + o) for n, d, o in prologue),
            ("dtpu_flash_fwd.1", 9e6, PRE + FLASH), ("fusion.2", 3e6, AGAIN + Q_PROJ),
        ):
            events.append(op(name, t, dur, op_name))
            t += dur
    return events


def test_the_readers_on_a_program_that_runs_the_kernels():
    """Two steps; per step 4 ms forward and 6 backward in the two calls, 0.5
    of tables under the scope in XLA forward and again: 11 ms, and the
    projection and the flash kernel beside them in neither sum."""
    events = events_of((
        ("dtpu_head_prologue_fwd.1", 4e6, "dtpu_head_prologue_fwd/pallas_call"),
        ("fusion.9", 0.5e6, "cos"),
        ("dtpu_head_prologue_bwd.1", 6e6, "dtpu_head_prologue_bwd/pallas_call"),
    ))
    events += [op("fusion.7", 1e9, 1e6, AGAIN + PROLOGUE + "cos")]
    counters = {"trace_steps": 2, "tokens_per_step": 8192}
    observed = observed_for(SDAR, events, counters)
    assert reader(MS).read(observed) == pytest.approx(11.0)
    peaks = CATALOG.peaks("TPU v5 lite")
    assert reader(ROOFLINE).read(observed) == pytest.approx(
        100 * 552_960 * 8192 / peaks["hbm_bytes_per_s"] / 0.011)
    assert 50 < reader(ROOFLINE).read(observed) < 100
    # Trinity-Mini's cell counts one row a token over its five layers
    trinity = observed_for(TRINITY, events, {"trace_steps": 2, "tokens_per_step": 16384})
    assert reader(ROOFLINE).read(trinity) == pytest.approx(
        100 * 230_400 * 16384 / peaks["hbm_bytes_per_s"] / 0.011)
    # the accepted readers see the same program
    by_name = {m["name"]: m for m in CATALOG.benchmark["per_layer"]}
    assert CATALOG.layer_metric(by_name["models.attn_ms_per_step"]).read(
        observed) == pytest.approx(3 + 10.5 + 9 + 3 + 0.5)
    assert CATALOG.layer_metric(by_name["models.recompute_ms_per_step"]).read(
        observed) == pytest.approx(3.5)


def test_where_xla_runs_the_chain_the_time_is_read_and_the_share_is_not():
    """The same scope over XLA's fusions (LFM2's cell; the CPU): the time is
    the scope's, and no share of the peak is made from it."""
    events = events_of((("fusion.3", 7e6, "mul"), ("fusion.4", 5e6, "reduce_sum")))
    counters = {"trace_steps": 2, "tokens_per_step": 16384}
    for cell in (LFM2, SDAR):
        observed = observed_for(cell, events, counters)
        assert reader(MS).read(observed) == pytest.approx(12.0)
        assert reader(ROOFLINE).read(observed) is None
    # a kernel of that name OUTSIDE the scope is not the scope's
    stray = events + [op("dtpu_head_prologue_fwd.1", 1e9, 1e6, PRE + "elsewhere/pallas_call")]
    assert reader(ROOFLINE).read(observed_for(SDAR, stray, counters)) is None


def test_a_program_without_the_scope_and_a_run_without_a_trace_read_nothing():
    """A parent of PR 51 under these files: no span, no value, no error."""
    events = events_of(())
    counters = {"trace_steps": 2, "tokens_per_step": 8192}
    for observed in (observed_for(SDAR, events, counters),
                     observed_for(SDAR, None, counters),
                     observed_for(SDAR, events, {})):
        assert reader(MS).read(observed) is None
        assert reader(ROOFLINE).read(observed) is None
