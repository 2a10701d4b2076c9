"""Unified telemetry layer (distribuuuu_tpu/telemetry/, ISSUE 5): span
nesting, registry aggregation, the per-rank sink + jsonlog mirror,
Perfetto export over merged rank files, run_report math + the
--compare regression gate, the kind-schema static check, and — the hard
contract — trajectory neutrality (telemetry on ≡ off bit-identically).
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import distribuuuu_tpu.config as config
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu import telemetry
from distribuuuu_tpu.telemetry import (
    export,
    registry as registry_lib,
    schema,
    spans,
)
from distribuuuu_tpu.utils import jsonlog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import run_report  # noqa: E402  (tools/, needs the path insert above)


@pytest.fixture(autouse=True)
def _close_sinks():
    yield
    spans.close_telemetry()
    jsonlog.close_metrics_log()
    registry_lib.get_registry().reset()


def _read(path):
    return [json.loads(ln) for ln in open(path).read().splitlines()]


# ---------------------------------------------------------------- spans
def test_noop_before_setup():
    spans.emit_event("stall", age_s=1.0, count=1)  # must not raise
    spans.emit_span("step", 0.0, 1.0)
    with spans.span("ckpt_save"):
        pass
    assert not spans.enabled()


def test_sink_opens_with_clock_anchor(tmp_path):
    path = spans.setup_telemetry(str(tmp_path), rank=3)
    assert os.path.basename(path) == "rank00003.jsonl"
    recs = _read(path)
    assert recs[0]["kind"] == "clock"
    assert recs[0]["rank"] == 3
    # anchor pair sampled back-to-back: unix and mono describe ~the same
    # instant (their difference equals the clocks' offset, checked via a
    # fresh pair)
    off_now = time.time() - time.perf_counter()
    off_anchor = recs[0]["unix"] - recs[0]["mono"]
    assert abs(off_now - off_anchor) < 5.0


def test_span_nesting_and_timestamps(tmp_path):
    path = spans.setup_telemetry(str(tmp_path), rank=0)
    with spans.span("ckpt_save", track="t"):
        time.sleep(0.01)
        with spans.span("ckpt_snapshot", foo=7):
            time.sleep(0.01)
    recs = [r for r in _read(path) if r["kind"] == "span"]
    inner = next(r for r in recs if r["name"] == "ckpt_snapshot")
    outer = next(r for r in recs if r["name"] == "ckpt_save")
    assert inner["parent"] == "ckpt_save" and inner["depth"] == 1
    assert inner["track"] == "t"  # inherited from the enclosing span
    assert "depth" not in outer
    assert inner["foo"] == 7
    # containment: inner ⊆ outer in time
    assert outer["t0"] <= inner["t0"]
    assert inner["t0"] + inner["dur"] <= outer["t0"] + outer["dur"] + 1e-6
    assert outer["dur"] >= 0.02 - 1e-3
    for r in recs:
        schema.validate_record(r)


def test_emit_span_precomputed_stamps(tmp_path):
    path = spans.setup_telemetry(str(tmp_path), rank=0)
    spans.emit_span("step", 10.0, 10.5, track="pipeline", phase="train",
                    epoch=1, batch=4, n=32)
    (rec,) = [r for r in _read(path) if r["kind"] == "span"]
    assert rec["t0"] == 10.0 and rec["dur"] == 0.5
    assert rec["track"] == "pipeline" and rec["batch"] == 4
    schema.validate_record(rec)


def test_jsonlog_mirrors_rank_local_kinds_on_non_primary(tmp_path):
    """The satellite-3 fix: before the telemetry layer, a non-primary
    process's stall/data_error records vanished (jsonlog's sink is
    primary-only). With a per-rank sink open they survive."""
    jsonlog.setup_metrics_log(str(tmp_path), primary=False)  # rank > 0
    path = spans.setup_telemetry(str(tmp_path / "telemetry"), rank=2)
    jsonlog.metrics_log("stall", age_s=12.5, last="epoch 1 batch 7", count=1)
    jsonlog.metrics_log("data_error", index=9, attempts=3, error="IOError: x")
    # primary sink never existed; the rank file has both records
    assert not os.path.exists(tmp_path / "metrics.jsonl")
    recs = _read(path)
    kinds = [r["kind"] for r in recs]
    assert "stall" in kinds and "data_error" in kinds
    stall = next(r for r in recs if r["kind"] == "stall")
    assert stall["rank"] == 2 and stall["age_s"] == 12.5
    for r in recs:
        schema.validate_record(r)


def test_timeline_not_mirrored(tmp_path):
    """timeline stays primary-only (the exporter reads metrics.jsonl);
    mirroring would double every batch record in rank 0's file."""
    jsonlog.setup_metrics_log(str(tmp_path), primary=True)
    path = spans.setup_telemetry(str(tmp_path / "telemetry"), rank=0)
    jsonlog.timeline_log("train", 1, 0, 16, get0=1.0, get1=1.1)
    assert any(
        r["kind"] == "timeline" for r in _read(tmp_path / "metrics.jsonl")
    )
    assert not any(r["kind"] == "timeline" for r in _read(path))


def test_emit_overhead_is_bounded(tmp_path):
    """The ISSUE 5 'overhead bounded and measured' clause: one span write
    costs ~30µs on this container (measured); the bound here is a loose
    CI-jitter-proof ceiling. At ~5 spans/batch that is ≪1% of any real
    step, and the writes happen outside the measured intervals anyway."""
    spans.setup_telemetry(str(tmp_path), rank=0)
    n = 2000
    t0 = time.perf_counter()
    for i in range(n):
        spans.emit_span("step", 1.0, 1.1, track="pipeline",
                        phase="train", epoch=1, batch=i, n=8)
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 500e-6, f"emit_span cost {per_call * 1e6:.0f}µs/call"


# -------------------------------------------------------------- registry
def test_registry_aggregation():
    reg = registry_lib.Registry()
    reg.counter("c").inc()
    reg.counter("c").inc(2.5)
    reg.gauge("g").set(1.0)
    reg.gauge("g").set(4.0)
    h = reg.histogram("h")
    for v in range(1, 101):
        h.observe(float(v))
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3.5
    assert snap["gauges"]["g"] == 4.0
    hs = snap["histograms"]["h"]
    assert hs["count"] == 100 and hs["min"] == 1.0 and hs["max"] == 100.0
    assert hs["p50"] == 50.0 and hs["p90"] == 90.0 and hs["p99"] == 99.0
    assert hs["mean"] == pytest.approx(50.5)


def test_registry_instruments_are_shared_by_name():
    reg = registry_lib.Registry()
    assert reg.counter("x") is reg.counter("x")
    assert reg.histogram("y") is reg.histogram("y")


def test_registry_snapshot_lands_in_sink(tmp_path):
    path = spans.setup_telemetry(str(tmp_path), rank=0)
    registry_lib.get_registry().counter("jit.compiles").inc(4)
    telemetry.emit_snapshot(epoch=2)
    (rec,) = [r for r in _read(path) if r["kind"] == "registry"]
    assert rec["counters"]["jit.compiles"] == 4.0
    assert rec["epoch"] == 2
    schema.validate_record(rec)


def test_serve_metrics_ride_the_shared_registry():
    """Satellite 1: ServeMetrics' meters ARE registry instruments (one
    schema for serve and train) while the serve_bench JSON fields stay
    exactly what they were."""
    from distribuuuu_tpu.serve.metrics import ServeMetrics

    m = ServeMetrics()
    m.record_batch(3, 4, 0.010, [0.001, 0.002, 0.003])
    m.record_rejection()
    snap = m.snapshot()
    assert snap["requests"] == 3 and snap["rejected"] == 1
    assert snap["batches"] == 1 and snap["batch_occupancy"] == 0.75
    assert snap["p50_ms"] == 2.0 and snap["p99_ms"] == 3.0
    assert snap["mean_batch_ms"] == 10.0
    # the instruments live in a Registry and snapshot through its schema
    rsnap = m.registry.snapshot()
    assert rsnap["counters"]["serve.requests"] == 3.0
    assert rsnap["histograms"]["serve.latency_s"]["count"] == 3


# ---------------------------------------------------------------- schema
def test_validate_record_rejects_undeclared_and_drifted():
    with pytest.raises(schema.SchemaError, match="undeclared"):
        schema.validate_record({"kind": "no_such_kind"})
    with pytest.raises(schema.SchemaError, match="missing required"):
        schema.validate_record({"kind": "stall", "age_s": 1.0})  # no count
    schema.validate_record({"kind": "stall", "age_s": 1.0, "count": 2})


def test_schema_static_check_is_clean_on_the_repo():
    """Tier-1 gate: every emit call site in distribuuuu_tpu/ declares its
    kind (satellite 2). Run as the CLI so the check itself is covered."""
    out = subprocess.run(
        [sys.executable, os.path.join("tools", "check_telemetry_schema.py")],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 violation(s)" in out.stdout


def test_schema_static_check_flags_violations(tmp_path):
    import check_telemetry_schema as checker

    bad = tmp_path / "pkg"
    bad.mkdir()
    (bad / "mod.py").write_text(
        "from distribuuuu_tpu.utils.jsonlog import metrics_log\n"
        "metrics_log('totally_new_kind', x=1)\n"        # undeclared
        "metrics_log('stall', age_s=1.0)\n"             # drifted: no count
        "k = 'dyn'\nmetrics_log(k, x=1)\n"              # dynamic outside sinks
    )
    violations, seen = checker.check_tree(str(bad))
    msgs = "\n".join(violations)
    assert "undeclared kind 'totally_new_kind'" in msgs
    assert "drifted" in msgs and "count" in msgs
    assert "non-literal kind" in msgs
    assert len(violations) == 3
    # a clean file passes
    good = tmp_path / "ok"
    good.mkdir()
    (good / "mod.py").write_text(
        "metrics_log('stall', age_s=1.0, count=2)\n"
    )
    violations, seen = checker.check_tree(str(good))
    assert violations == [] and seen == {"stall"}


# ------------------------------------------------- synthetic rank fixtures
def _write_rank(tmp_path, rank, step_ms, *, extra=None, anchor=1000.0):
    """A synthetic rank file: clock anchor + one 'step' span per entry of
    ``step_ms`` (spaced 1s apart on the mono clock) + optional extras."""
    tdir = tmp_path / "telemetry"
    tdir.mkdir(exist_ok=True)
    path = tdir / f"rank{rank:05d}.jsonl"
    recs = [{"kind": "clock", "rank": rank, "t": 0.0,
             "unix": 1_700_000_000.0, "mono": anchor}]
    for i, ms in enumerate(step_ms):
        t0 = anchor + i * 1.0
        recs.append({
            "kind": "span", "rank": rank, "t": 0.0, "v": 1, "name": "step",
            "t0": t0, "dur": ms / 1e3, "track": "pipeline",
            "phase": "train", "epoch": 1, "batch": i, "n": 8,
        })
        recs.append({
            "kind": "span", "rank": rank, "t": 0.0, "v": 1, "name": "wait",
            "t0": t0 - 0.05, "dur": 0.05, "track": "pipeline",
            "phase": "train", "epoch": 1, "batch": i,
        })
    for r in extra or []:
        recs.append({"rank": rank, "t": 0.0, **r})
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")
    return path


# ---------------------------------------------------------------- export
def test_perfetto_export_merges_ranks_onto_one_timebase(tmp_path):
    # two ranks with DIFFERENT mono origins but one unix timebase: the
    # exporter must land both on the same wall-clock axis
    _write_rank(tmp_path, 0, [100.0, 100.0], anchor=1000.0)
    _write_rank(tmp_path, 1, [100.0, 100.0], anchor=500_000.0,
                extra=[{"kind": "stall", "age_s": 9.0, "count": 1,
                        "t": 1_700_000_001.0},
                       {"kind": "compile", "event": "backend_compile",
                        "dur_s": 0.25, "mono": 500_000.5}])
    trace = export.merge_trace(str(tmp_path))
    evs = trace["traceEvents"]
    # trace-event schema: every event has name/ph/pid; X events add ts+dur
    for e in evs:
        assert {"name", "ph", "pid"} <= set(e)
        if e["ph"] == "X":
            assert isinstance(e["ts"], float) and e["dur"] >= 0.0
    pids = {e["pid"] for e in evs if e["ph"] == "X"}
    assert pids == {0, 1}  # one track group per rank
    # the same (batch, name) slice on both ranks maps to ~the same unix µs
    def ts_of(pid, batch):
        return next(e["ts"] for e in evs
                    if e["ph"] == "X" and e["pid"] == pid
                    and e["name"] == "step" and e["args"]["batch"] == batch)
    assert ts_of(0, 0) == pytest.approx(ts_of(1, 0), abs=1.0)
    assert ts_of(0, 0) == pytest.approx(1_700_000_000.0 * 1e6, abs=1e3)
    # instants + compile slices made it over with their own tracks
    assert any(e["ph"] == "i" and e["name"] == "stall" for e in evs)
    assert any(e["ph"] == "X" and e["name"] == "compile" for e in evs)
    # process/thread name metadata for Perfetto's track labels
    names = [e for e in evs if e["ph"] == "M" and e["name"] == "process_name"]
    assert {n["args"]["name"] for n in names} == {"rank 0", "rank 1"}


def test_export_includes_primary_timeline_records(tmp_path):
    _write_rank(tmp_path, 0, [100.0], anchor=1000.0)
    with open(tmp_path / "metrics.jsonl", "w") as f:
        f.write(json.dumps({
            "kind": "timeline", "t": 0.0, "v": 1, "phase": "train",
            "epoch": 1, "batch": 0, "n": 8, "dec0": 1000.0, "dec1": 1000.2,
            "asm1": 1000.25, "get0": 1000.3, "get1": 1000.31,
            "put0": 1000.31, "put1": 1000.33, "step0": 1000.33,
            "step1": 1000.43,
        }) + "\n")
    trace = export.merge_trace(str(tmp_path))
    evs = [e for e in trace["traceEvents"] if e.get("cat") == "timeline"]
    assert {e["name"] for e in evs} == {
        "wait", "h2d", "step", "decode", "assemble"
    }
    dec = next(e for e in evs if e["name"] == "decode")
    # placed through rank 0's anchor: mono 1000.0 ≡ unix 1.7e9
    assert dec["ts"] == pytest.approx(1_700_000_000.0 * 1e6, abs=1e3)
    assert dec["dur"] == pytest.approx(0.2 * 1e6, rel=1e-6)


def test_export_raises_without_any_telemetry(tmp_path):
    with pytest.raises(FileNotFoundError):
        export.merge_trace(str(tmp_path))


# ------------------------------------------------------------- run_report
def test_run_report_percentiles_and_straggler_skew(tmp_path):
    # rank 0 steady at 100ms; rank 1 a 2× straggler at 200ms
    _write_rank(tmp_path, 0, [100.0] * 10)
    _write_rank(tmp_path, 1, [200.0] * 10,
                extra=[{"kind": "stall", "age_s": 30.0, "count": 1},
                       {"kind": "data_error", "index": 5, "attempts": 3,
                        "error": "x"},
                       {"kind": "compile", "event": "backend_compile",
                        "dur_s": 1.5, "mono": 1.0},
                       {"kind": "span", "v": 1, "name": "ckpt_save",
                        "t0": 50.0, "dur": 2.0, "track": "ckpt"}])
    rep = run_report.build_report(str(tmp_path))
    assert rep["n_ranks"] == 2
    assert rep["per_rank_step"]["0"]["p50_ms"] == 100.0
    assert rep["per_rank_step"]["1"]["p50_ms"] == 200.0
    assert rep["step"]["count"] == 20
    assert rep["step"]["p99_ms"] == 200.0
    assert rep["straggler_skew"] == 2.0
    # wait spans: 50ms wait per ~1s window on each rank
    assert 0.02 < rep["data_wait_frac"] < 0.12
    assert rep["events"] == {"stall": 1, "data_error": 1, "nonfinite": 0}
    assert rep["recompiles"] == {"count": 1, "wall_s": 1.5}
    assert rep["checkpoint"]["saves"] == 1
    assert rep["checkpoint"]["save_max_s"] == 2.0


def test_run_report_compare_gate_both_ways(tmp_path):
    _write_rank(tmp_path, 0, [100.0] * 10)
    rep = run_report.build_report(str(tmp_path))
    base_ok = dict(rep)  # identical → PASS
    cmp = run_report.compare(rep, base_ok, tol_pct=10.0, tol_overrides={})
    assert cmp["ok"] and cmp["checked"] >= 2
    # a baseline whose steps were 2× faster → current is a regression
    fast = json.loads(json.dumps(rep))
    for q in ("p50_ms", "p90_ms", "p99_ms"):
        fast["step"][q] = rep["step"][q] / 2.0
    cmp = run_report.compare(rep, fast, tol_pct=10.0, tol_overrides={})
    assert not cmp["ok"]
    failed = {r["metric"] for r in cmp["rows"] if not r["ok"]}
    assert "step_ms_p50" in failed
    # tolerance knob: 150% headroom absorbs the 2× delta
    cmp = run_report.compare(rep, fast, tol_pct=150.0, tol_overrides={})
    assert cmp["ok"]
    # per-metric override beats the global knob
    cmp = run_report.compare(
        rep, fast, tol_pct=150.0, tol_overrides={"step_ms_p50": 10.0}
    )
    assert not cmp["ok"]


def test_regression_gate_against_bench_artifact(tmp_path, chip_bench_root):
    """Satellite 6: a driver bench record (``BENCH_r*.json`` shape) is a
    usable --compare reference point, and the gate fails/passes correctly
    around it — exercised through compare() so the gate itself can't rot."""
    bench = json.load(
        open(os.path.join(chip_bench_root[0], "BENCH_r05.json")))
    ref_ips = float(bench["parsed"]["value"])
    base = run_report.comparable_metrics(bench)
    assert base == {"img_per_sec": ref_ips}

    def run_cli(ips):
        _write_rank(tmp_path, 0, [100.0] * 4)
        rep = run_report.build_report(str(tmp_path))
        rep["img_per_sec"] = ips
        rep_path = tmp_path / "cur.json"
        rep_path.write_text(json.dumps(rep))
        # compare() consumed directly: the CLI wraps exactly this
        return run_report.compare(
            rep, bench, tol_pct=10.0, tol_overrides={}
        )

    assert run_cli(ref_ips * 0.95)["ok"]       # within 10% → PASS
    assert not run_cli(ref_ips * 0.5)["ok"]    # halved throughput → FAIL


def test_run_report_cli_trace_one_command(tmp_path):
    """Acceptance shape: `run_report.py --trace RUN_DIR` writes BOTH the
    merged trace (≥2 rank tracks here) and RUN_REPORT.json."""
    _write_rank(tmp_path, 0, [100.0] * 4)
    _write_rank(tmp_path, 1, [110.0] * 4)
    rc = run_report.main(["--trace", str(tmp_path)])
    assert rc == 0
    trace = json.load(open(tmp_path / "trace.json"))
    pids = {e["pid"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert pids == {0, 1}
    rep = json.load(open(tmp_path / "RUN_REPORT.json"))
    assert rep["n_ranks"] == 2
    assert rep["step"]["p50_ms"] in (100.0, 110.0)
    assert rep["straggler_skew"] == pytest.approx(1.1)


# --------------------------------------------------- trajectory neutrality
def _tiny_train(tmp_path, enabled: bool):
    import jax

    from distribuuuu_tpu import trainer

    config.reset_cfg()
    cfg.MODEL.ARCH = "resnet18"
    cfg.MODEL.NUM_CLASSES = 10
    cfg.MODEL.DUMMY_INPUT = True
    cfg.DEVICE.COMPUTE_DTYPE = "float32"
    cfg.TRAIN.BATCH_SIZE = 2
    cfg.TRAIN.IM_SIZE = 32
    cfg.TRAIN.PRINT_FREQ = 4
    cfg.TEST.BATCH_SIZE = 4
    cfg.TEST.IM_SIZE = 32
    cfg.OPTIM.MAX_EPOCH = 1
    cfg.OPTIM.BASE_LR = 0.01
    cfg.RNG_SEED = 0
    cfg.TELEMETRY.ENABLED = enabled
    cfg.OUT_DIR = str(tmp_path / ("on" if enabled else "off"))
    trainer.train_model()
    # the trained params live in the last checkpoint — compare those
    from distribuuuu_tpu.utils import checkpoint as ckpt

    restored = ckpt.load_checkpoint(ckpt.get_checkpoint(0))
    leaves = jax.tree.leaves(restored["params"])
    spans.close_telemetry()
    jsonlog.close_metrics_log()
    return [np.asarray(x) for x in leaves]


@pytest.mark.slow
def test_two_process_run_report_and_trace(tmp_path):
    """The ISSUE 5 acceptance command: a finished 2-process dummy run,
    then ONE command — ``run_report.py --trace out/`` — produces (a) a
    merged Perfetto-loadable trace with ≥ 2 rank tracks and (b)
    RUN_REPORT.json with cross-rank step percentiles, straggler skew,
    data-wait fraction, resilience-event and recompile counts."""
    from tests.test_multiprocess_e2e import _spawn_workers

    out_dir, _outs = _spawn_workers(tmp_path)
    files = export.rank_files(out_dir)
    assert set(files) == {0, 1}  # one sink per rank
    out = subprocess.run(
        [sys.executable, os.path.join("tools", "run_report.py"),
         "--trace", out_dir],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode == 0, out.stdout + out.stderr
    trace = json.load(open(os.path.join(out_dir, "trace.json")))
    pids = {e["pid"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert {0, 1} <= pids  # ≥ 2 rank tracks
    rep = json.load(open(os.path.join(out_dir, "RUN_REPORT.json")))
    assert rep["n_ranks"] == 2
    assert set(rep["per_rank_step"]) == {"0", "1"}
    assert rep["step"]["count"] > 0 and rep["step"]["p50_ms"] > 0
    assert rep["straggler_skew"] >= 1.0
    assert rep["data_wait_frac"] is not None
    assert rep["events"] == {"stall": 0, "data_error": 0, "nonfinite": 0}
    assert rep["recompiles"]["count"] > 0  # both ranks compiled the step
    assert rep["checkpoint"]["saves"] >= 2  # the collective save, per rank
    # every record in every rank file obeys the declared schema
    for path in files.values():
        for rec in _read(path):
            schema.validate_record(rec)


@pytest.mark.slow
def test_trajectory_neutral_end_to_end(tmp_path):
    """The ISSUE 5 hard contract at full train_model scope: telemetry on
    vs off produces bit-identical trained states (1e-7 is the acceptance
    bound; equality is what we actually get — nothing telemetry does
    touches RNG or the compiled step)."""
    on = _tiny_train(tmp_path, enabled=True)
    off = _tiny_train(tmp_path, enabled=False)
    assert os.path.exists(tmp_path / "on" / "telemetry" / "rank00000.jsonl")
    assert not os.path.exists(tmp_path / "off" / "telemetry")
    for a, b in zip(on, off):
        np.testing.assert_allclose(a, b, rtol=0.0, atol=1e-7)


@pytest.mark.slow  # 45s: two full toy train runs; tier-1 budget (ISSUE 18)
def test_trajectory_neutral_step_level(tmp_path):
    """Fast tier-1 half of the neutrality contract: the train_epoch hot
    path with spans enabled produces the identical state as with
    telemetry off (same steps, same metrics, same params)."""
    import jax

    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.parallel import mesh as mesh_lib, sharding
    from distribuuuu_tpu.utils.optim import construct_optimizer

    def run(enabled):
        config.reset_cfg()
        cfg.MODEL.ARCH = "resnet18"
        cfg.MODEL.NUM_CLASSES = 10
        cfg.DEVICE.COMPUTE_DTYPE = "float32"
        cfg.TELEMETRY.ENABLED = enabled
        if enabled:
            spans.setup_telemetry(str(tmp_path / "telemetry"), rank=0)
        mesh = mesh_lib.mesh_from_cfg(cfg)
        model = trainer.build_model_from_cfg()
        state = trainer.create_train_state(model, jax.random.key(0), mesh, 32)
        step = trainer.make_train_step(model, construct_optimizer(), topk=5)
        rng = np.random.default_rng(7)
        for it in range(3):
            hb = {
                "image": rng.standard_normal((16, 32, 32, 3)).astype(np.float32),
                "label": rng.integers(0, 10, size=(16,)).astype(np.int32),
                "mask": np.ones((16,), np.float32),
            }
            t0 = time.perf_counter()
            state, m = step(state, sharding.shard_batch(mesh, hb))
            if enabled:
                trainer._emit_batch_spans(
                    "train", 1, it,
                    {"get0": t0, "get1": t0, "put0": t0, "put1": t0,
                     "step0": t0, "step1": time.perf_counter()},
                )
        spans.close_telemetry()
        return jax.tree.leaves(jax.tree.map(np.asarray, state.params))

    on = run(True)
    off = run(False)
    # spans were really written by the instrumented pass
    recs = _read(tmp_path / "telemetry" / "rank00000.jsonl")
    assert sum(r.get("name") == "step" for r in recs) == 3
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a, b)
