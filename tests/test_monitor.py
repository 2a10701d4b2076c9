"""Live observability plane (telemetry/live.py, tools/monitor.py,
tools/soak.py — ISSUE 7): tailer edge cases (torn lines, truncation,
rotation, late rank sinks, anchor re-reads), live-aggregate parity with
run_report on the same fixture, alert-rule thresholds / hysteresis /
dedup, Prometheus exposition (golden), the /metrics HTTP endpoint, the
serve stats probe, BENCH_INDEX trajectory + gate integration, soak --dry
validation, and — the hard contract — an attached monitor changes no
training bits.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import distribuuuu_tpu.config as config
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu.telemetry import live, schema, spans
from distribuuuu_tpu.utils import jsonlog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import bench_history  # noqa: E402  (tools/, needs the path insert above)
import run_report  # noqa: E402


@pytest.fixture(autouse=True)
def _close_sinks():
    yield
    spans.close_telemetry()
    jsonlog.close_metrics_log()


def _jl(path, recs, mode="a"):
    with open(path, mode) as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")


def _span(rank, name, t0, dur, **kw):
    return {"kind": "span", "rank": rank, "t": 0.0, "v": 1, "name": name,
            "t0": t0, "dur": dur, "track": "pipeline", "phase": "train",
            "epoch": 1, **kw}


def _rank_path(tmp_path, rank):
    tdir = tmp_path / "telemetry"
    tdir.mkdir(exist_ok=True)
    return str(tdir / f"rank{rank:05d}.jsonl")


def _write_rank(tmp_path, rank, step_ms, *, extra=None, anchor=1000.0):
    """run_report-compatible fixture: clock anchor + one step span per
    entry (1s apart) + a 50ms wait span per step."""
    path = _rank_path(tmp_path, rank)
    recs = [{"kind": "clock", "rank": rank, "t": 0.0,
             "unix": 1_700_000_000.0, "mono": anchor}]
    for i, ms in enumerate(step_ms):
        t0 = anchor + i * 1.0
        recs.append(_span(rank, "step", t0, ms / 1e3, batch=i, n=8))
        recs.append(_span(rank, "wait", t0 - 0.05, 0.05, batch=i))
    for r in extra or []:
        recs.append({"rank": rank, "t": 0.0, **r})
    _jl(path, recs, mode="w")
    return path


# ------------------------------------------------------- tailer edge cases
def test_tailer_incremental_never_double_counts(tmp_path):
    path = _rank_path(tmp_path, 0)
    t = live.FileTailer(path, rank=0)
    assert t.poll() == []  # absent file: no crash, nothing read
    _jl(path, [{"kind": "stall", "age_s": 1.0, "count": i} for i in range(3)])
    assert len(t.poll()) == 3
    assert t.poll() == []  # nothing new
    _jl(path, [{"kind": "stall", "age_s": 1.0, "count": 3}])
    got = t.poll()
    assert [r["count"] for r in got] == [3]
    assert t.lines == 4


def test_tailer_holds_partial_trailing_line(tmp_path):
    path = _rank_path(tmp_path, 0)
    t = live.FileTailer(path)
    with open(path, "w") as f:
        f.write('{"kind": "stall", "age_s": 1.0, "co')
    assert t.poll() == []  # torn tail buffered, not parsed, not dropped
    with open(path, "a") as f:
        f.write('unt": 7}\n{"kind": "stall", "age')
    got = t.poll()
    assert len(got) == 1 and got[0]["count"] == 7
    with open(path, "a") as f:
        f.write('_s": 2.0, "count": 8}\n')
    got = t.poll()
    assert len(got) == 1 and got[0]["count"] == 8
    assert t.bad_lines == 0


def test_tailer_survives_truncation(tmp_path):
    path = _rank_path(tmp_path, 0)
    t = live.FileTailer(path)
    _jl(path, [{"kind": "stall", "age_s": 1.0, "count": i} for i in range(5)])
    assert len(t.poll()) == 5
    with open(path, "w") as f:  # truncate-in-place (same inode)
        f.write('{"kind": "stall", "age_s": 9.0, "count": 99}\n')
    got = t.poll()
    assert [r["count"] for r in got] == [99]
    assert t.resets == 1


def test_tailer_survives_rotation(tmp_path):
    path = _rank_path(tmp_path, 0)
    t = live.FileTailer(path)
    _jl(path, [{"kind": "stall", "age_s": 1.0, "count": 1}])
    assert len(t.poll()) == 1
    # rotation: a NEW file (new inode) replaces the path, same length
    side = str(tmp_path / "new.jsonl")
    _jl(side, [{"kind": "stall", "age_s": 2.0, "count": 2}], mode="w")
    os.replace(side, path)
    got = t.poll()
    assert [r["count"] for r in got] == [2]
    assert t.resets == 1


def test_tailer_skips_bad_json_lines(tmp_path):
    path = _rank_path(tmp_path, 0)
    t = live.FileTailer(path)
    with open(path, "w") as f:
        f.write("not json at all\n")
        f.write('{"kind": "stall", "age_s": 1.0, "count": 1}\n')
    got = t.poll()
    assert len(got) == 1 and t.bad_lines == 1


def test_tailer_clock_anchor_reread(tmp_path):
    path = _rank_path(tmp_path, 0)
    t = live.FileTailer(path)
    _jl(path, [{"kind": "clock", "unix": 1000.0, "mono": 10.0}])
    t.poll()
    assert t.to_unix(11.0) == pytest.approx(1001.0)
    # restarted run appends a fresh anchor: later monos map through it
    _jl(path, [{"kind": "clock", "unix": 5000.0, "mono": 0.0}])
    t.poll()
    assert t.to_unix(1.0) == pytest.approx(5001.0)


def test_run_tailer_picks_up_late_rank_sink(tmp_path):
    rt = live.RunTailer(str(tmp_path))
    assert rt.poll() == ([], [])  # no telemetry dir yet: no crash
    _write_rank(tmp_path, 0, [100.0])
    recs, _ = rt.poll()
    assert {r["rank"] for r in recs if r["kind"] == "span"} == {0}
    # an elastic-resume rank appears LATE: read from byte 0, no loss
    _write_rank(tmp_path, 3, [100.0, 100.0])
    recs, _ = rt.poll()
    assert {r["rank"] for r in recs if r["kind"] == "span"} == {3}
    assert sum(1 for r in recs if r.get("name") == "step") == 2
    assert sorted(rt.tailers) == [0, 3]


# ------------------------------------------- aggregate parity w/ run_report
def test_aggregator_matches_run_report_on_same_fixture(tmp_path):
    _write_rank(tmp_path, 0, [100.0] * 10)
    _write_rank(tmp_path, 1, [200.0] * 10,
                extra=[{"kind": "stall", "age_s": 30.0, "count": 1},
                       {"kind": "compile", "event": "backend_compile",
                        "dur_s": 1.5, "mono": 1.0},
                       {"kind": "span", "v": 1, "name": "ckpt_save",
                        "t0": 50.0, "dur": 2.0, "track": "ckpt"}])
    rep = run_report.build_report(str(tmp_path))

    agg = live.LiveAggregator()
    rt = live.RunTailer(str(tmp_path))
    agg.consume(*rt.poll())
    snap = agg.snapshot(window_s=10.0)

    assert snap["steps"] == rep["step"]["count"] == 20
    for q in ("p50_ms", "p90_ms", "p99_ms", "mean_ms", "max_ms"):
        assert snap["step"][q] == rep["step"][q]
    assert snap["straggler_skew"] == rep["straggler_skew"] == 2.0
    assert snap["data_wait_frac"] == rep["data_wait_frac"]
    assert snap["compiles"]["count"] == rep["recompiles"]["count"] == 1
    assert snap["compiles"]["wall_s"] == rep["recompiles"]["wall_s"]
    assert snap["ckpt"]["saves"] == rep["checkpoint"]["saves"] == 1
    assert snap["ckpt"]["save_max_s"] == rep["checkpoint"]["save_max_s"]
    assert snap["events"]["stall"] == rep["events"]["stall"] == 1


def test_aggregator_windows_reset_but_totals_roll(tmp_path):
    _write_rank(tmp_path, 0, [100.0] * 4)
    agg = live.LiveAggregator()
    rt = live.RunTailer(str(tmp_path))
    agg.consume(*rt.poll())
    s1 = agg.snapshot(window_s=1.0)
    assert s1["steps"] == 4 and s1["totals"]["steps"] == 4
    s2 = agg.snapshot(window_s=1.0)  # nothing new arrived
    assert s2["steps"] == 0 and s2["totals"]["steps"] == 4
    assert s2["img_per_sec"] is None


def test_aggregator_ignores_mirrored_events_from_primary(tmp_path):
    # the same stall exists in the rank sink AND metrics.jsonl (the
    # jsonlog mirror); with rank sinks present it must count ONCE
    _write_rank(tmp_path, 0, [100.0],
                extra=[{"kind": "stall", "age_s": 2.0, "count": 1}])
    _jl(str(tmp_path / "metrics.jsonl"),
        [{"kind": "stall", "t": 0.0, "age_s": 2.0, "count": 1}], mode="w")
    agg = live.LiveAggregator()
    rt = live.RunTailer(str(tmp_path))
    agg.consume(*rt.poll())
    assert agg.snapshot(1.0)["events"]["stall"] == 1


def test_live_throughput_sees_interstep_gaps(tmp_path):
    # 8 images every 1s vs 8 images every 2s with the SAME 100ms step
    # dur: images/sum(durs) would be blind to the gap; the active-span
    # rate must halve
    _write_rank(tmp_path, 0, [100.0] * 6)
    agg = live.LiveAggregator()
    rt = live.RunTailer(str(tmp_path))
    agg.consume(*rt.poll())
    fast = agg.snapshot(6.0)["img_per_sec"]
    path = _rank_path(tmp_path, 1)
    recs = [{"kind": "clock", "rank": 1, "t": 0.0, "unix": 0.0, "mono": 0.0}]
    for i in range(6):
        recs.append(_span(1, "step", i * 2.0, 0.1, batch=i, n=8))
    _jl(path, recs, mode="w")
    agg2 = live.LiveAggregator()
    t = live.FileTailer(path, rank=1)
    agg2.consume(t.poll())
    slow = agg2.snapshot(12.0)["img_per_sec"]
    assert slow == pytest.approx(fast / 2, rel=0.05)


# ------------------------------------------------------------- alert rules
def _snap(*, steps=16, compiles=0, stall=0, nonfinite=0, skew=1.0,
          per_rank=None, img_per_sec=None, serve=None, totals=None):
    return {
        "v": 1, "window_s": 5.0, "ranks": 1, "steps": steps, "images": steps,
        "img_per_sec": img_per_sec,
        "step": {"count": steps, "mean_ms": 100.0, "p50_ms": 100.0,
                 "p90_ms": 100.0, "p99_ms": 100.0, "max_ms": 100.0},
        "per_rank_p50_ms": per_rank or {"0": 100.0},
        "straggler_skew": skew, "data_wait_frac": 0.05,
        "compiles": {"count": compiles, "wall_s": 0.0},
        "events": {"stall": stall, "data_error": 0, "nonfinite": nonfinite},
        "ckpt": {"saves": 0, "save_max_s": 0.0, "restores": 0},
        "serve": serve,
        "totals": totals or {"steps": steps, "images": steps, "compiles": 0,
                             "stall": 0, "data_error": 0, "nonfinite": 0},
    }


def test_rule_threshold_and_dedup():
    eng = live.RuleEngine([live.AlertRule({"kind": "stall", "threshold": 1})],
                          interval_s=5.0)
    assert eng.evaluate(_snap()) == []
    fired = eng.evaluate(_snap(stall=1))
    assert [a["rule"] for a in fired] == ["stall"]
    assert fired[0]["value"] == 1 and "stall" in fired[0]["message"]
    # continued breach: active alert does NOT re-fire (dedup)
    assert eng.evaluate(_snap(stall=2)) == []
    assert eng.active_rules() == ["stall"]


def test_rule_hysteresis_clear_then_refire():
    eng = live.RuleEngine(
        [live.AlertRule({"kind": "stall", "threshold": 1,
                         "clear_windows": 2})],
        interval_s=5.0,
    )
    assert len(eng.evaluate(_snap(stall=1))) == 1
    assert eng.evaluate(_snap()) == []  # calm 1/2: still active
    assert eng.active_rules() == ["stall"]
    assert eng.evaluate(_snap()) == []  # calm 2/2: clears
    assert eng.active_rules() == []
    assert len(eng.evaluate(_snap(stall=1))) == 1  # new excursion re-fires
    assert eng.fired_counts()["stall"] == 2


def test_rule_breach_windows_requires_consecutive():
    eng = live.RuleEngine(
        [live.AlertRule({"kind": "straggler-skew", "threshold": 1.5,
                         "breach_windows": 2})],
        interval_s=5.0,
    )
    two = {"0": 100.0, "1": 200.0}
    assert eng.evaluate(_snap(skew=2.0, per_rank=two)) == []  # 1/2
    assert eng.evaluate(_snap(skew=1.0, per_rank=two)) == []  # reset
    assert eng.evaluate(_snap(skew=2.0, per_rank=two)) == []  # 1/2 again
    fired = eng.evaluate(_snap(skew=2.0, per_rank=two))       # 2/2
    assert [a["rule"] for a in fired] == ["straggler-skew"]


def test_straggler_rule_needs_two_ranks():
    eng = live.RuleEngine(
        [live.AlertRule({"kind": "straggler-skew", "threshold": 1.5})],
        interval_s=5.0,
    )
    # a huge skew value with a single rank reporting is no signal
    assert eng.evaluate(_snap(skew=9.0, per_rank={"0": 100.0})) == []


def test_recompile_storm_ignores_startup_burst_even_across_lookback():
    eng = live.RuleEngine(
        [live.AlertRule({"kind": "recompile-storm", "threshold": 3,
                         "window_s": 15})],
        interval_s=5.0,
    )
    # startup: a big compile burst BEFORE any step was ever seen
    burst = _snap(steps=0, compiles=10,
                  totals={"steps": 0, "images": 0, "compiles": 10,
                          "stall": 0, "data_error": 0, "nonfinite": 0})
    assert eng.evaluate(burst) == []
    # steps begin; the old burst sits inside the 15s lookback but those
    # windows are non-steady — no storm
    assert eng.evaluate(_snap(compiles=0)) == []
    assert eng.evaluate(_snap(compiles=1)) == []
    # a REAL mid-run storm fires
    fired = eng.evaluate(_snap(compiles=4))
    assert [a["rule"] for a in fired] == ["recompile-storm"]
    assert fired[0]["value"] == 5.0  # 1 + 4 over the steady lookback


def test_throughput_rule_dormant_without_baseline_then_fires():
    rule = live.AlertRule({"kind": "throughput-regression",
                           "threshold": 40.0})
    eng = live.RuleEngine([rule], interval_s=5.0)
    assert eng.evaluate(_snap(img_per_sec=1.0)) == []  # no baseline: dormant
    rule.baseline = 100.0
    assert eng.evaluate(_snap(img_per_sec=70.0)) == []  # above the floor
    fired = eng.evaluate(_snap(img_per_sec=50.0))  # below 100×(1−40%)
    assert [a["rule"] for a in fired] == ["throughput-regression"]
    assert fired[0]["threshold"] == 60.0


def test_p99_rule_reads_serve_probe():
    eng = live.RuleEngine(
        [live.AlertRule({"kind": "p99-breach", "threshold": 250.0,
                         "min_steps": 4})],
        interval_s=5.0,
    )
    calm = {"p50_ms": 10.0, "p99_ms": 40.0, "window_samples": 50,
            "queue_depth": 0, "occupancy": 0.5, "requests": 50,
            "rejected": 0, "replicas": 1, "routable": 1}
    assert eng.evaluate(_snap(serve=calm)) == []
    assert eng.evaluate(_snap(serve=None)) == []  # probe down ≠ breach
    thin = dict(calm, p99_ms=900.0, window_samples=2)
    assert eng.evaluate(_snap(serve=thin)) == []  # too few samples
    hot = dict(calm, p99_ms=900.0)
    assert [a["rule"] for a in eng.evaluate(_snap(serve=hot))] == [
        "p99-breach"
    ]


def test_load_rules_yaml_and_validation(tmp_path):
    rules = live.load_rules(os.path.join(REPO, "config",
                                         "monitor_rules.yaml"))
    assert {r.kind for r in rules} == set(live.RULE_KINDS)
    bad = tmp_path / "bad.yaml"
    bad.write_text("rules:\n  - kind: volcano-eruption\n    threshold: 1\n")
    with pytest.raises(live.RuleError, match="unknown rule kind"):
        live.load_rules(str(bad))
    bad.write_text("rules:\n  - kind: stall\n")
    with pytest.raises(live.RuleError, match="threshold"):
        live.load_rules(str(bad))
    bad.write_text("rules:\n  - kind: stall\n    threshold: 1\n"
                   "  - kind: stall\n    threshold: 2\n")
    with pytest.raises(live.RuleError, match="duplicate"):
        live.load_rules(str(bad))
    bad.write_text("rules:\n  - kind: stall\n    threshold: 1\n"
                   "    blorp: 2\n")
    with pytest.raises(live.RuleError, match="unknown keys"):
        live.load_rules(str(bad))


# ----------------------------------------------------- monitor composition
def test_monitor_tick_emits_schema_valid_records(tmp_path):
    _write_rank(tmp_path, 0, [100.0] * 4,
                extra=[{"kind": "nonfinite", "epoch": 1, "batch": 2,
                        "policy": "skip"}])
    eng = live.RuleEngine(
        [live.AlertRule({"kind": "nonfinite", "threshold": 1})],
        interval_s=1.0,
    )
    mon = live.Monitor(str(tmp_path), eng)
    out = mon.tick()
    mon.close()
    assert [a["rule"] for a in out["alerts"]] == ["nonfinite"]
    recs = [json.loads(ln)
            for ln in open(tmp_path / "MONITOR.jsonl").read().splitlines()]
    kinds = [r["kind"] for r in recs]
    assert kinds == ["monitor.snapshot", "alert"]
    for r in recs:  # every record obeys the declared kind schema
        schema.validate_record(r)
    # the monitor's own sink must NOT look like a rank sink: a fresh
    # rescan sees exactly the run's rank 0, never MONITOR.jsonl
    assert live.RunTailer(str(tmp_path)).rescan() == [0]


def test_prometheus_rendering_golden():
    snap = _snap(steps=10, compiles=2, stall=1, img_per_sec=123.4,
                 totals={"steps": 42, "images": 336, "compiles": 3,
                         "stall": 1, "data_error": 0, "nonfinite": 0})
    rule = live.AlertRule({"kind": "stall", "threshold": 1})
    eng = live.RuleEngine([rule], interval_s=5.0)
    eng.evaluate(snap)  # fires → active, fired=1
    text = live.render_prometheus(snap, eng)
    golden = """\
# HELP dtpu_step_ms cross-rank step time quantiles over the last window (ms)
# TYPE dtpu_step_ms gauge
dtpu_step_ms{quantile="p50"} 100.0
dtpu_step_ms{quantile="p90"} 100.0
dtpu_step_ms{quantile="p99"} 100.0
# HELP dtpu_steps_window steps observed in the last window
# TYPE dtpu_steps_window gauge
dtpu_steps_window 10
# HELP dtpu_straggler_skew slowest/fastest rank p50 step time over the last window
# TYPE dtpu_straggler_skew gauge
dtpu_straggler_skew 1.0
# HELP dtpu_data_wait_frac fraction of the pipeline wall spent waiting on data
# TYPE dtpu_data_wait_frac gauge
dtpu_data_wait_frac 0.05
# HELP dtpu_img_per_sec live throughput over the step-active span of the last window
# TYPE dtpu_img_per_sec gauge
dtpu_img_per_sec 123.4
# HELP dtpu_steps_total steps observed since the monitor attached
# TYPE dtpu_steps_total counter
dtpu_steps_total 42
# HELP dtpu_recompiles_total backend compile events since the monitor attached
# TYPE dtpu_recompiles_total counter
dtpu_recompiles_total 3
# HELP dtpu_events_total resilience events since the monitor attached
# TYPE dtpu_events_total counter
dtpu_events_total{kind="stall"} 1
dtpu_events_total{kind="data_error"} 0
dtpu_events_total{kind="nonfinite"} 0
# HELP dtpu_alerts_total alerts fired per rule since the monitor attached
# TYPE dtpu_alerts_total counter
dtpu_alerts_total{rule="stall"} 1
# HELP dtpu_alert_active 1 while the rule's alert is active (hysteresis window)
# TYPE dtpu_alert_active gauge
dtpu_alert_active{rule="stall"} 1
"""
    assert text == golden


def test_metrics_http_endpoint():
    srv = live.MetricsHTTPServer(port=0).start()
    try:
        srv.update("dtpu_test 1\n")
        url = f"http://{srv.host}:{srv.port}/metrics"
        with urllib.request.urlopen(url, timeout=5) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            assert resp.read() == b"dtpu_test 1\n"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://{srv.host}:{srv.port}/nope", timeout=5
            )
    finally:
        srv.stop()


def test_probe_serve_normalizes_router_and_replica_shapes():
    from distribuuuu_tpu.serve import protocol

    fleet_stats = {
        "replicas": 2, "routable": 2, "requests": 100, "rejected": 3,
        "p50_ms": 10.0, "p90_ms": 20.0, "p99_ms": 30.0,
        "per_replica": [
            {"replica": 0, "routable": True, "queue_depth": 4,
             "occupancy": 0.8},
            {"replica": 1, "routable": True, "queue_depth": 2,
             "occupancy": 0.6},
        ],
    }

    def fake_peer(stats, with_window):
        lst = protocol.open_listener("127.0.0.1", 0)

        def serve_once():
            conn, _ = lst.accept()
            with conn:
                payload = protocol.recv_frame(conn)
                ctrl = protocol.parse_ctrl(payload)
                assert ctrl["op"] == "stats"
                out = dict(stats)
                if with_window and ctrl.get("window_s"):
                    out["window"] = {"samples": 9, "p50_ms": 11.0,
                                     "p90_ms": 22.0, "p99_ms": 333.0}
                protocol.send_frame(conn, json.dumps(out).encode())
            lst.close()

        threading.Thread(target=serve_once, daemon=True).start()
        return lst.getsockname()[:2]

    # fleet router WITH window support: windowed p99, summed queue depth
    out = live.probe_serve(fake_peer(fleet_stats, True), window_s=5.0)
    assert out["p99_ms"] == 333.0 and out["window_samples"] == 9
    assert out["queue_depth"] == 6
    assert out["occupancy"] == pytest.approx(0.7)
    # bare replica (engine.stats shape): cumulative fallback
    replica_stats = {"requests": 50, "rejected": 0, "p50_ms": 5.0,
                     "p99_ms": 15.0, "queue_depth": 3,
                     "batch_occupancy": 0.9}
    out = live.probe_serve(fake_peer(replica_stats, False), window_s=5.0)
    assert out["p99_ms"] == 15.0 and out["queue_depth"] == 3
    assert out["window_samples"] == 50 and out["replicas"] == 1
    # a dead peer is None, not an exception
    assert live.probe_serve(("127.0.0.1", 1), timeout=0.2) is None


# --------------------------------------------- bench trajectory + the gate
def test_bench_index_builds_ordered_trajectory(chip_bench_root):
    root, values, _ = chip_bench_root
    series = bench_history.build_index(root)["series"][
        "resnet50_train_images_per_sec_per_chip"]
    assert [p["round"] for p in series] == ["r01", "r02", "r03", "r04", "r05"]
    assert [p["value"] for p in series] == values
    assert series[0]["source"] == "BENCH_r01.json"
    # the committed index matches a regeneration (tier-1 keeps it fresh:
    # landing a new BENCH artifact without re-running bench_history fails)
    committed = json.load(open(os.path.join(REPO, "BENCH_INDEX.json")))
    assert committed["series"] == bench_history.build_index(REPO)["series"]


def test_run_report_compare_accepts_bench_index(chip_bench_root):
    root, values, copy_in = chip_bench_root
    copy_in("COSTMODEL_r01.json")
    index = bench_history.build_index(root)
    base = run_report.comparable_metrics(index)
    assert base["img_per_sec"] == values[-1]  # the LATEST point gates
    # the cost-model series (COSTMODEL_r*.json, PR 8) ride the same gate
    assert "mfu" in base and "hbm_headroom_pct" in base
    current = {"step": {"p50_ms": 1.0}, "img_per_sec": base["img_per_sec"]}
    cmp = run_report.compare(current, index, 10.0, {})
    assert cmp["ok"] and cmp["checked"] == 1  # only img_per_sec overlaps
    worse = dict(current, img_per_sec=base["img_per_sec"] * 0.5)
    assert not run_report.compare(worse, index, 10.0, {})["ok"]


# --------------------------------------------------- CLI / soak validation
def _tool(name, *args):
    return subprocess.run(
        [sys.executable, os.path.join("tools", name), *args],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300,
    )


def test_soak_dry_validates_plan_and_rules():
    out = _tool("soak.py", "--dry")
    assert out.returncode == 0, out.stdout + out.stderr
    assert "5 intervals" in out.stdout and "p99_burst" in out.stdout


def test_monitor_dry_validates_rules_and_fails_on_broken(tmp_path):
    out = _tool("monitor.py", "--dry")
    assert out.returncode == 0, out.stdout + out.stderr
    bad = tmp_path / "bad.yaml"
    bad.write_text("rules:\n  - kind: nope\n    threshold: 1\n")
    out = _tool("monitor.py", "--dry", "--rules", str(bad))
    assert out.returncode == 1
    assert "unknown rule kind" in out.stdout


def test_monitor_cli_once_over_finished_run(tmp_path):
    _write_rank(tmp_path, 0, [100.0] * 4,
                extra=[{"kind": "stall", "age_s": 2.0, "count": 1}])
    out = _tool("monitor.py", str(tmp_path), "--once")
    assert out.returncode == 0, out.stdout + out.stderr
    assert "ALERT stall" in out.stdout
    assert "1 alert(s) fired" in out.stdout
    assert os.path.exists(tmp_path / "MONITOR.jsonl")


# --------------------------------------------------- trajectory neutrality
@pytest.mark.slow  # 37s: two full toy train runs; tier-1 budget (ISSUE 18)
def test_monitor_attached_changes_no_training_bits(tmp_path):
    """The ISSUE 7 hard contract, fast tier: a Monitor actively tailing
    the run directory (and writing its own sink) while training steps
    execute produces the IDENTICAL state as an unwatched telemetry-off
    run."""
    import jax

    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.parallel import mesh as mesh_lib, sharding
    from distribuuuu_tpu.utils.optim import construct_optimizer

    def run(watched: bool):
        config.reset_cfg()
        cfg.MODEL.ARCH = "resnet18"
        cfg.MODEL.NUM_CLASSES = 10
        cfg.DEVICE.COMPUTE_DTYPE = "float32"
        cfg.TELEMETRY.ENABLED = watched
        out_dir = str(tmp_path / ("on" if watched else "off"))
        stop = threading.Event()
        watcher = None
        if watched:
            spans.setup_telemetry(os.path.join(out_dir, "telemetry"), rank=0)
            eng = live.RuleEngine(
                live.load_rules(os.path.join(REPO, "config",
                                             "monitor_rules.yaml")),
                interval_s=0.05,
            )
            mon = live.Monitor(out_dir, eng)
            watcher = threading.Thread(
                target=mon.run, args=(0.05,),
                kwargs={"should_stop": stop.is_set}, daemon=True,
            )
            watcher.start()
        mesh = mesh_lib.mesh_from_cfg(cfg)
        model = trainer.build_model_from_cfg()
        state = trainer.create_train_state(model, jax.random.key(0), mesh, 32)
        step = trainer.make_train_step(model, construct_optimizer(), topk=5)
        rng = np.random.default_rng(7)
        for it in range(3):
            hb = {
                "image": rng.standard_normal((16, 32, 32, 3)).astype(
                    np.float32
                ),
                "label": rng.integers(0, 10, size=(16,)).astype(np.int32),
                "mask": np.ones((16,), np.float32),
            }
            t0 = time.perf_counter()
            state, _ = step(state, sharding.shard_batch(mesh, hb))
            if watched:
                trainer._emit_batch_spans(
                    "train", 1, it,
                    {"get0": t0, "get1": t0, "put0": t0, "put1": t0,
                     "step0": t0, "step1": time.perf_counter()},
                )
        stop.set()
        if watcher is not None:
            watcher.join(timeout=10)
        spans.close_telemetry()
        return jax.tree.leaves(jax.tree.map(np.asarray, state.params))

    on = run(True)
    off = run(False)
    assert os.path.exists(tmp_path / "on" / "MONITOR.jsonl")
    for a, b in zip(on, off):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------------- soak smoke
@pytest.mark.slow
def test_soak_smoke_verdict(tmp_path):
    """Short referee: control + nonfinite intervals, live-monitored, the
    nonfinite injection raises exactly its alert, the control raises
    none, gates evaluate, and the monitored control run is bit-identical
    to an unmonitored rerun."""
    out_json = str(tmp_path / "SOAK_smoke.json")
    out = _tool("soak.py", "--smoke", "--work-dir", str(tmp_path / "work"),
                "--out", out_json)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
    verdict = json.load(open(out_json))
    assert verdict["ok"] is True
    assert verdict["control_clean"] is True
    assert verdict["alerts_exact"] is True
    assert verdict["divergence"]["bit_identical"] is True
    names = {i["name"]: i for i in verdict["intervals"]}
    assert names["control"]["raised_alerts"] == []
    assert names["nonfinite"]["raised_alerts"] == ["nonfinite"]
    assert names["nonfinite"]["gate"]["ok"] is True
    # the soak's own event stream obeys the declared schema
    events = [json.loads(ln) for ln in open(
        tmp_path / "work" / "soak_events.jsonl"
    ).read().splitlines()]
    assert {e["kind"] for e in events} == {"soak.interval", "soak.verdict"}
    for e in events:
        schema.validate_record(e)
