"""Async execution plane (distribuuuu_tpu/asyncplane/, ISSUEs 10+11):
committer ordering (manifest strictly last) + join-barrier correctness,
async-vs-sync checkpoint payload equality, concurrent-eval result parity
with sync eval, compile-cache hit/miss counters (unit + a real cold/warm
restart pair), config validation, the new schema kinds, the run_report
on/off-path checkpoint section, BENCH_r06 indexing — and the hard
contract: async-everything on ≡ fully-sync run bit-identical.

ISSUE 11 additions: the dispatch sequencer (token FIFO + fence-on-switch
+ wedge watchdog), the cross-host commit barrier protocol (single- and
2-process), the subprocess-isolated AOT memory probe (byte-identical to
in-process; coexists with the compile cache), snapshot materialization
of process-spanning leaves, and the deadlock-regression pins: the
async-everything trajectory bit-identical to sync at 8 devices (the
previously-deadlocking configuration) and a real 2-process multi-host
async commit.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

import distribuuuu_tpu.config as config
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu.asyncplane import committer, compile_cache, evalloop
from distribuuuu_tpu.telemetry import (
    registry as registry_lib,
    runtime as telemetry_runtime,
    schema,
    spans,
)
from distribuuuu_tpu.utils import checkpoint as ckpt, jsonlog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import bench_history  # noqa: E402
import run_report  # noqa: E402


@pytest.fixture(autouse=True)
def _drain_and_close():
    yield
    try:
        committer.join_commits()
    except committer.AsyncCommitError:
        pass
    spans.close_telemetry()
    jsonlog.close_metrics_log()
    registry_lib.get_registry().reset()


def _tree(seed=0.0):
    return {
        "params": {"w": np.arange(6, dtype=np.float32).reshape(2, 3) + seed},
        "batch_stats": {"m": np.ones(3, np.float32)},
        "opt_state": {"mu": np.zeros(3, np.float32), "lr": 0.1},
        "step": np.int32(7),
    }


# ------------------------------------------------------------- committer
def test_manifest_written_strictly_last(tmp_path, monkeypatch):
    """The PR 3 commit protocol survives going async: at the injectable
    crash-window hook (payload durable, manifest pending) the orbax
    payload files are ALL on disk and MANIFEST.json is NOT."""
    from distribuuuu_tpu.resilience import manifest as manifest_lib
    from distribuuuu_tpu.utils import faults

    cfg.OUT_DIR = str(tmp_path)
    cfg.CHECKPOINT.ASYNC = True
    observed = {}

    def probe(path, epoch):
        payload_files = []
        for dirpath, _, names in os.walk(path):
            payload_files += [n for n in names if n != "MANIFEST.json"]
        observed["payload_files"] = len(payload_files)
        observed["manifest_there"] = os.path.isfile(
            os.path.join(path, "MANIFEST.json")
        )

    monkeypatch.setattr(faults, "maybe_kill_mid_async_save", probe)
    path = ckpt.save_checkpoint(_tree(), 0, 0.5, is_best=False)
    committer.join_commits()
    assert observed["payload_files"] > 0  # orbax payload fully written...
    assert observed["manifest_there"] is False  # ...manifest strictly after
    ok, reason = manifest_lib.verify_checkpoint(path)
    assert ok, reason


def test_join_barrier_serializes_back_to_back_saves():
    """submit joins the previous commit FIRST: at most one commit in
    flight, completion order == submit order even when the first commit
    is slow."""
    order = []

    def slow():
        time.sleep(0.3)
        order.append("a")

    committer.submit_commit("a", slow)
    committer.submit_commit("b", lambda: order.append("b"))
    # the second submit could only start after "a" fully committed
    assert order[0] == "a"
    committer.join_commits()
    assert order == ["a", "b"]


def test_commit_failure_surfaces_at_join():
    def boom():
        raise OSError("disk gone")

    committer.submit_commit("ckpt_ep_042", boom)
    with pytest.raises(committer.AsyncCommitError, match="ckpt_ep_042"):
        committer.join_commits()
    committer.join_commits()  # error consumed; barrier is clean again


def test_async_payload_bitwise_equals_sync(tmp_path):
    tree = _tree()
    cfg.OUT_DIR = str(tmp_path / "async")
    cfg.CHECKPOINT.ASYNC = True
    p_async = ckpt.save_checkpoint(tree, 0, 0.5, is_best=True)
    committer.join_commits()
    cfg.CHECKPOINT.ASYNC = False
    cfg.OUT_DIR = str(tmp_path / "sync")
    p_sync = ckpt.save_checkpoint(tree, 0, 0.5, is_best=True)
    a, b = ckpt.load_checkpoint(p_async), ckpt.load_checkpoint(p_sync)
    la = jax.tree_util.tree_flatten_with_path(a)[0]
    lb = jax.tree_util.tree_flatten_with_path(b)[0]
    assert [k for k, _ in la] == [k for k, _ in lb]
    for (_, va), (_, vb) in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))
    # the best side-writes committed (and verify) in both modes
    from distribuuuu_tpu.resilience import manifest as manifest_lib

    for out in ("async", "sync"):
        ok, reason = manifest_lib.verify_checkpoint(
            str(tmp_path / out / "checkpoints" / "best")
        )
        assert ok, (out, reason)


def test_async_multi_host_gate_lifted_with_sequencer(monkeypatch):
    """ISSUE 11: multi-host async commit is ON by default (the
    cross-host barrier handles it); ASYNC.SEQUENCER=False is the
    explicit escape hatch restoring the PR 10 single-host gate."""
    cfg.CHECKPOINT.ASYNC = True
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    assert ckpt.async_enabled() is True  # barrier-backed multi-host
    cfg.ASYNC.SEQUENCER = False
    assert ckpt.async_enabled() is False  # the escape hatch
    cfg.ASYNC.SEQUENCER = True
    monkeypatch.setattr(jax, "process_count", lambda: 1)
    assert ckpt.async_enabled() is True


def test_preempt_save_drains_committer_first(tmp_path):
    """The preemption join barrier: a slow in-flight commit becomes
    durable BEFORE the preempt checkpoint is written synchronously."""
    order = []

    def slow():
        time.sleep(0.2)
        order.append("boundary_commit")

    cfg.OUT_DIR = str(tmp_path)
    cfg.CHECKPOINT.ASYNC = True
    committer.submit_commit("ckpt_ep_000", slow)
    path = ckpt.save_preempt_checkpoint(_tree(), 1, 0.0)
    order.append("preempt_saved")
    assert order == ["boundary_commit", "preempt_saved"]
    from distribuuuu_tpu.resilience import manifest as manifest_lib

    ok, reason = manifest_lib.verify_checkpoint(path)
    assert ok, reason  # the preempt save itself committed synchronously


# -------------------------------------------------------- concurrent eval
def _eval_setup():
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.data.dummy import DummyDataset
    from distribuuuu_tpu.data.loader import Loader
    from distribuuuu_tpu.parallel import mesh as mesh_lib

    config.reset_cfg()
    cfg.MODEL.ARCH = "resnet18"
    cfg.MODEL.NUM_CLASSES = 10
    cfg.MODEL.DUMMY_INPUT = True
    cfg.DEVICE.COMPUTE_DTYPE = "float32"
    cfg.TRAIN.IM_SIZE = 16
    cfg.TRAIN.BATCH_SIZE = 1
    cfg.RNG_SEED = 1
    mesh = mesh_lib.build_mesh()
    model = trainer.build_model_from_cfg()
    eval_step = trainer.make_eval_step(model, topk=5)
    state = trainer.create_train_state(model, jax.random.key(0), mesh, 16)
    loader = Loader(
        DummyDataset(length=20, size=16), batch_size=8, shuffle=False,
        drop_last=False, workers=2,
    )
    loader.set_epoch(0)
    return trainer, mesh, state, eval_step, loader


def test_concurrent_eval_matches_sync_validate():
    """The worker runs the REAL validate body against a device snapshot:
    result 4-tuple identical to the synchronous call, and the snapshot
    leaves are genuinely independent copies of the live state."""
    from distribuuuu_tpu.utils.logger import get_logger

    trainer, mesh, state, eval_step, loader = _eval_setup()
    sync = trainer.validate(
        loader, mesh, state, eval_step, 0, get_logger(), quiet=True
    )

    conc = evalloop.ConcurrentEval(
        lambda snap, ep: trainer.validate(
            loader, mesh, snap, eval_step, ep, get_logger(),
            quiet=True, watch_preemption=False,
        )
    )
    conc.launch(state, 0)
    assert conc.in_flight
    ep, result, snap = conc.join()
    assert ep == 0 and not conc.in_flight
    assert result == sync
    # the snapshot is a COPY: same values, different buffers
    live_leaf = jax.tree.leaves(state.params)[0]
    snap_leaf = jax.tree.leaves(snap.params)[0]
    np.testing.assert_array_equal(np.asarray(live_leaf), np.asarray(snap_leaf))
    assert snap_leaf is not live_leaf


def test_concurrent_eval_relaunch_guard_and_error_propagation():
    class _S:  # minimal state stand-in with .replace
        params = {"w": np.ones(2, np.float32)}
        batch_stats = {}
        step = 0
        key = None

        def replace(self, **kw):
            return self

    def boom(snap, ep):
        raise RuntimeError("eval exploded")

    conc = evalloop.ConcurrentEval(boom)
    conc.launch(_S(), 3)
    with pytest.raises(RuntimeError, match="eval exploded"):
        conc.join()
    ok = evalloop.ConcurrentEval(lambda snap, ep: (1.0, 2.0, 3.0, 4))
    ok.launch(_S(), 0)
    with pytest.raises(RuntimeError, match="still in flight"):
        ok.launch(_S(), 1)
    assert ok.join()[1] == (1.0, 2.0, 3.0, 4)


# ----------------------------------------------------------- compile cache
def test_compile_cache_config_validation(tmp_path, monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_DIR, raising=False)
    cfg.COMPILE_CACHE.MIN_COMPILE_TIME_S = -1.0
    with pytest.raises(ValueError, match="MIN_COMPILE_TIME_S"):
        compile_cache.setup_from_cfg(cfg)
    config.reset_cfg()
    cfg.COMPILE_CACHE.MAX_SIZE_MB = -5
    with pytest.raises(ValueError, match="MAX_SIZE_MB"):
        compile_cache.setup_from_cfg(cfg)
    config.reset_cfg()
    # CPU backend, not enabled: off (the cache is opt-in off the chip)
    assert compile_cache.setup_from_cfg(cfg) is None
    cfg.COMPILE_CACHE.ENABLED = True
    cfg.COMPILE_CACHE.DIR = str(tmp_path / "cc")
    cache_dir = compile_cache.setup_from_cfg(cfg)
    assert cache_dir == str(tmp_path / "cc") and os.path.isdir(cache_dir)
    assert jax.config.jax_compilation_cache_dir == cache_dir
    # a later disabled run in the same process does not keep writing there
    config.reset_cfg()
    compile_cache.setup_from_cfg(cfg)
    assert not jax.config.jax_compilation_cache_dir


_PLACEMENT_SCRIPT = """
import json, sys
import jax
import distribuuuu_tpu.config as config
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu.asyncplane import compile_cache
out = []
for out_dir, enabled in json.loads(sys.argv[1]):
    config.reset_cfg()
    cfg.OUT_DIR = out_dir
    cfg.COMPILE_CACHE.ENABLED = enabled
    before = jax.config.jax_compilation_cache_dir
    got = compile_cache.setup_from_cfg(cfg)
    out.append([before, got, jax.config.jax_compilation_cache_dir])
print("PLACED " + json.dumps(out))
"""


def _placement(tmp_path, runs, env_dir=None):
    """[before, returned, after] of jax's cache dir for each (OUT_DIR,
    ENABLED) run, in a fresh process."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    env.pop(compile_cache.ENV_DIR, None)
    if env_dir:
        env[compile_cache.ENV_DIR] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", _PLACEMENT_SCRIPT, json.dumps(runs)],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=str(tmp_path),
    )
    assert out.returncode == 0, out.stdout + out.stderr
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("PLACED ")]
    return json.loads(line[-1][len("PLACED "):])


def test_cache_placed_from_outside_is_untouched(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: no code path clears or replaces
    ``jax_compilation_cache_dir``, whatever COMPILE_CACHE says."""
    outside = str(tmp_path / "placed_outside")
    rows = _placement(
        tmp_path, [[str(tmp_path / "a"), False], [str(tmp_path / "b"), True]],
        env_dir=outside,
    )
    for before, returned, after in rows:
        assert before == returned == after == outside
    assert not os.path.exists(tmp_path / "a" / "compile_cache")


def test_cache_default_is_one_fixed_dir_in_the_checkout(tmp_path):
    """Variable unset and enabled: the fixed in-checkout directory —
    identical across two OUT_DIRs and two processes, never under OUT_DIR."""
    runs = [[str(tmp_path / "a"), True], [str(tmp_path / "b"), True]]
    first = _placement(tmp_path, runs)
    second = _placement(tmp_path, runs[::-1])
    placed = {row[1] for row in first + second}
    assert placed == {os.path.join(REPO, ".compile_cache")}
    assert compile_cache.CHECKOUT_DIR == placed.pop()


def test_cache_hit_suppresses_compile_count(tmp_path):
    """Unit-level listener contract (telemetry/runtime.py): the bus
    sequence of a cache hit (cache_hits event → backend_compile
    duration) counts a hit, NOT a compile; a miss still counts the
    compile. kind=\"compile.cache\" records land schema-valid."""
    path = spans.setup_telemetry(str(tmp_path), rank=0)
    reg = registry_lib.get_registry()
    reg.reset()
    # a cache hit: the following backend_compile is a deserialization
    telemetry_runtime._on_event("/jax/compilation_cache/cache_hits")
    telemetry_runtime._on_event_duration(
        "/jax/core/compile/backend_compile_duration", 0.004
    )
    # a cache miss: the following backend_compile is the real thing
    telemetry_runtime._on_event("/jax/compilation_cache/cache_misses")
    telemetry_runtime._on_event_duration(
        "/jax/core/compile/backend_compile_duration", 1.5
    )
    snap = reg.snapshot()["counters"]
    assert snap["jit.cache_hits"] == 1
    assert snap["jit.cache_misses"] == 1
    assert snap["jit.compiles"] == 1  # only the miss compiled
    recs = [json.loads(ln) for ln in open(path).read().splitlines()]
    cache_recs = [r for r in recs if r["kind"] == "compile.cache"]
    assert [r["event"] for r in cache_recs] == ["hit", "miss"]
    for r in cache_recs:
        schema.validate_record(r)
    # exactly ONE kind="compile" record — the real compile, not the hit
    assert len([r for r in recs if r["kind"] == "compile"]) == 1


_CACHE_SCRIPT = """
import os, sys, json
os.environ["JAX_PLATFORMS"] = "cpu"
import jax, jax.numpy as jnp
jax.config.update("jax_platforms", "cpu")
import distribuuuu_tpu.config as config
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu.asyncplane import compile_cache
from distribuuuu_tpu.telemetry import registry as registry_lib, spans
cache_dir, sink_dir = sys.argv[1], sys.argv[2]
config.reset_cfg()
cfg.COMPILE_CACHE.ENABLED = True
cfg.COMPILE_CACHE.DIR = cache_dir
compile_cache.setup_from_cfg(cfg)
spans.setup_telemetry(sink_dir, rank=0)
f = jax.jit(lambda x: (x * 2 + 1).sum())
g = jax.jit(lambda x, y: jnp.tanh(x) @ y)
f(jnp.ones((64, 64))).block_until_ready()
g(jnp.ones((16, 16)), jnp.ones((16, 16))).block_until_ready()
print("COUNTERS " + json.dumps(
    registry_lib.get_registry().snapshot()["counters"]))
"""


def test_warm_restart_hits_cache_zero_compiles(tmp_path):
    """The real thing, across processes: a cold run populates the cache
    (misses, real compiles); a warm rerun of the same programs in a
    FRESH interpreter reports cache hits and ZERO counted compiles."""
    script = tmp_path / "cc_script.py"
    script.write_text(_CACHE_SCRIPT)
    env = {**os.environ, "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", "")}

    def run(tag):
        out = subprocess.run(
            [sys.executable, str(script), str(tmp_path / "cache"),
             str(tmp_path / tag)],
            capture_output=True, text=True, env=env, cwd=REPO, timeout=180,
        )
        assert out.returncode == 0, out.stdout + out.stderr
        line = [ln for ln in out.stdout.splitlines()
                if ln.startswith("COUNTERS ")][-1]
        return json.loads(line[len("COUNTERS "):])

    cold = run("cold")
    assert cold.get("jit.compiles", 0) >= 2  # the two user programs
    assert cold.get("jit.cache_misses", 0) >= 2
    assert cold.get("jit.cache_hits", 0) == 0
    warm = run("warm")
    assert warm.get("jit.compiles", 0) == 0  # everything deserialized
    assert warm.get("jit.cache_hits", 0) >= 2


# ----------------------------------------------------- dispatch sequencer
def test_sequencer_passthrough_when_not_installed():
    from distribuuuu_tpu.asyncplane import sequencer

    sequencer.shutdown()
    assert not sequencer.installed()
    # zero-overhead path: the fn runs directly, fence kwarg ignored
    assert sequencer.dispatch("train", lambda a, b: a + b, 2, 3,
                              fence=True) == 5


def test_sequencer_token_order_fence_and_stats():
    """Two streams hammering the ring: every dispatch serialized, token
    grants strictly FIFO, the stream switches recorded, and the eval
    stream's per-dispatch fence clears its own fence (train never
    inherits an eval fence)."""
    import threading

    import jax.numpy as jnp

    from distribuuuu_tpu.asyncplane import sequencer

    sequencer.shutdown()
    seq = sequencer.install(wedge_timeout=0.0)
    active = []  # critical-section occupancy probe
    overlap = []

    def make(stream, n, fence):
        def run():
            for i in range(n):
                def prog(i=i):
                    active.append(stream)
                    if len(active) > 1:
                        overlap.append(tuple(active))
                    out = jnp.ones(()) * i
                    active.remove(stream)
                    return out
                sequencer.dispatch(stream, prog, fence=fence)
        return run

    threads = [
        threading.Thread(target=make("train", 40, False)),
        threading.Thread(target=make("eval", 40, True)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert overlap == []  # token held exclusively for every dispatch
    st = seq.snapshot_stats()
    assert st["tokens"] == 80
    assert st["streams"] == {"train": 40, "eval": 40}
    assert st["switches"] >= 1  # the streams interleaved at least once
    assert st["wedges"] == 0
    sequencer.shutdown()


def test_sequencer_wedge_flag_and_record(tmp_path):
    """A dispatcher that holds the token past the watchdog timeout is
    flagged — kind=\"dispatch.wedge\" record + counter — while the other
    stream's dispatch completes once the hold ends (alert, not hang)."""
    import threading
    import time as _time

    from distribuuuu_tpu.asyncplane import sequencer

    path = spans.setup_telemetry(str(tmp_path), rank=0)
    reg = registry_lib.get_registry()
    reg.reset()
    sequencer.shutdown()
    sequencer.install(wedge_timeout=0.2)

    def wedged():
        _time.sleep(0.9)  # the stuck dispatch, holding the token
        return 1

    t = threading.Thread(
        target=lambda: sequencer.dispatch("train", wedged), daemon=True
    )
    t.start()
    _time.sleep(0.1)  # let the wedged stream take the token first
    out = sequencer.dispatch("eval", lambda: 2)  # blocks behind the wedge
    t.join(timeout=30)
    assert out == 2  # the run survived the wedge
    spans.close_telemetry()
    recs = [json.loads(ln) for ln in open(path).read().splitlines()]
    wedge = [r for r in recs if r.get("kind") == "dispatch.wedge"]
    assert wedge and wedge[0]["holder"] == "train"
    # the count of wedges lives in the record (live.py sums the records)
    assert [r["count"] for r in wedge] == list(range(1, len(wedge) + 1))
    for r in wedge:
        schema.validate_record(r)
    sequencer.shutdown()


def test_wedge_fault_injection_sleeps_once(monkeypatch):
    from distribuuuu_tpu.utils import faults

    config.reset_cfg()
    cfg.FAULTS.ENABLED = True
    cfg.FAULTS.WEDGE_DISPATCH = 5
    cfg.FAULTS.WEDGE_S = 0.05
    faults.reset()
    import time as _time

    t0 = _time.perf_counter()
    faults.maybe_wedge_dispatch(3)  # below the token index: no-op
    assert _time.perf_counter() - t0 < 0.04
    t0 = _time.perf_counter()
    faults.maybe_wedge_dispatch(5)  # wedges once
    assert _time.perf_counter() - t0 >= 0.05
    t0 = _time.perf_counter()
    faults.maybe_wedge_dispatch(6)  # one-shot: never again
    assert _time.perf_counter() - t0 < 0.04
    config.reset_cfg()
    faults.reset()


# -------------------------------------------- cross-host commit barrier
def _barrier_payload(tmp_path, name="ckpt_ep_007"):
    path = str(tmp_path / "checkpoints" / name)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    return path


def test_multihost_commit_barrier_protocol(tmp_path):
    """Both hosts' shares driven in one process (explicit rank/world):
    the manifest is written strictly AFTER every host arrived, the
    barrier dir is cleaned up, and both hosts emit ckpt.barrier
    records."""
    import threading

    from distribuuuu_tpu.resilience import manifest as manifest_lib

    config.reset_cfg()
    cfg.OUT_DIR = str(tmp_path)
    sink = spans.setup_telemetry(str(tmp_path / "telemetry"), rank=0)
    path = _barrier_payload(tmp_path)
    payload = {"w": np.arange(4.0)}
    order = []

    def write_payload():
        import orbax.checkpoint as ocp

        order.append("payload")
        ocp.PyTreeCheckpointer().save(path, payload, force=True)

    def write_manifest():
        # every host must have arrived BEFORE the manifest commits
        bdir = committer.barrier_dir(path)
        assert os.path.isfile(os.path.join(bdir, "host0.arrived"))
        assert os.path.isfile(os.path.join(bdir, "host1.arrived"))
        order.append("manifest")
        manifest_lib.write_manifest(path, payload, kind="full", epoch=7)

    peer = threading.Thread(
        target=committer.multihost_commit,
        args=(path, None, 7, lambda: None, lambda: None),
        kwargs={"rank": 1, "world": 2}, daemon=True,
    )
    peer.start()
    committer.multihost_commit(
        path, payload, 7, write_payload, write_manifest, rank=0, world=2
    )
    peer.join(timeout=60)
    assert not peer.is_alive()
    assert order == ["payload", "manifest"]  # payload first, marker last
    ok, reason = manifest_lib.verify_checkpoint(path)
    assert ok, reason
    assert not os.path.isdir(committer.barrier_dir(path))  # cleaned up
    spans.close_telemetry()
    recs = [json.loads(ln) for ln in open(sink).read().splitlines()]
    barrier = [r for r in recs if r.get("kind") == "ckpt.barrier"]
    assert {r["host"] for r in barrier} == {0, 1}
    for r in barrier:
        schema.validate_record(r)
        assert r["hosts"] == 2


def test_multihost_barrier_stale_attempt_cannot_satisfy(tmp_path):
    """A barrier dir left by a killed previous attempt is cleared by the
    new attempt's open — stale arrivals never satisfy a fresh save."""
    path = _barrier_payload(tmp_path)
    bdir = committer.barrier_dir(path)
    os.makedirs(bdir, exist_ok=True)
    # stale state from a dead run: OPEN + a peer arrival
    open(os.path.join(bdir, "OPEN"), "w").write("stale")
    open(os.path.join(bdir, "host1.arrived"), "w").write("stale")
    committer.open_barrier(path)
    assert os.path.isfile(os.path.join(bdir, "OPEN"))
    assert not os.path.isfile(os.path.join(bdir, "host1.arrived"))


def test_multihost_barrier_timeout_is_an_error(tmp_path, monkeypatch):
    """A peer that never arrives surfaces as TimeoutError (→
    AsyncCommitError at the join barrier), bounded by
    ASYNC.BARRIER_TIMEOUT_S — never a silent hang."""
    config.reset_cfg()
    cfg.OUT_DIR = str(tmp_path)
    cfg.ASYNC.BARRIER_TIMEOUT_S = 0.3
    path = _barrier_payload(tmp_path)
    with pytest.raises(TimeoutError, match="BARRIER_TIMEOUT"):
        committer.multihost_commit(
            path, None, 7, lambda: None, lambda: None, rank=0, world=2
        )
    config.reset_cfg()


def test_snapshot_tree_materializes_and_refuses():
    """The multi-host snapshot assembly: replicated shards (same index,
    many devices) dedup and cover; split shards assemble in place; a
    cross-host-sharded leaf (local shards cannot cover) refuses with
    MultiHostSnapshotError — the degrade-to-sync trigger."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distribuuuu_tpu.parallel import mesh as mesh_lib

    # the real thing on the live mesh (fully-addressable fast path)
    mesh = mesh_lib.build_mesh()
    arr = jax.device_put(
        jnp.arange(16.0).reshape(4, 4), NamedSharding(mesh, P())
    )
    snap = committer.snapshot_tree({"a": arr, "b": 3})
    np.testing.assert_array_equal(snap["a"], np.arange(16.0).reshape(4, 4))
    assert snap["b"] == 3

    # replicated process-spanning leaf: every local shard is the full
    # array under the same index — assembles, covered once
    full = np.arange(6.0)
    out = committer._assemble_shards(
        (6,), np.float32,
        [((slice(None),), full), ((slice(None),), full)],
    )
    np.testing.assert_array_equal(out, full)

    # locally-sharded leaf: disjoint slices assemble in place
    out = committer._assemble_shards(
        (4,), np.float32,
        [((slice(0, 2),), np.array([0.0, 1.0])),
         ((slice(2, 4),), np.array([2.0, 3.0]))],
    )
    np.testing.assert_array_equal(out, np.arange(4.0))

    # cross-host-sharded: local coverage is partial — refuse
    with pytest.raises(committer.MultiHostSnapshotError, match="2/4"):
        committer._assemble_shards(
            (4,), np.float32, [((slice(0, 2),), np.array([0.0, 1.0]))]
        )


# --------------------------------------- subprocess-isolated AOT probe
def test_memory_probe_subprocess_matches_inprocess():
    """The isolated AOT probe's memory ledger is byte-identical to the
    in-process lowered.compile().memory_analysis() — same StableHLO,
    same SPMD options, a pristine child heap."""
    import jax.numpy as jnp

    from distribuuuu_tpu.telemetry import costmodel

    @jax.jit
    def step(x, w):
        return ((x @ w) ** 2).sum()

    x = jnp.ones((8, 4))
    w = jnp.ones((4, 4))
    lowered = step.lower(x, w)
    inproc = costmodel.normalize_memory(
        lowered.compile().memory_analysis()
    )
    probed = costmodel.probe_memory_subprocess(lowered)
    assert probed == inproc


def test_memory_ledger_coexists_with_compile_cache(tmp_path):
    """PR 10 caveat #2 deleted: with the persistent compilation cache
    ACTIVE, the memory half of the ledger still lands (via the
    subprocess probe) — a run gets the cache AND the HBM ledger."""
    import jax.numpy as jnp

    from distribuuuu_tpu.telemetry import costmodel

    config.reset_cfg()
    cfg.COMPILE_CACHE.ENABLED = True
    cfg.COMPILE_CACHE.DIR = str(tmp_path / "cc")
    compile_cache.setup_from_cfg(cfg)
    assert jax.config.jax_compilation_cache_dir  # the hazard is armed
    try:
        @jax.jit
        def step(x):
            return (x * 2.0).sum()

        analyses = costmodel.analyze_jitted(
            step, (jnp.ones((16, 16)),), with_memory=True
        )
        assert analyses["memory"] is not None
        assert analyses["memory"]["total_bytes"] > 0
    finally:
        config.reset_cfg()
        compile_cache.setup_from_cfg(cfg)  # clears the process-global dir


# ------------------------------------------------- schema / report / index
def test_new_kinds_declared_and_static_check_clean():
    assert "ckpt.async" in schema.KINDS
    assert "compile.cache" in schema.KINDS
    for kind in ("dispatch.token", "dispatch.wedge", "ckpt.barrier"):
        assert kind in schema.KINDS  # ISSUE 11 sequencer/barrier kinds
    for kind in ("dispatch.ring", "ckpt.shard"):
        assert kind in schema.KINDS  # ISSUE 18 pod-scale async kinds
    import check_telemetry_schema as chk

    violations, seen = chk.check_tree(
        os.path.join(REPO, "distribuuuu_tpu")
    )
    assert violations == [], violations
    assert "ckpt.async" in seen and "compile.cache" in seen
    assert {"dispatch.token", "dispatch.wedge", "ckpt.barrier"} <= seen
    assert {"dispatch.ring", "ckpt.shard"} <= seen


def test_run_report_splits_on_vs_off_path(tmp_path):
    """run_report's checkpoint section attributes trainer-blocked
    (snapshot) vs background (commit) seconds and tallies cache events."""
    tdir = tmp_path / "telemetry"
    path = spans.setup_telemetry(str(tdir), rank=0)
    spans.emit_span("step", 1.0, 1.1, track="pipeline", phase="train",
                    epoch=1, batch=0, n=8)
    spans.emit_span("ckpt_snapshot", 2.0, 2.05, track="ckpt",
                    ckpt="ckpt_ep_000", epoch=0)
    spans.emit_span("ckpt_commit", 2.05, 3.25, track="ckpt",
                    ckpt="ckpt_ep_000", epoch=0)
    spans.emit_event("compile.cache", event="hit", hits=1, misses=0)
    spans.emit_event("compile.cache", event="miss", hits=1, misses=1)
    spans.close_telemetry()
    rep = run_report.build_report(str(tmp_path))
    ck = rep["checkpoint"]
    assert ck["snapshots"] == 1 and ck["commits"] == 1
    assert ck["on_path_s"] == pytest.approx(0.05, abs=1e-3)
    assert ck["off_path_s"] == pytest.approx(1.2, abs=1e-3)
    assert ck["on_path_s"] < 0.5 * ck["off_path_s"]  # the acceptance shape
    assert rep["compile_cache"] == {"hits": 1, "misses": 1}
    # sanity: the record forms above are schema-valid
    for r in [json.loads(ln) for ln in open(path).read().splitlines()]:
        schema.validate_record(r)


def test_run_report_sequencer_and_barrier_sections(tmp_path):
    """run_report surfaces the sequencer's token stats (last
    dispatch.token record wins) and the per-host commit-barrier waits."""
    tdir = tmp_path / "telemetry"
    spans.setup_telemetry(str(tdir), rank=0)
    spans.emit_span("step", 1.0, 1.1, track="pipeline", phase="train",
                    epoch=1, batch=0, n=8)
    spans.emit_event("dispatch.token", tokens=10, streams={"train": 9},
                     max_wait_s=0.01, total_wait_s=0.02, fence_waits=1,
                     fence_wait_s=0.005, max_fence_wait_s=0.005,
                     switches=2, wedges=0)
    spans.emit_event("dispatch.token", tokens=40,
                     streams={"train": 30, "eval": 10},
                     max_wait_s=0.02, total_wait_s=0.09, fence_waits=4,
                     fence_wait_s=0.03, max_fence_wait_s=0.01,
                     switches=8, wedges=0)
    spans.emit_event("ckpt.barrier", ckpt="ckpt_ep_000", host=0, hosts=2,
                     wait_s=0.12)
    spans.emit_event("ckpt.barrier", ckpt="ckpt_ep_000", host=1, hosts=2,
                     wait_s=0.34)
    spans.close_telemetry()
    rep = run_report.build_report(str(tmp_path))
    seq = rep["sequencer"]
    assert seq["tokens"] == 40  # the LAST record's running aggregate
    assert seq["streams"] == {"train": 30, "eval": 10}
    assert seq["max_wait_s"] == pytest.approx(0.02)
    assert seq["fence_waits"] == 4
    barrier = rep["checkpoint"]["barrier"]
    assert barrier["hosts"] == 2
    assert barrier["per_host"]["1"]["max_wait_s"] == pytest.approx(0.34)


def test_dispatch_wedge_rule_fires_and_dedups():
    """The monitor's dispatch-wedge rule: aggregator counts
    dispatch.wedge records into the snapshot, the rule fires on the
    first one, dedups while active, and the shipped rules file declares
    it (the RULE_KINDS pin in test_monitor covers the full set)."""
    from distribuuuu_tpu.telemetry import live

    agg = live.LiveAggregator()
    agg.consume([{"kind": "dispatch.wedge", "age_s": 1.2,
                  "holder": "train", "count": 1, "rank": 0}])
    snap = agg.snapshot(window_s=5.0)
    assert snap["dispatch_wedges"] == 1
    engine = live.RuleEngine(
        [live.AlertRule({"kind": "dispatch-wedge", "threshold": 1})],
        interval_s=5.0,
    )
    fired = engine.evaluate(snap)
    assert [f["rule"] for f in fired] == ["dispatch-wedge"]
    # active alert dedups on the next breached window
    agg.consume([{"kind": "dispatch.wedge", "age_s": 2.0,
                  "holder": "eval", "count": 2, "rank": 0}])
    assert engine.evaluate(agg.snapshot(window_s=5.0)) == []
    # wedge-free windows: value 0, rule calm
    assert engine.evaluate(agg.snapshot(window_s=5.0)) == []
    rules = live.load_rules(
        os.path.join(REPO, "config", "monitor_rules.yaml")
    )
    assert "dispatch-wedge" in {r.kind for r in rules}


def test_bench_index_carries_asyncplane_series(chip_bench_root):
    """BENCH_r06.json indexed (regeneration pin: tests/test_monitor.py
    asserts committed == rebuilt; here the asyncplane series exist and
    none of them rides a throughput-reference name)."""
    root, values, copy_in = chip_bench_root
    copy_in("BENCH_r06.json")
    copy_in("BENCH_r07.json")
    index = bench_history.build_index(root)
    series = index["series"]
    assert "ckpt_trainer_blocked_s_async" in series
    assert "ckpt_trainer_blocked_s_sync" in series
    assert "warm_restart_compiles" in series
    assert "warm_restart_cache_hits" in series
    # the async run blocks the trainer for less than the sync run did
    blocked_async = series["ckpt_trainer_blocked_s_async"][-1]["value"]
    blocked_sync = series["ckpt_trainer_blocked_s_sync"][-1]["value"]
    assert blocked_async < blocked_sync
    # warm restart: previously-compiled step programs not recompiled
    warm = series["warm_restart_compiles"][-1]["value"]
    cold = series["cold_start_compiles"][-1]["value"]
    assert warm <= max(2.0, 0.1 * cold)
    assert series["warm_restart_cache_hits"][-1]["value"] >= 2
    # r07: the sequencer overhead series (concurrent eval at 8 devices
    # — the previously-deadlocking config — completed and was measured)
    assert series["sequencer_tokens_issued"][-1]["value"] > 0
    assert "sequencer_trainer_blocked_s" in series
    assert "sequencer_token_max_wait_s" in series
    # none of the new series can poison the throughput gate
    assert run_report.comparable_metrics(index)["img_per_sec"] == values[-1]


# ---------------------------------------------- cross-host dispatch ring
def _ring_pair(tmp_path, deadline=5.0, detach=600.0):
    """A leader+follower CrossHostRing over one tmp root (both 'hosts'
    in this process — the protocol is pure filesystem, so the ring's
    correctness properties are testable without a second process)."""
    from distribuuuu_tpu.asyncplane import ring as ring_mod

    root = str(tmp_path / "ring")
    lead = ring_mod.CrossHostRing(root, 0, 2, deadline,
                                  detach_after_s=detach)
    lead.open(timeout=1.0)
    follow = ring_mod.CrossHostRing(root, 1, 2, deadline,
                                    detach_after_s=detach)
    follow.open(timeout=1.0)
    return lead, follow


def test_ring_follower_reproduces_leader_order(tmp_path):
    """THE agreement property (tentpole (a)): whatever interleaving the
    leader's two dispatch threads produce, the follower's granted
    (slot, stream) sequence is IDENTICAL — even with adversarial timing
    on the follower's threads. Two SPMD programs from two host threads
    enqueue in ONE per-device order on every host."""
    import threading

    from distribuuuu_tpu.asyncplane import sequencer

    lead_ring, follow_ring = _ring_pair(tmp_path)
    seq_l = sequencer.DispatchSequencer()
    seq_l.attach_ring(lead_ring)
    seq_f = sequencer.DispatchSequencer()
    seq_f.attach_ring(follow_ring)
    n_train, n_eval = 24, 9
    lead_order, follow_order = [], []

    def drive(seq, order, stream, n, delay):
        def run():
            for i in range(n):
                seq.dispatch(stream, lambda: order.append(stream))
                time.sleep(delay)
        return run

    threads = [
        # leader: its local FIFO decides the global order
        threading.Thread(target=drive(seq_l, lead_order, "train",
                                      n_train, 0.001)),
        threading.Thread(target=drive(seq_l, lead_order, "eval",
                                      n_eval, 0.004)),
        # follower: adversarial thread timing — eval hammers early and
        # fast, train lags; the published order must still win
        threading.Thread(target=drive(seq_f, follow_order, "eval",
                                      n_eval, 0.0)),
        threading.Thread(target=drive(seq_f, follow_order, "train",
                                      n_train, 0.002)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert len(lead_order) == len(follow_order) == n_train + n_eval
    assert follow_order == lead_order  # ONE order on every host
    assert not follow_ring.wedged and not follow_ring.detached
    assert follow_ring.stats["slots"] == n_train + n_eval
    assert lead_ring.stats["slots"] == n_train + n_eval
    assert lead_ring.stats["switches"] >= 2  # the streams interleaved


def test_ring_deadline_miss_flags_wedge_then_completes(tmp_path):
    """A follower blocked past ASYNC.RING_DEADLINE_S flags
    dispatch.wedge (record + counter + sticky ring-wedged state for the
    trainer's epoch boundary) but keeps waiting — when the leader's
    order finally lands, the run COMPLETES. Degraded, never hung."""
    import threading

    from distribuuuu_tpu.asyncplane import sequencer

    path = spans.setup_telemetry(str(tmp_path / "telemetry"), rank=1)
    reg = registry_lib.get_registry()
    reg.reset()
    lead_ring, follow_ring = _ring_pair(tmp_path, deadline=0.15)
    seq_f = sequencer.DispatchSequencer()
    seq_f.attach_ring(follow_ring)
    out = []

    def late_leader():
        time.sleep(0.5)  # well past the follower's 0.15s deadline
        lead_ring.publish(0, "eval")

    t = threading.Thread(target=late_leader, daemon=True)
    t.start()
    seq_f.dispatch("eval", lambda: out.append("ran"))
    t.join(timeout=30)
    assert out == ["ran"]  # completed once the order landed
    assert follow_ring.wedged and not follow_ring.detached
    assert follow_ring.stats["deadline_misses"] == 1
    assert seq_f._ring_wedged  # the trainer's epoch-boundary signal
    spans.close_telemetry()
    recs = [json.loads(ln) for ln in open(path).read().splitlines()]
    wedge = [r for r in recs if r.get("kind") == "dispatch.wedge"]
    assert wedge and "ring slot 0" in wedge[0]["phase"]
    assert [r["count"] for r in wedge] == list(range(1, len(wedge) + 1))
    for r in wedge:
        schema.validate_record(r)


def test_ring_detaches_after_leader_silence(tmp_path):
    """Past detach_after_s (the ASYNC.BARRIER_TIMEOUT_S contract) of
    zero leader progress the follower DETACHES to its local FIFO — a
    dead leader costs cross-host agreement, never a hang."""
    from distribuuuu_tpu.asyncplane import sequencer

    lead_ring, follow_ring = _ring_pair(tmp_path, deadline=0.1,
                                        detach=0.3)
    del lead_ring  # the leader never publishes anything
    seq_f = sequencer.DispatchSequencer()
    seq_f.attach_ring(follow_ring)
    t0 = time.perf_counter()
    assert seq_f.dispatch("train", lambda: 42) == 42
    assert time.perf_counter() - t0 < 30  # bounded, not a hang
    assert follow_ring.detached and follow_ring.wedged
    # detached mode: subsequent dispatches grant locally, immediately
    assert seq_f.dispatch("eval", lambda: 7) == 7
    st = follow_ring.snapshot_stats()
    assert st["role"] == "follower" and st["detached"] is True
    assert st["slots"] == 2


def test_ring_validation_and_open_timeout(tmp_path):
    from distribuuuu_tpu.asyncplane import ring as ring_mod
    from distribuuuu_tpu.asyncplane import sequencer

    with pytest.raises(ValueError, match="RING_DEADLINE_S"):
        ring_mod.CrossHostRing(str(tmp_path / "r"), 0, 2, 0.0)
    # follower with no leader: bounded OPEN wait names the knob
    orphan = ring_mod.CrossHostRing(str(tmp_path / "never"), 1, 2, 1.0)
    with pytest.raises(TimeoutError, match="BARRIER_TIMEOUT"):
        orphan.open(timeout=0.2)
    # install_ring requires an installed sequencer
    sequencer.shutdown()
    with pytest.raises(RuntimeError, match="install"):
        sequencer.install_ring(str(tmp_path / "r2"), 0, 2, 1.0)


def test_ring_open_clears_stale_attempt_and_module_api(tmp_path):
    """The leader's open() fresh-clears the ring root — a watermark or
    switch record from a previous (killed) attempt can never leak into
    this run's order. Module API: install_ring attaches to the active
    sequencer, emit_stats rides a schema-valid dispatch.ring record."""
    from distribuuuu_tpu.asyncplane import sequencer

    root = tmp_path / "ring"
    root.mkdir()
    (root / "watermark").write_text('{"seq": 99, "sw": 1}')
    (root / "sw_000000").write_text('{"seq": 0, "stream": "eval"}')
    (root / "OPEN").write_text("stale")
    sink = spans.setup_telemetry(str(tmp_path / "telemetry"), rank=0)
    sequencer.shutdown()
    sequencer.install(wedge_timeout=0.0)
    r = sequencer.install_ring(str(root), 0, 2, 5.0, detach_after_s=1.0)
    assert sequencer.ring_installed()
    assert sorted(os.listdir(root)) == ["OPEN"]  # stale order gone
    assert r.agreed_stream(99) is None
    # idempotent: a re-install keeps the attached ring
    assert sequencer.install_ring(str(root), 0, 2, 5.0) is r
    sequencer.dispatch("train", lambda: 1)
    sequencer.dispatch("eval", lambda: 2)
    # the wedge signal round-trip the trainer boundary uses
    assert not sequencer.ring_wedged()
    _active = sequencer._active
    _active._ring_wedged = True
    assert sequencer.ring_wedged()
    sequencer.clear_ring_wedge()
    assert not sequencer.ring_wedged()
    sequencer.emit_stats(final=True)
    spans.close_telemetry()
    recs = [json.loads(ln) for ln in open(sink).read().splitlines()]
    ring_recs = [r for r in recs if r.get("kind") == "dispatch.ring"]
    assert len(ring_recs) == 1
    assert ring_recs[0]["role"] == "leader"
    assert ring_recs[0]["slots"] == 2
    schema.validate_record(ring_recs[0])
    sequencer.shutdown()


def test_faults_validate_cfg_names_ring_arithmetic():
    """Armed FAULTS knobs with impossible arithmetic refuse at startup,
    naming the knobs AND the units (the satellite-3 contract)."""
    from distribuuuu_tpu.utils import faults

    config.reset_cfg()
    cfg.FAULTS.ENABLED = True
    cfg.FAULTS.WEDGE_RING = 3
    cfg.FAULTS.WEDGE_RING_S = 0.0
    with pytest.raises(ValueError, match="positive number of\\s+seconds"):
        faults.validate_cfg()
    cfg.FAULTS.WEDGE_RING_S = 10.0  # below the 30s default deadline
    with pytest.raises(ValueError) as ei:
        faults.validate_cfg()
    msg = str(ei.value)
    assert "WEDGE_RING_S" in msg and "RING_DEADLINE_S" in msg
    assert "10.0 s" in msg and "30.0 s" in msg  # the arithmetic, named
    cfg.FAULTS.WEDGE_RING_S = 31.0
    faults.validate_cfg()  # now observable: passes
    cfg.FAULTS.WEDGE_RING = -1
    cfg.FAULTS.DROP_SHARD_FILE = 0
    cfg.FAULTS.DROP_SHARD_HOST = -2
    with pytest.raises(ValueError, match="host rank"):
        faults.validate_cfg()
    config.reset_cfg()
    faults.validate_cfg()  # disarmed: no-op
    faults.reset()


def test_wedge_ring_injection_one_shot():
    from distribuuuu_tpu.utils import faults

    config.reset_cfg()
    cfg.FAULTS.ENABLED = True
    cfg.FAULTS.WEDGE_RING = 5
    cfg.FAULTS.WEDGE_RING_S = 0.05
    faults.reset()
    t0 = time.perf_counter()
    faults.maybe_wedge_ring(3)  # below the slot index: no-op
    assert time.perf_counter() - t0 < 0.04
    t0 = time.perf_counter()
    faults.maybe_wedge_ring(5)  # wedges once
    assert time.perf_counter() - t0 >= 0.05
    t0 = time.perf_counter()
    faults.maybe_wedge_ring(6)  # one-shot: never again
    assert time.perf_counter() - t0 < 0.04
    config.reset_cfg()
    faults.reset()


# ------------------------------------------------ sharded multi-host save
def _sharded_fixture(tmp_path, name="ckpt_ep_003"):
    """A hand-built 2-host sharded checkpoint: a float leaf split across
    hosts, a bfloat16 leaf split across hosts, a host-side scalar and
    the optax string format marker (both owned by host 0) — the exact
    leaf species a ZeRO-3 TrainState produces."""
    import jax.numpy as jnp

    w = np.arange(32, dtype=np.float32).reshape(8, 4)
    mu = np.asarray(jnp.arange(6, dtype=jnp.bfloat16))
    marker = "optax_leaves_v1"
    cursor = np.int64(3)
    leaves = [
        {"path": ["params", "w"], "shape": [8, 4], "dtype": "float32"},
        {"path": ["opt", "format"], "shape": [], "dtype": "utf8"},
        {"path": ["opt", "mu", "w"], "shape": [6], "dtype": "bfloat16"},
        {"path": ["cursor"], "shape": [], "dtype": "int64"},
    ]
    raw_marker = np.frombuffer(marker.encode("utf-8"), np.uint8)
    owned0 = {"00000.0": w[:4], "00001.0": raw_marker,
              "00002.0": mu[:3], "00003.0": np.asarray(cursor)}
    shards0 = [
        {"leaf": 0, "key": "00000.0", "index": [[0, 4], [0, 4]],
         "shape": [4, 4], "dtype": "float32"},
        {"leaf": 1, "key": "00001.0", "index": [],
         "shape": [int(raw_marker.size)], "dtype": "utf8"},
        {"leaf": 2, "key": "00002.0", "index": [[0, 3]],
         "shape": [3], "dtype": "bfloat16"},
        {"leaf": 3, "key": "00003.0", "index": [],
         "shape": [], "dtype": "int64"},
    ]
    owned1 = {"00000.1": w[4:], "00002.1": mu[3:]}
    shards1 = [
        {"leaf": 0, "key": "00000.1", "index": [[4, 8], [0, 4]],
         "shape": [4, 4], "dtype": "float32"},
        {"leaf": 2, "key": "00002.1", "index": [[3, 6]],
         "shape": [3], "dtype": "bfloat16"},
    ]
    path = str(tmp_path / "checkpoints" / name)
    os.makedirs(path, exist_ok=True)
    committer.write_host_shards(
        path, 0, 2, owned0,
        {"format": committer.SHARD_FORMAT, "leaves": leaves,
         "shards": shards0},
    )
    committer.write_host_shards(
        path, 1, 2, owned1,
        {"format": committer.SHARD_FORMAT, "leaves": leaves,
         "shards": shards1},
    )
    expect = {"params": {"w": w}, "opt": {"format": marker,
                                          "mu": {"w": mu}},
              "cursor": cursor}
    return path, expect


def test_sharded_roundtrip_bit_identical(tmp_path):
    """Reassembly from per-host shard files is bit-identical for every
    leaf species a ZeRO-3 state holds: split float blocks, split
    bfloat16 (raw-byte round-trip — numpy's npz header cannot carry the
    dtype), host scalars, and the utf8 string format marker."""
    path, expect = _sharded_fixture(tmp_path)
    assert committer.sharded_layout_present(path)
    got = committer.read_sharded_checkpoint(path)
    np.testing.assert_array_equal(got["params"]["w"],
                                  expect["params"]["w"])
    assert got["params"]["w"].dtype == np.float32
    mu = got["opt"]["mu"]["w"]
    assert str(mu.dtype) == "bfloat16"
    assert mu.tobytes() == expect["opt"]["mu"]["w"].tobytes()
    assert got["opt"]["format"] == "optax_leaves_v1"
    assert int(got["cursor"]) == 3
    # load_checkpoint dispatches on the layout, same reassembly
    via_ckpt = ckpt.load_checkpoint(path)
    np.testing.assert_array_equal(via_ckpt["params"]["w"],
                                  expect["params"]["w"])


def test_sharded_restore_refuses_missing_shard(tmp_path):
    """A shard-count mismatch REFUSES, naming the manifest's recorded
    sharding (hosts + the expected file names + which are missing) —
    silently restoring a partial tree is never an option."""
    path, _ = _sharded_fixture(tmp_path)
    os.unlink(os.path.join(path, "shards_host1.npz"))
    with pytest.raises(committer.ShardLayoutError) as ei:
        committer.read_sharded_checkpoint(path)
    msg = str(ei.value)
    assert "hosts=2" in msg and "SHARDS_host0.json" in msg
    assert "shards_host1.npz" in msg and "refusing" in msg


def test_sharded_restore_refuses_layout_drift_and_bad_coverage(tmp_path):
    """Mixed-save shard files (layout drift across hosts) and a layout
    whose shards do not cover a leaf both refuse with the reason."""
    path, _ = _sharded_fixture(tmp_path)
    lay1 = json.load(open(os.path.join(path, "SHARDS_host1.json")))
    drift = dict(lay1)
    drift["leaves"] = list(lay1["leaves"][:-1])  # a different tree spec
    with open(os.path.join(path, "SHARDS_host1.json"), "w") as f:
        json.dump(drift, f)
    with pytest.raises(committer.ShardLayoutError,
                       match="different tree spec"):
        committer.read_sharded_checkpoint(path)
    # coverage hole: host1 stops recording its half of params/w
    cover = dict(lay1)
    cover["shards"] = [m for m in lay1["shards"] if m["leaf"] != 0]
    with open(os.path.join(path, "SHARDS_host1.json"), "w") as f:
        json.dump(cover, f)
    with pytest.raises(committer.ShardLayoutError,
                       match="params/w.*16/32"):
        committer.read_sharded_checkpoint(path)


def test_snapshot_host_shards_ownership_and_refusals(tmp_path):
    """snapshot_host_shards on a host tree: rank 0 owns host-side leaves
    (identical on every host by construction), rank 1 owns none; string
    scalars ride the utf8 tag; object leaves and non-dict containers
    refuse with MultiHostSnapshotError (the sync-collective valve)."""
    tree = {"params": {"w": np.arange(4.0, dtype=np.float32)},
            "opt": {"format": "optax_leaves_v1"},
            "cursor": np.int64(7)}
    owned0, layout0 = committer.snapshot_host_shards(tree, 0)
    owned1, layout1 = committer.snapshot_host_shards(tree, 1)
    assert layout0["leaves"] == layout1["leaves"]  # identical spec
    assert len(owned0) == 3 and owned1 == {}
    path = str(tmp_path / "checkpoints" / "ckpt_ep_000")
    committer.write_host_shards(path, 0, 2, owned0, layout0)
    committer.write_host_shards(path, 1, 2, owned1, layout1)
    got = committer.read_sharded_checkpoint(path)
    np.testing.assert_array_equal(got["params"]["w"],
                                  tree["params"]["w"])
    assert got["opt"]["format"] == "optax_leaves_v1"
    assert int(got["cursor"]) == 7
    with pytest.raises(committer.MultiHostSnapshotError,
                       match="object-dtype"):
        committer.snapshot_host_shards({"bad": np.array(None)}, 0)
    with pytest.raises(committer.MultiHostSnapshotError,
                       match="non-dict"):
        committer.snapshot_host_shards({"t": (np.zeros(2),)}, 0)


def test_manifest_digest_walk_covers_shard_files(tmp_path):
    """The existing MANIFEST digest walk automatically covers the shard
    files: a committed sharded save verifies ok, and a dropped shard
    file FAILS verification — the restart's quarantine + walk-back
    trigger, with no new verification machinery."""
    from distribuuuu_tpu.resilience import manifest as manifest_lib

    path, expect = _sharded_fixture(tmp_path)
    tree = manifest_lib.tree_spec(expect)
    topo = manifest_lib.world_topology(expect)
    manifest_lib.write_manifest(
        path, None, kind="full", epoch=3, tree=tree, topology=topo,
        sharded={"hosts": 2, "files": ["shards_host0.npz",
                                       "shards_host1.npz"]},
    )
    ok, reason = manifest_lib.verify_checkpoint(path)
    assert ok, reason
    man = json.load(open(os.path.join(path, "MANIFEST.json")))
    assert man["sharded"]["hosts"] == 2  # the recorded sharding
    assert set(man["files"]) >= {"shards_host0.npz", "shards_host1.npz",
                                 "SHARDS_host0.json", "SHARDS_host1.json"}
    os.unlink(os.path.join(path, "shards_host1.npz"))
    ok, reason = manifest_lib.verify_checkpoint(path)
    assert not ok and "shards_host1.npz" in reason


def test_drop_shard_file_injection_validates_and_drops(tmp_path):
    """The drop-one-shard-file fault: host index validated against the
    LIVE world (refusal names the range arithmetic), then the victim's
    npz is deleted exactly once."""
    from distribuuuu_tpu.utils import faults

    path, _ = _sharded_fixture(tmp_path)
    config.reset_cfg()
    cfg.FAULTS.ENABLED = True
    cfg.FAULTS.DROP_SHARD_FILE = 3
    cfg.FAULTS.DROP_SHARD_HOST = 5
    faults.reset()
    with pytest.raises(ValueError) as ei:
        faults.maybe_drop_shard_file(path, 3, world=2)
    msg = str(ei.value)
    assert "0 <= host < world (2)" in msg and "shards_host1.npz" in msg
    cfg.FAULTS.DROP_SHARD_HOST = 1
    faults.reset()
    faults.maybe_drop_shard_file(path, 2, world=2)  # wrong epoch: no-op
    assert os.path.isfile(os.path.join(path, "shards_host1.npz"))
    faults.maybe_drop_shard_file(path, 3, world=2)
    assert not os.path.isfile(os.path.join(path, "shards_host1.npz"))
    config.reset_cfg()
    faults.reset()


def test_cross_host_predicate_is_metadata_only():
    """tree_is_cross_host_sharded: False for host trees and
    fully-addressable device arrays (the single-host fast path keeps
    the orbax protocol), no communication, never raises on strings."""
    import jax.numpy as jnp

    tree = {"w": jnp.ones((4, 4)), "s": "optax_leaves_v1",
            "n": np.int64(2)}
    assert committer.tree_is_cross_host_sharded(tree) is False


def test_run_report_ring_and_shard_sections(tmp_path):
    """run_report surfaces the per-host ring waits (dispatch.ring, last
    record per host wins) and the per-host shard-commit durations
    (ckpt.shard) — the satellite-2 sections."""
    tdir = tmp_path / "telemetry"
    path = spans.setup_telemetry(str(tdir), rank=0)
    spans.emit_span("step", 1.0, 1.1, track="pipeline", phase="train",
                    epoch=1, batch=0, n=8)
    spans.emit_event("dispatch.token", tokens=12, streams={"train": 12},
                     max_wait_s=0.01, total_wait_s=0.02, fence_waits=0,
                     fence_wait_s=0.0, max_fence_wait_s=0.0,
                     switches=1, wedges=0)
    spans.emit_event("dispatch.ring", host=0, hosts=2, role="leader",
                     slots=12, switches=3, total_wait_s=0.0,
                     max_wait_s=0.0, deadline_misses=0, wedged=False,
                     detached=False)
    spans.emit_event("dispatch.ring", host=1, hosts=2, role="follower",
                     slots=12, switches=3, total_wait_s=0.8,
                     max_wait_s=0.3, deadline_misses=1, wedged=True,
                     detached=False)
    spans.emit_event("ckpt.shard", ckpt="ckpt_ep_000", host=0, hosts=2,
                     shards=210, bytes=44823923, write_s=0.42)
    spans.emit_event("ckpt.shard", ckpt="ckpt_ep_001", host=0, hosts=2,
                     shards=210, bytes=44823923, write_s=0.38)
    spans.emit_event("ckpt.shard", ckpt="ckpt_ep_000", host=1, hosts=2,
                     shards=80, bytes=44667648, write_s=0.41)
    spans.close_telemetry()
    for r in [json.loads(ln) for ln in open(path).read().splitlines()]:
        schema.validate_record(r)
    rep = run_report.build_report(str(tmp_path))
    ring = rep["sequencer"]["ring"]
    assert ring["hosts"] == 2
    assert ring["per_host"]["0"]["role"] == "leader"
    f = ring["per_host"]["1"]
    assert f["role"] == "follower" and f["wedged"] is True
    assert f["max_wait_s"] == pytest.approx(0.3)
    assert f["deadline_misses"] == 1
    shards = rep["checkpoint"]["shards"]
    assert shards["hosts"] == 2
    h0 = shards["per_host"]["0"]
    assert h0["saves"] == 2 and h0["shards"] == 210
    assert h0["mean_write_s"] == pytest.approx(0.4)
    assert shards["per_host"]["1"]["max_write_s"] == pytest.approx(0.41)


# ------------------------------------------------------- trajectory pin
_PIN_SCRIPT = """
import os, sys, json
os.environ["JAX_PLATFORMS"] = "cpu"
ndev = int(sys.argv[4])
if ndev <= 1:
    os.environ.pop("XLA_FLAGS", None)  # ONE device
else:
    # the multi-device mesh — the configuration whose concurrent eval
    # DEADLOCKED before the dispatch sequencer (ISSUE 11)
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=%d" % ndev
    )
import jax
jax.config.update("jax_platforms", "cpu")
import distribuuuu_tpu.config as config
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu import trainer

out, mode, cc_dir = sys.argv[1], sys.argv[2], sys.argv[3]
config.reset_cfg()
cfg.MODEL.ARCH = "resnet18"
cfg.MODEL.NUM_CLASSES = 10
cfg.MODEL.DUMMY_INPUT = True
cfg.DEVICE.COMPUTE_DTYPE = "float32"
cfg.TRAIN.BATCH_SIZE = 4
cfg.TRAIN.IM_SIZE = 16
cfg.TRAIN.PRINT_FREQ = 64
cfg.TEST.BATCH_SIZE = 32
cfg.TEST.IM_SIZE = 16
cfg.OPTIM.MAX_EPOCH = 2
cfg.OPTIM.BASE_LR = 0.01
cfg.RNG_SEED = 0
cfg.OUT_DIR = out
if mode == "async":
    # async-EVERYTHING: background ckpt commit + concurrent eval +
    # persistent compile cache, all at once
    cfg.CHECKPOINT.ASYNC = True
    cfg.TRAIN.CONCURRENT_EVAL = True
    cfg.COMPILE_CACHE.ENABLED = True
    cfg.COMPILE_CACHE.DIR = cc_dir
best = trainer.train_model()
assert jax.device_count() == ndev
print(f"PIN_DONE best={best}", flush=True)
"""


def _run_pin_pair(tmp_path, ndev: int):
    """Run the async-everything vs fully-sync pin pair at ``ndev``
    virtual devices; returns ((out_dir, evals), (out_dir, evals))."""
    script = tmp_path / "pin.py"
    script.write_text(_PIN_SCRIPT)
    env = {**os.environ, "PYTHONPATH": REPO + os.pathsep
           + os.environ.get("PYTHONPATH", "")}

    def run(mode):
        out_dir = str(tmp_path / mode)
        proc = subprocess.run(
            [sys.executable, str(script), out_dir, mode,
             str(tmp_path / "cc"), str(ndev)],
            capture_output=True, text=True, env=env, cwd=REPO, timeout=540,
        )
        assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
        if mode == "async":  # the overlapped paths genuinely engaged
            assert "concurrent eval: validate() overlaps" in proc.stderr \
                or "concurrent eval: validate() overlaps" in proc.stdout
            if ndev > 1:  # ...under the sequencer, not a silent degrade
                assert "dispatch sequencer active" in proc.stderr \
                    or "dispatch sequencer active" in proc.stdout
        evals = [
            (r["epoch"], r["loss"], r["top1"], r["topk"], r["samples"])
            for r in (json.loads(ln)
                      for ln in open(os.path.join(out_dir, "metrics.jsonl")))
            if r["kind"] == "eval"
        ]
        return out_dir, evals

    return run("async"), run("sync")


def _assert_pin_pair_identical(out_async, ev_async, out_sync, ev_sync):
    assert len(ev_async) == 2 and ev_async == ev_sync  # per-epoch metrics
    for name in ("ckpt_ep_000", "ckpt_ep_001", "best"):
        a = ckpt.load_checkpoint(os.path.join(out_async, "checkpoints", name))
        b = ckpt.load_checkpoint(os.path.join(out_sync, "checkpoints", name))
        la = jax.tree_util.tree_flatten_with_path(a)[0]
        lb = jax.tree_util.tree_flatten_with_path(b)[0]
        assert [k for k, _ in la] == [k for k, _ in lb]
        for (key, va), (_, vb) in zip(la, lb):
            if "best_acc1" in jax.tree_util.keystr(key):
                # concurrent mode: the boundary save records best as of
                # the PREVIOUS eval (this epoch's is still in flight) —
                # documented lag; the state trees themselves must match
                continue
            np.testing.assert_array_equal(
                np.asarray(va), np.asarray(vb),
                err_msg=f"{name}:{jax.tree_util.keystr(key)}",
            )


@pytest.mark.slow  # two full subprocess trainings; tier-1 budget (ISSUE 16)
def test_async_everything_trajectory_bit_identical(tmp_path):
    """ISSUE 10 hard contract, same style as the PR 7 monitor pin: a run
    with background checkpoint commit + concurrent eval + persistent
    compile cache all ON produces BIT-IDENTICAL checkpoint state trees
    and eval metrics as the fully synchronous run, on one device."""
    (out_async, ev_async), (out_sync, ev_sync) = _run_pin_pair(tmp_path, 1)
    _assert_pin_pair_identical(out_async, ev_async, out_sync, ev_sync)


@pytest.mark.slow  # two 8-device subprocess trainings; tier-1 budget
def test_async_everything_multidevice_bit_identical(tmp_path):
    """ISSUE 11 acceptance: the previously-DEADLOCKING configuration —
    concurrent eval + async save + compile cache on the 8-virtual-device
    CPU mesh — completes under the dispatch sequencer (bounded by the
    subprocess timeout: a regression deadlocks and fails the bound) and
    is bit-identical to the fully synchronous 8-device run."""
    (out_async, ev_async), (out_sync, ev_sync) = _run_pin_pair(tmp_path, 8)
    _assert_pin_pair_identical(out_async, ev_async, out_sync, ev_sync)


_MH_SCRIPT = """
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import distribuuuu_tpu.config as config
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu import trainer

config.reset_cfg()
cfg.MODEL.ARCH = "resnet18"
cfg.MODEL.NUM_CLASSES = 10
cfg.MODEL.DUMMY_INPUT = True
cfg.DEVICE.COMPUTE_DTYPE = "float32"
cfg.TRAIN.BATCH_SIZE = 2
cfg.TRAIN.IM_SIZE = 16
cfg.TRAIN.PRINT_FREQ = 32
cfg.TEST.BATCH_SIZE = 16
cfg.TEST.IM_SIZE = 16
cfg.OPTIM.MAX_EPOCH = 1
cfg.RNG_SEED = 0
cfg.OUT_DIR = sys.argv[1]
cfg.CHECKPOINT.ASYNC = True
best = trainer.train_model()
print(f"MH_PIN_DONE rank={jax.process_index()} best={best}", flush=True)
"""


@pytest.mark.slow  # real 2-process distributed run; tier-1 budget
def test_multihost_async_commit_two_processes(tmp_path):
    """ISSUE 11 acceptance, the multi-host half: a REAL 2-process run
    with CHECKPOINT.ASYNC commits its checkpoints through the
    cross-host barrier — both hosts complete, every save has a durable
    manifest, the barrier dirs are cleaned up, and each host left its
    ckpt.barrier telemetry record."""
    import socket

    from distribuuuu_tpu.resilience import manifest as manifest_lib

    script = tmp_path / "mh.py"
    script.write_text(_MH_SCRIPT)
    out = str(tmp_path / "out")
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    procs, logs = [], []
    for rank in range(2):
        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)
        env.update(
            MASTER_ADDR="127.0.0.1", COORDINATOR_PORT=str(port),
            WORLD_SIZE="2", RANK=str(rank),
            PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
        )
        log = open(tmp_path / f"mh{rank}.log", "w+")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, str(script), out], env=env, cwd=REPO,
            stdout=log, stderr=subprocess.STDOUT, text=True,
        ))
    outs = []
    for p, log in zip(procs, logs):
        try:
            p.wait(timeout=420)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
        log.seek(0)
        outs.append(log.read())
        log.close()
    assert [p.returncode for p in procs] == [0, 0], outs[0][-3000:]
    assert all("MH_PIN_DONE" in o for o in outs)
    # every committed save verifies; no barrier litter left behind
    ckpt_dir = os.path.join(out, "checkpoints")
    names = sorted(os.listdir(ckpt_dir))
    assert "ckpt_ep_000" in names
    assert not any(n.endswith(".barrier") for n in names)
    for name in names:
        if name.startswith("."):
            continue
        ok, reason = manifest_lib.verify_checkpoint(
            os.path.join(ckpt_dir, name)
        )
        assert ok, (name, reason)
    # each host recorded its barrier wait
    barrier_hosts = set()
    tdir = os.path.join(out, "telemetry")
    for fname in os.listdir(tdir):
        if not fname.endswith(".jsonl"):
            continue
        for ln in open(os.path.join(tdir, fname)):
            try:
                r = json.loads(ln)
            except json.JSONDecodeError:
                continue
            if r.get("kind") == "ckpt.barrier":
                schema.validate_record(r)
                barrier_hosts.add(r["host"])
    assert barrier_hosts == {0, 1}
