"""LM workload plane (ISSUE 12): decoder-only GPT on the partition layer,
KV-cache generation pinned against the teacher-forced forward, continuous
batching under ragged completions, the streaming serve protocol, and the
telemetry/config satellites."""

import glob
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import distribuuuu_tpu.config as config
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu import models


def _tiny_gpt(seq_len=32, vocab=320, dtype=jnp.float32, **kw):
    from distribuuuu_tpu.models.gpt import GPT

    return GPT(
        vocab_size=vocab, seq_len=seq_len, dim=32, depth=2, num_heads=2,
        dtype=dtype, **kw,
    )


def _params(model, key=0):
    return model.init(
        jax.random.key(key), model.dummy_input(), train=False
    )["params"]


@pytest.fixture()
def f32(monkeypatch):
    cfg.DEVICE.COMPUTE_DTYPE = "float32"
    yield


# ------------------------------------------------------------------ model


def test_gpt_forward_shape_and_registry(f32):
    model = models.build_model("gpt_nano", num_classes=320, seq_len=16,
                               dtype=jnp.float32)
    toks = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.key(0), toks, train=False)["params"]
    logits = model.apply({"params": params}, toks, train=False)
    assert logits.shape == (2, 16, 320)
    assert "gpt_nano" in models.available_models()
    assert "gpt_nano_moe" in models.available_models()


def test_gpt_attention_is_causal(f32):
    """Changing token j must not move any logit at positions < j."""
    model = _tiny_gpt(seq_len=12)
    params = _params(model)
    rng = np.random.default_rng(0)
    a = rng.integers(0, 256, (1, 12)).astype(np.int32)
    b = a.copy()
    b[0, 7:] = (b[0, 7:] + 11) % 256  # perturb the tail only
    la = model.apply({"params": params}, jnp.asarray(a), train=False)
    lb = model.apply({"params": params}, jnp.asarray(b), train=False)
    np.testing.assert_allclose(la[0, :7], lb[0, :7], rtol=0, atol=0)
    assert not np.allclose(la[0, 7:], lb[0, 7:])


def test_gpt_shorter_input_slices_position_table(f32):
    model = _tiny_gpt(seq_len=16)
    params = _params(model)
    toks = jnp.zeros((1, 5), jnp.int32)
    assert model.apply(
        {"params": params}, toks, train=False
    ).shape == (1, 5, 320)
    with pytest.raises(ValueError, match="exceeds the trained context"):
        model.apply(
            {"params": params}, jnp.zeros((1, 17), jnp.int32), train=False
        )


def test_token_metrics_flatten(f32):
    """cross_entropy/accuracy over [B, S, V] == the flattened [B*S, V]
    computation — the task head IS the shared loss (no LM loss path)."""
    from distribuuuu_tpu.utils.metrics import accuracy, cross_entropy

    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.standard_normal((2, 5, 7)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 7, (2, 5)), jnp.int32)
    flat_l = logits.reshape(-1, 7)
    flat_t = labels.reshape(-1)
    np.testing.assert_allclose(
        float(cross_entropy(logits, labels)),
        float(cross_entropy(flat_l, flat_t)), rtol=1e-6,
    )
    a = accuracy(logits, labels, topk=(1, 3))
    b = accuracy(flat_l, flat_t, topk=(1, 3))
    np.testing.assert_allclose(
        [float(x) for x in a], [float(x) for x in b], rtol=1e-6
    )


def test_eval_step_counts_tokens(f32):
    """The one eval step generalizes per-token: count == mask · seq_len,
    masked-out (padded) sequences contribute nothing."""
    from distribuuuu_tpu.parallel.partition.lowering import (
        TrainState, make_eval_step,
    )

    model = _tiny_gpt(seq_len=8)
    params = _params(model)
    state = TrainState(params=params, batch_stats={}, opt_state=None,
                       step=jnp.int32(0), key=jax.random.key(0))
    step = make_eval_step(model, topk=5)
    rng = np.random.default_rng(2)
    batch = {
        "image": jnp.asarray(rng.integers(0, 256, (4, 8)), jnp.int32),
        "label": jnp.asarray(rng.integers(0, 256, (4, 8)), jnp.int32),
        "mask": jnp.asarray([1.0, 1.0, 1.0, 0.0], jnp.float32),
    }
    m = step(state, batch)
    assert float(m["count"]) == 3 * 8
    assert np.isfinite(float(m["loss_sum"]))


# ------------------------------------------------- KV-cache decode (pins)


def _engine(model, params, **kw):
    from distribuuuu_tpu.lm.generate import GenerateEngine

    kw.setdefault("prompt_len", 8)
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("batch_tiles", [2])
    kw.setdefault("cache_tiles", [16])
    return GenerateEngine(model, {"params": params}, **kw)


def test_kv_decode_logits_match_teacher_forced(f32):
    """THE acceptance pin: prefill + per-token decode logits equal the
    full teacher-forced forward at every position (within float
    tolerance), so the cache math is the training math."""
    model = _tiny_gpt(seq_len=32)
    params = _params(model)
    eng = _engine(model, params, batch_tiles=[1], cache_tiles=[32],
                  prompt_len=8, max_new_tokens=8)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 256, (6,)).astype(np.int32)
    # prefill: per-position logits over the prompt
    ptile = 8
    padded = np.zeros((1, ptile), np.int32)
    padded[0, :6] = prompt
    logits_pre, kv = eng._prefill_exec[ptile](eng._variables,
                                              jnp.asarray(padded))
    full = model.apply({"params": params}, jnp.asarray(prompt[None]),
                       train=False)
    np.testing.assert_allclose(
        np.asarray(logits_pre)[0, :6], np.asarray(full)[0], atol=1e-4,
    )
    # decode: one token at a time continues the same logits
    eng.start()
    out = eng.submit(prompt, max_new_tokens=8).result()
    seq = np.concatenate([prompt, out])
    tf = np.asarray(model.apply(
        {"params": params}, jnp.asarray(seq[None]), train=False,
    ))[0]
    # greedy from each teacher-forced position reproduces the decode
    for k, tok in enumerate(out):
        assert int(tf[len(prompt) - 1 + k].argmax()) == tok
    eng.drain()


def test_continuous_batching_ragged_completions_uncontaminated(f32):
    """Concurrent requests with ragged lengths/budgets produce EXACTLY
    the tokens each would produce alone (no cross-request logit
    contamination through the paged cache), and every request retires —
    zero drops."""
    model = _tiny_gpt(seq_len=32)
    params = _params(model)
    eng = _engine(model, params, batch_tiles=[1, 2, 4], cache_tiles=[16, 32],
                  prompt_len=8, max_new_tokens=8).start()
    rng = np.random.default_rng(4)
    subs = []
    for i in range(7):
        p = rng.integers(0, 256, (2 + i % 5,)).astype(np.int32)
        subs.append((p, eng.submit(p, max_new_tokens=2 + i % 6)))
    for p, stream in subs:
        got = stream.result(timeout=120.0)
        assert stream.reason in ("eos", "max_new_tokens", "cache_full")
        seq = list(p)
        for tok in got:  # isolated greedy reference
            lg = model.apply(
                {"params": params},
                jnp.asarray(np.asarray(seq)[None]), train=False,
            )
            assert tok == int(np.asarray(lg)[0, -1].argmax())
            seq.append(tok)
    st = eng.stats()
    assert st["requests"] == 7 and st["retired"] == 7
    assert st["queue_depth"] == 0 and st["active"] == 0
    eng.drain()


def test_moe_gpt_decode_matches_teacher_forced(f32):
    """The MoE LM decodes through MoeMlp's reference path — same pin."""
    model = _tiny_gpt(seq_len=16, moe_experts=4, moe_top_k=2)
    params = _params(model)
    eng = _engine(model, params, batch_tiles=[1], cache_tiles=[16],
                  prompt_len=4, max_new_tokens=4).start()
    prompt = np.asarray([10, 20, 30], np.int32)
    out = eng.submit(prompt).result()
    seq = list(prompt)
    for tok in out:
        lg = model.apply({"params": params},
                         jnp.asarray(np.asarray(seq)[None]), train=False)
        assert tok == int(np.asarray(lg)[0, -1].argmax())
        seq.append(tok)
    eng.drain()


def test_generate_config_validation(f32):
    from distribuuuu_tpu.lm.generate import validate_generate_cfg

    # cache tile cannot hold prompt + new tokens — message carries the sum
    with pytest.raises(ValueError, match=r"MAX_NEW_TOKENS=16 = 32"):
        validate_generate_cfg(64, 16, 16, [2], [24])
    # cache tile beyond the trained context
    with pytest.raises(ValueError, match="LM.SEQ_LEN"):
        validate_generate_cfg(32, 8, 8, [2], [64])
    bt, ct = validate_generate_cfg(64, 16, 16, [], [])
    assert bt == [1, 2, 4] and ct == [64]


def test_engine_tile_growth_and_stats(f32):
    """Admissions past the smallest tiles grow batch/cache tiles through
    the precompiled pads; stats expose the fleet warm-gate contract."""
    model = _tiny_gpt(seq_len=32)
    params = _params(model)
    eng = _engine(model, params, batch_tiles=[1, 2], cache_tiles=[16, 32],
                  prompt_len=8, max_new_tokens=12)
    st = eng.stats()
    assert st["n_compiles"] == eng.n_compiles > 0
    assert st["buckets"] == [[1, 16], [1, 32], [2, 16], [2, 32]]
    eng.start()
    rng = np.random.default_rng(5)
    streams = [
        eng.submit(rng.integers(0, 256, (8,)).astype(np.int32),
                   max_new_tokens=12)
        for _ in range(2)
    ]
    for s in streams:
        # 8 prompt + 12 new = 20 cached positions → past the 16 tile
        assert len(s.result(timeout=120.0)) == 12
    assert (eng._b_tile, eng._c_tile) == (2, 32)  # grew to cover both
    eng.drain()


# ------------------------------------------------ streaming serve protocol


def test_generate_streams_through_protocol(f32):
    from distribuuuu_tpu.lm import service as lm_service
    from distribuuuu_tpu.serve import protocol

    model = _tiny_gpt(seq_len=32)
    params = _params(model)
    eng = _engine(model, params).start()
    listener = protocol.open_listener("127.0.0.1", 0)
    port = listener.getsockname()[1]
    stop = threading.Event()
    t = threading.Thread(
        target=protocol.serve_forever,
        args=(eng, listener, stop.is_set), daemon=True,
    )
    t.start()
    try:
        frames = list(lm_service.generate_request(
            "127.0.0.1", port, tokens=[1, 2, 3], max_new_tokens=4,
        ))
        toks = [f["token"] for f in frames if f.get("stream") == "token"]
        done = frames[-1]
        assert done["stream"] == "done"
        assert done["tokens"] == toks and len(toks) >= 1
        assert done["reason"] in ("eos", "max_new_tokens", "cache_full")
        # stats ctrl frame speaks the fleet pool's warm-gate contract
        import socket

        with socket.create_connection(("127.0.0.1", port)) as c:
            protocol.send_frame(c, protocol.ctrl_request("stats"))
            st = json.loads(protocol.recv_frame(c))
        assert st["n_compiles"] >= len(st["buckets"])
        assert "jit_compiles" in st
        # oversized prompt → clean error frame, connection stays usable
        with pytest.raises(RuntimeError, match="PROMPT_LEN"):
            list(lm_service.generate_request(
                "127.0.0.1", port, tokens=list(range(99)),
            ))
    finally:
        stop.set()
        t.join(5)
        eng.drain()


def test_router_streams_generate_frames(f32):
    """The fleet router relays a generate frame sequence verbatim from a
    (fake, in-process) replica to the client — the new streaming ctrl
    frame rides the existing fleet protocol."""
    import socket

    from distribuuuu_tpu.lm import service as lm_service
    from distribuuuu_tpu.serve import protocol
    from distribuuuu_tpu.serve.fleet.router import Router

    # fake replica: answers one generate request with 3 token frames + done
    rep_listener = protocol.open_listener("127.0.0.1", 0)
    rep_port = rep_listener.getsockname()[1]

    def fake_replica():
        conn, _ = rep_listener.accept()
        with conn:
            payload = protocol.recv_frame(conn)
            ctrl = protocol.parse_ctrl(payload)
            assert ctrl["op"] == "generate"
            for i, tok in enumerate([7, 8, 9]):
                protocol.send_frame(conn, json.dumps(
                    {"stream": "token", "token": tok, "i": i}
                ).encode())
            protocol.send_frame(conn, json.dumps({
                "stream": "done", "tokens": [7, 8, 9], "n": 3,
                "reason": "max_new_tokens",
            }).encode())

    rt = threading.Thread(target=fake_replica, daemon=True)
    rt.start()
    router = Router(request_timeout_s=10.0)
    rep = router.add_replica("127.0.0.1", rep_port)
    router.mark_routable(rep.id)
    client_listener = protocol.open_listener("127.0.0.1", 0)
    client_port = client_listener.getsockname()[1]
    stop = threading.Event()
    st = threading.Thread(
        target=router.serve, args=(client_listener, stop.is_set),
        daemon=True,
    )
    st.start()
    try:
        frames = list(lm_service.generate_request(
            "127.0.0.1", client_port, tokens=[1], max_new_tokens=3,
        ))
        assert [f.get("token") for f in frames[:-1]] == [7, 8, 9]
        assert frames[-1]["stream"] == "done"
        assert int(router.registry.counter("fleet.streams").value) == 1
    finally:
        stop.set()
        st.join(5)
        rep_listener.close()


def test_router_stream_no_routable(f32):
    from distribuuuu_tpu.serve import protocol
    from distribuuuu_tpu.serve.fleet.router import Router

    import socket

    router = Router()
    listener = protocol.open_listener("127.0.0.1", 0)
    port = listener.getsockname()[1]
    stop = threading.Event()
    t = threading.Thread(
        target=router.serve, args=(listener, stop.is_set), daemon=True
    )
    t.start()
    try:
        with socket.create_connection(("127.0.0.1", port), timeout=10) as c:
            protocol.send_frame(
                c, protocol.ctrl_request("generate", tokens=[1, 2])
            )
            resp = json.loads(protocol.recv_frame(c))
        assert resp["error"] == "no_routable_replicas"
        assert "retry_after_ms" in resp
    finally:
        stop.set()
        t.join(5)


# --------------------------------------------------- telemetry satellites


def test_generation_telemetry_and_run_report(f32, tmp_path):
    """gen.*/lm.tokens records land schema-valid in the per-rank sink;
    run_report's lm section surfaces tokens/s + decode p50/p99; the
    decode tiles carry a MEMORY-bound roofline verdict (the acceptance
    criterion the future-kernel work targets)."""
    import sys

    from distribuuuu_tpu import telemetry
    from distribuuuu_tpu.telemetry import schema

    from distribuuuu_tpu.telemetry import costmodel

    cfg.OUT_DIR = str(tmp_path)
    telemetry.setup_from_cfg(cfg, rank=0)
    # a label is captured once a PROCESS: whatever ran in this worker before
    # (another file's generation under a sink) must not have used these up
    costmodel.reset()
    try:
        model = _tiny_gpt(seq_len=32)
        params = _params(model)
        eng = _engine(model, params, emit_interval_s=0.0).start()
        rng = np.random.default_rng(6)
        for i in range(3):
            eng.submit(
                rng.integers(0, 256, (3 + i,)).astype(np.int32),
                max_new_tokens=3,
            ).result(timeout=120.0)
        eng.drain()
    finally:
        from distribuuuu_tpu.telemetry import spans

        spans.close_telemetry()
    recs = []
    for p in glob.glob(str(tmp_path / "telemetry" / "rank*.jsonl")):
        with open(p) as f:
            recs.extend(json.loads(line) for line in f)
    kinds = {r.get("kind") for r in recs}
    assert {"gen.admit", "gen.prefill", "gen.decode", "gen.retire",
            "lm.tokens"} <= kinds
    for r in recs:
        schema.validate_record(r)
    roof = {
        r["label"]: r["bound"] for r in recs
        if r.get("kind") == "cost.roofline"
        and r["label"].startswith("gen_decode")
    }
    assert roof and all(b == "memory" for b in roof.values())
    # run_report lm section
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    sys.path.insert(0, tools)
    try:
        import run_report

        rep = run_report.build_report(str(tmp_path))
    finally:
        sys.path.remove(tools)
    lm = rep["lm"]
    assert lm["retires"] == 3 and lm["admits"] == 3
    assert lm["tokens_per_s"] is not None and lm["new_tokens"] == 9
    assert lm["decode"]["count"] > 0 and lm["decode"]["p99_ms"] > 0


def test_bench_index_has_lm_series(chip_bench_root):
    """BENCH_r08.json is committed and indexed with series names that
    cannot clobber the img/s throughput reference (the PR 8 lesson)."""
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tools = os.path.join(repo, "tools")
    sys.path.insert(0, tools)
    try:
        import bench_history
        import run_report

        index = bench_history.build_index(repo)
        root, values, copy_in = chip_bench_root
        copy_in("BENCH_r08.json")
        beside_chip_records = bench_history.build_index(root)
    finally:
        sys.path.remove(tools)
    assert "lm_train_tokens_per_s" in index["series"]
    assert "lm_generate_tokens_per_s" in index["series"]
    assert any(k.startswith("lm_decode_step_ms_") for k in index["series"])
    # regeneration pin: the committed index matches a fresh build
    with open(os.path.join(repo, "BENCH_INDEX.json")) as f:
        committed = json.load(f)
    assert committed["series"] == index["series"], (
        "BENCH_INDEX.json is stale — rerun tools/bench_history.py"
    )
    # the lm series must NOT land on the throughput gate's reference
    assert "lm_train_tokens_per_s" in beside_chip_records["series"]
    gated = run_report.comparable_metrics(beside_chip_records)
    assert gated["img_per_sec"] == values[-1]  # still the resnet50 record


@pytest.mark.slow
def test_lm_fleet_streams_with_zero_drops(tmp_path):
    """ISSUE 12 acceptance, end to end: REAL gpt replicas behind the REAL
    fleet router; concurrent clients with ragged budgets all stream to
    completion (zero dropped requests), every stream's token frames match
    its done frame, and every client of the same request gets the same
    tokens no matter which replica served it — greedy requests via
    deterministic decode, SAMPLED requests via the ctrl-frame key replay
    contract (ISSUE 17 acceptance: same temperature/top_p/seed ⇒
    bit-identical streams across real replicas)."""
    import socket

    from distribuuuu_tpu.lm import service as lm_service
    from distribuuuu_tpu.serve import protocol
    from distribuuuu_tpu.serve.fleet import FleetService

    config.reset_cfg()
    cfg.MODEL.ARCH = "gpt_nano"
    cfg.MODEL.NUM_CLASSES = 320
    cfg.DEVICE.COMPUTE_DTYPE = "float32"
    cfg.DEVICE.PLATFORM = "cpu"
    cfg.LM.SEQ_LEN = 32
    cfg.GENERATE.PROMPT_LEN = 8
    cfg.GENERATE.MAX_NEW_TOKENS = 6
    cfg.GENERATE.BATCH_TILES = [2]
    cfg.GENERATE.CACHE_TILES = [16]
    cfg.RNG_SEED = 0
    cfg.OUT_DIR = str(tmp_path)
    cfg.SERVE.FLEET.AUTOSCALE = False
    cfg.SERVE.FLEET.HEALTH_PERIOD_S = 0.5
    cfg_path = os.path.join(str(tmp_path), "fleet_cfg.yaml")
    with open(cfg_path, "w") as f:
        f.write(cfg.dump())

    svc = FleetService(cfg, 2, cfg_path=cfg_path, out_dir=str(tmp_path))
    try:
        svc.start(wait=True)
        assert svc.router.n_routable() == 2, (
            f"replicas failed warm-up; see fleet/replica*.log in {tmp_path}"
        )
        listener = protocol.open_listener("127.0.0.1", 0)
        port = listener.getsockname()[1]
        stop = threading.Event()
        server = threading.Thread(
            target=svc.serve, args=(listener, stop.is_set),
            kwargs=dict(poll_s=0.05), daemon=True,
        )
        server.start()
        rng = np.random.default_rng(12)
        # 5 request groups x 2 identical clients: groups 0-1 greedy,
        # groups 2-4 sampled with a per-group ctrl-frame key — the pair
        # may land on different replicas and must still match
        gprompts = [
            rng.integers(0, 256, (2 + g,)).astype(int).tolist()
            for g in range(5)
        ]
        results: dict[int, dict] = {}
        errors: list = []

        def client(i):
            g = i % 5
            kw = {} if g < 2 else dict(
                temperature=0.9, top_p=0.9, seed=50 + g,
            )
            try:
                frames = list(lm_service.generate_request(
                    "127.0.0.1", port, tokens=gprompts[g],
                    max_new_tokens=3 + g, timeout=120.0, **kw,
                ))
                toks = [
                    f["token"] for f in frames if f.get("stream") == "token"
                ]
                results[i] = {"frames": frames, "tokens": toks}
            except Exception as e:  # noqa: BLE001
                errors.append((i, e))

        n_clients = 10
        threads = [
            threading.Thread(target=client, args=(i,))
            for i in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(180.0)
        stop.set()
        server.join(5)
        assert not errors, errors
        assert len(results) == n_clients  # zero dropped requests
        by_group: dict[int, list] = {}
        for i, r in results.items():
            done = r["frames"][-1]
            assert done["stream"] == "done" and "error" not in done
            assert done["tokens"] == r["tokens"]
            assert len(r["tokens"]) >= 1
            by_group.setdefault(i % 5, []).append(tuple(r["tokens"]))
        for g, outs in by_group.items():
            # determinism across replicas: an identical request — greedy
            # (g < 2) or sampled with the same ctrl-frame key (g >= 2) —
            # streams the same tokens, whichever replica decoded it
            assert len(outs) == 2 and len(set(outs)) == 1, (g, outs)
        assert int(svc.router.registry.counter("fleet.streams").value) \
            == n_clients
    finally:
        svc.shutdown()


def test_tokenizer_roundtrip_and_identity():
    from distribuuuu_tpu.lm.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    ids = tok.encode("hello, wörld")
    assert ids.dtype == np.uint16 and int(ids.max()) < 256
    assert tok.decode(ids) == "hello, wörld"
    assert tok.decode(list(ids) + [tok.eos_id, 300]) == "hello, wörld"
    ident = tok.identity()
    assert ident == {
        "tokenizer": "byte-v1", "vocab_size": 320, "eos_id": 256,
    }
    assert tok.vocab_size % 64 == 0  # even TP sharding of the vocab dim
