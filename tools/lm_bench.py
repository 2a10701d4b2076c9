"""LM workload-plane bench → BENCH_r08.json (ISSUE 12 satellite).

Two halves, matching the plane's two phases:

  * **train** — pack a deterministic synthetic byte corpus into token
    shards (tools/make_token_shards.py machinery), lower ``gpt_nano``
    through the REAL partition lowering, and time steady-state train
    steps → tokens/s (= sequences/s × LM.SEQ_LEN, counted after a warmup
    step so compile time never pollutes the rate);
  * **generate** — build the KV-cache engine (lm/generate.py), time each
    prefill prompt tile and each (batch, cache-len) decode tile at
    steady state, and run a short continuous-batching burst for the
    end-to-end tokens/s.

Series names are indexed by tools/bench_history.py ``index_lm`` and
deliberately avoid the ``images_per_sec`` throughput-gate patterns (the
PR 8 clobbering lesson): CPU token rates are trajectory data, never the
img/s regression reference.

    python tools/lm_bench.py [--json-out BENCH_r08.json] [--steps 8]
        [--seq-len 64] [--arch gpt_nano]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import _path  # noqa: F401  — repo root onto sys.path for the package import


def _synthetic_corpus(n_docs: int = 24, words: int = 300):
    import numpy as np

    rng = np.random.default_rng(7)
    for _ in range(n_docs):
        yield " ".join(
            f"tok{rng.integers(0, 200)}" for _ in range(words)
        ).encode()


def bench_train(arch: str, seq_len: int, steps: int, batch: int) -> dict:
    import jax
    import numpy as np

    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.data import construct_train_loader
    from distribuuuu_tpu.data.shards import tokens as token_shards
    from distribuuuu_tpu.parallel import mesh as mesh_lib
    from distribuuuu_tpu.parallel.partition import lowering, topology
    from distribuuuu_tpu.utils.optim import construct_optimizer

    td = tempfile.mkdtemp(prefix="lm_bench_")
    split_dir = os.path.join(td, "train")
    token_shards.write_token_shards(
        split_dir,
        token_shards.pack_token_stream(_synthetic_corpus(), seq_len),
        seq_len, source="lm_bench synthetic",
    )
    cfg.MODEL.ARCH = arch
    cfg.MODEL.NUM_CLASSES = 320
    cfg.DATA.FORMAT = "tokens"
    cfg.LM.SEQ_LEN = seq_len
    cfg.TRAIN.DATASET = td
    cfg.TRAIN.BATCH_SIZE = batch
    topo = topology.from_cfg(cfg)
    mesh = mesh_lib.mesh_from_cfg(cfg)
    model = trainer.build_model_from_cfg(topo)
    low = lowering.lower(
        model, construct_optimizer(), topk=5, mesh=mesh, topology=topo,
        im_size=cfg.TRAIN.IM_SIZE,
    )
    state = low.init_state(jax.random.key(0), cfg.TRAIN.IM_SIZE)
    loader = construct_train_loader()
    loader.set_epoch(0)
    it = iter(loader)
    seqs_per_step = None
    t_steady = None
    n_timed = 0
    for i in range(steps + 1):
        try:
            hb = next(it)
        except StopIteration:
            loader.set_epoch(i)
            it = iter(loader)
            hb = next(it)
        seqs_per_step = int(np.shape(hb["image"])[0])
        db = low.put_batch(hb)
        state, metrics = low.train_step(state, db)
        if i == 0:
            jax.block_until_ready(state.params)  # warmup: compile excluded
            t_steady = time.perf_counter()
        else:
            n_timed += 1
    jax.block_until_ready(state.params)
    wall = time.perf_counter() - t_steady
    step_s = wall / max(1, n_timed)
    return {
        "arch": arch,
        "seq_len": seq_len,
        "batch_seqs": seqs_per_step,
        "steps_timed": n_timed,
        "step_ms": round(step_s * 1e3, 3),
        "seqs_per_s": round(seqs_per_step / step_s, 3),
        "tokens_per_s": round(seqs_per_step * seq_len / step_s, 1),
        "final_loss": round(float(metrics["loss"]), 4),
    }


def bench_generate(arch: str, seq_len: int) -> dict:
    import jax
    import numpy as np

    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu import models
    from distribuuuu_tpu.lm.generate import GenerateEngine
    from distribuuuu_tpu.models.layers import resolve_dtype

    cfg.GENERATE.PROMPT_LEN = min(32, seq_len // 2)
    cfg.GENERATE.MAX_NEW_TOKENS = min(32, seq_len // 2)
    cfg.GENERATE.BATCH_TILES = [1, 2, 4]
    cfg.GENERATE.CACHE_TILES = [seq_len]
    model = models.build_model(
        arch, num_classes=320, seq_len=seq_len,
        dtype=resolve_dtype(cfg.DEVICE.COMPUTE_DTYPE),
    )
    params = model.init(
        jax.random.key(0), jax.numpy.zeros((1, 8), "int32"), train=False
    )["params"]
    t0 = time.perf_counter()
    eng = GenerateEngine(model, {"params": params})
    compile_s = time.perf_counter() - t0
    rng = np.random.default_rng(3)

    # per-tile steady-state latencies, measured directly on the AOT
    # executables (warm call first, then the timed mean)
    prefill_rows = []
    for p, ex in sorted(eng._prefill_exec.items()):
        toks = jax.numpy.asarray(rng.integers(0, 256, (1, p)), "int32")
        jax.block_until_ready(ex(eng._variables, toks))
        t0 = time.perf_counter()
        n = 5
        for _ in range(n):
            out = ex(eng._variables, toks)
        jax.block_until_ready(out)
        prefill_rows.append({
            "tile": p,
            "ms": round((time.perf_counter() - t0) / n * 1e3, 3),
        })
    decode_rows = []
    for (b, c), ex in sorted(eng._decode_exec.items()):
        cache = eng._zero_cache(b, c)
        toks = jax.numpy.asarray(rng.integers(0, 256, (b,)), "int32")
        lens = jax.numpy.asarray(rng.integers(1, c // 2, (b,)), "int32")
        logits, cache = ex(eng._variables, toks, lens, cache)
        jax.block_until_ready(logits)
        t0 = time.perf_counter()
        n = 10
        for _ in range(n):
            logits, cache = ex(eng._variables, toks, lens, cache)
        jax.block_until_ready(logits)
        ms = (time.perf_counter() - t0) / n * 1e3
        decode_rows.append({
            "tile_b": b, "tile_c": c, "ms_per_step": round(ms, 3),
            "tokens_per_s_at_tile": round(b / (ms / 1e3), 1),
        })

    # end-to-end continuous-batching burst through the scheduler
    eng.start()
    t0 = time.perf_counter()
    streams = [
        eng.submit(
            rng.integers(0, 256, (4 + 3 * (i % 5),)).astype(np.int32),
            max_new_tokens=cfg.GENERATE.MAX_NEW_TOKENS,
        )
        for i in range(12)
    ]
    total = sum(len(s.result(timeout=300.0)) for s in streams)
    burst_s = time.perf_counter() - t0
    stats = eng.stats()
    eng.drain()
    return {
        "arch": arch,
        "compile_s": round(compile_s, 2),
        "n_executables": eng.n_compiles,
        "prefill": prefill_rows,
        "decode": decode_rows,
        "burst_requests": len(streams),
        "burst_new_tokens": total,
        "tokens_per_s": round(total / burst_s, 2),
        "decode_p50_ms": stats["decode_p50_ms"],
        "decode_p99_ms": stats["decode_p99_ms"],
        "prefill_p50_ms": stats["prefill_p50_ms"],
        "prefill_p99_ms": stats["prefill_p99_ms"],
    }


def _bigram_perm(vocab: int = 64, seed: int = 5):
    """A fixed random successor map over a small token alphabet: token
    ``t`` is always followed by ``perm[t]``. Draft and target both learn
    this SAME next-token function, which is what makes speculative
    acceptance observable in a short bench — the corpus is predictable
    by construction, so agreement measures training, not luck."""
    import numpy as np

    rng = np.random.default_rng(seed)
    return rng.permutation(vocab)


def _bigram_batch(perm, batch: int, seq_len: int, rng):
    import numpy as np

    starts = rng.integers(0, len(perm), (batch,))
    out = np.empty((batch, seq_len + 1), np.int32)
    out[:, 0] = starts
    for j in range(seq_len):
        out[:, j + 1] = perm[out[:, j]]
    return out


def _train_lm_params(model, seq_len: int, steps: int, batch: int,
                     perm, init_seed: int = 0, lr: float = 3e-3):
    """Teach one decoder the bigram corpus with a plain jit'd AdamW loop
    — the bench wants agreeing weights, not a train-plane measurement,
    so the partition lowering stays out of the timing path."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    params = model.init(
        jax.random.key(init_seed), jnp.zeros((1, 8), "int32"), train=False
    )["params"]
    tx = optax.adamw(lr)
    opt = tx.init(params)

    def loss_fn(p, tokens, targets):
        logits = model.apply({"params": p}, tokens, train=False)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), targets
        ).mean()

    @jax.jit
    def step(p, o, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(p, tokens, targets)
        updates, o = tx.update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    rng = np.random.default_rng(11)
    loss = None
    for _ in range(steps):
        seqs = _bigram_batch(perm, batch, seq_len, rng)
        params, opt, loss = step(params, opt, seqs[:, :-1], seqs[:, 1:])
    return params, round(float(loss), 4)


def bench_speculative(arch: str, draft_arch: str, seq_len: int,
                      ks=(2, 4, 8), train_steps: int = 150) -> dict:
    """A/B target-only vs draft-K speculative decode (ISSUE 17
    satellite): same trained weights, same prompts, greedy — so the
    emitted streams are REQUIRED identical and only the wall clock and
    the acceptance counters may differ."""
    import jax
    import numpy as np

    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu import models
    from distribuuuu_tpu.lm.generate import GenerateEngine
    from distribuuuu_tpu.models.layers import resolve_dtype

    max_k = max(ks)
    # long generations on a short prompt: 48 new tokens per request so
    # the A/B measures the DECODE loop, not the 12 prefills both modes
    # pay identically (at 24 new tokens admission was ~half the wall and
    # drowned the round-level win)
    cfg.GENERATE.PROMPT_LEN = 8
    cfg.GENERATE.MAX_NEW_TOKENS = 48
    cfg.GENERATE.BATCH_TILES = [4]
    cfg.GENERATE.CACHE_TILES = [8 + 48 + max_k]
    dtype = resolve_dtype(cfg.DEVICE.COMPUTE_DTYPE)
    # the target must be the EXPENSIVE side of the A/B for speculation's
    # economics to exist: route in EVERY block (the zoo default is every
    # 2nd) with 16 experts (default 8), which on the dense reference MoE
    # path computes all E experts per token — a ~10x per-step cost over
    # the draft, disclosed in the artifact as target_kwargs. Real
    # deployments run 20-100x target/draft ratios; this is the smallest
    # gap that still shows the economics on a single CPU core.
    target_kwargs = (
        {"moe_every": 1, "moe_experts": 16} if arch.endswith("_moe")
        else {}
    )
    target = models.build_model(
        arch, num_classes=320, seq_len=seq_len, dtype=dtype,
        **target_kwargs,
    )
    draft = models.build_model(
        draft_arch, num_classes=320, seq_len=seq_len, dtype=dtype
    )
    perm = _bigram_perm()
    # target trains at batch 4 (vs the draft's 16): the E=16 dense-MoE
    # step is ~8x the draft's, and the bigram task is easy enough that
    # 150 small-batch steps land argmax agreement with the draft above
    # 99% — which is what acceptance (and the bench budget) needs
    tvars, t_loss = _train_lm_params(
        target, seq_len, train_steps, 4, perm, init_seed=0
    )
    dvars, d_loss = _train_lm_params(
        draft, seq_len, train_steps, 16, perm, init_seed=1
    )
    rng = np.random.default_rng(17)
    prompts = [
        _bigram_batch(perm, 1, 7, rng)[0].astype(np.int32)  # 8 tokens
        for _ in range(12)
    ]

    def burst(eng) -> tuple:
        eng.start()
        t0 = time.perf_counter()
        streams = [eng.submit(p, max_new_tokens=48) for p in prompts]
        toks = [s.result(timeout=300.0) for s in streams]
        wall = time.perf_counter() - t0
        stats = eng.stats()
        eng.drain()
        return toks, wall, stats

    base_eng = GenerateEngine(target, {"params": tvars})
    base_toks, base_wall, base_stats = burst(base_eng)
    total = sum(len(t) for t in base_toks)
    rows = [{
        "k": 0,
        "tokens_per_s": round(total / base_wall, 2),
        "round_p50_ms": base_stats["decode_p50_ms"],
        "new_tokens": total,
    }]
    for k in ks:
        eng = GenerateEngine(
            target, {"params": tvars},
            draft_model=draft, draft_variables={"params": dvars}, spec_k=k,
        )
        toks, wall, stats = burst(eng)
        rows.append({
            "k": k,
            "tokens_per_s": round(sum(len(t) for t in toks) / wall, 2),
            "round_p50_ms": stats["decode_p50_ms"],
            "new_tokens": sum(len(t) for t in toks),
            "rounds": stats["spec_rounds"],
            "proposed": stats["spec_proposed"],
            "accepted": stats["spec_accepted"],
            "bonus": stats["spec_bonus"],
            "acceptance_ratio": round(
                stats["spec_accepted"] / max(1, stats["spec_proposed"]), 4
            ),
            "accepted_per_round": round(
                (stats["spec_accepted"] + stats["spec_bonus"])
                / max(1, stats["spec_rounds"]), 3
            ),
            "identical_streams": toks == base_toks,
        })
    best = max(rows[1:], key=lambda r: r["tokens_per_s"])
    return {
        "target": arch,
        "target_kwargs": target_kwargs,
        "draft": draft_arch,
        "train_steps": train_steps,
        "target_loss": t_loss,
        "draft_loss": d_loss,
        "rows": rows,
        "speedup_best": round(
            best["tokens_per_s"] / rows[0]["tokens_per_s"], 3
        ),
        "note": (
            "single-core CPU container: draft and target share the one "
            "core, so draft steps serialize against verify instead of "
            "hiding behind it — the measured speedup is a floor for any "
            "parallel backend, and holds only because the bigram corpus "
            "keeps acceptance near K"
        ),
    }


def bench_long_context_train(arch: str, pack_len: int, steps: int,
                             batch: int) -> dict:
    """The dp×sp train half of ``--long-context`` (ISSUE 19a): the same
    partition-lowered train step as ``bench_train``, but on a dp2·sp4
    mesh (the ``config/gpt_nano_sp.yaml`` stanza shape) at a LONG pack
    length — token batches sharded (data, seq), every block's attention
    through the causal ring. Needs the 8-virtual-device CPU mesh
    (XLA_FLAGS=--xla_force_host_platform_device_count=8)."""
    import jax

    from distribuuuu_tpu.config import cfg

    if jax.device_count() < 8:
        raise SystemExit(
            f"--long-context trains a dp2·sp4 stanza and needs 8 devices "
            f"(have {jax.device_count()}) — run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )
    cfg.MESH.DATA = 2
    cfg.MESH.SEQ = 4
    cfg.MESH.MODEL = 1
    cfg.MESH.PIPE = 1
    row = bench_train(arch, pack_len, steps, batch)
    row["mesh"] = "dp2.sp4"
    return row


def bench_chunked_prefill_ab(arch: str, prompt_tokens: int, chunk: int,
                             max_new: int = 16, n_prompts: int = 2) -> dict:
    """Chunked-vs-whole prefill A/B at a long prompt (ISSUE 19c): the
    SAME weights and prompts through two engines — one with the classic
    whole-prompt bucket ladder up to ``prompt_tokens`` (the 4k-bucket
    cost the chunked path exists to avoid), one streaming the prompt
    into its KV page in ``chunk``-token AOT calls. Greedy continuations
    are REQUIRED identical; the wall clocks and compile ledgers are the
    measurement."""
    import jax
    import numpy as np

    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu import models
    from distribuuuu_tpu.lm.generate import GenerateEngine
    from distribuuuu_tpu.models.layers import resolve_dtype

    # f32: at bf16 on the 8-virtual-device CPU mesh the two prefill
    # paths can argmax-flip a near-tie token under different intra-op
    # reduction orders — the identity claim is about the math, so the
    # A/B measures it at the dtype where greedy identity is exact
    # (tier-1 pins the same at toy sizes: tests/test_lm_chunk_prefill.py)
    cfg.DEVICE.COMPUTE_DTYPE = "float32"
    cache = -(-(prompt_tokens + max_new) // chunk) * chunk
    model = models.build_model(
        arch, num_classes=320, seq_len=cache,
        dtype=resolve_dtype(cfg.DEVICE.COMPUTE_DTYPE),
    )
    params = model.init(
        jax.random.key(0), jax.numpy.zeros((1, 8), "int32"), train=False
    )["params"]
    rng = np.random.default_rng(13)
    prompts = [
        rng.integers(0, 256, (prompt_tokens,)).astype(np.int32)
        for _ in range(n_prompts)
    ]

    def run(engine_kwargs: dict) -> dict:
        t0 = time.perf_counter()
        eng = GenerateEngine(
            model, {"params": params}, max_new_tokens=max_new,
            batch_tiles=[1], cache_tiles=[cache], **engine_kwargs,
        )
        compile_s = time.perf_counter() - t0
        eng.start()
        walls, toks = [], []
        for p in prompts:
            t1 = time.perf_counter()
            toks.append(eng.submit(p, max_new_tokens=max_new).result(
                timeout=1800.0
            ))
            walls.append(time.perf_counter() - t1)
        stats = eng.stats()
        eng.drain()
        return {
            "compile_s": round(compile_s, 2),
            "n_executables": eng.n_compiles,
            "request_ms": [round(w * 1e3, 1) for w in walls],
            "prefill_p50_ms": stats["prefill_p50_ms"],
            "tokens": toks,
            "stats": stats,
        }

    whole = run({"prompt_len": prompt_tokens})
    chunked = run({"prompt_len": chunk, "chunk_prefill": chunk})
    identical = whole["tokens"] == chunked["tokens"]
    doc = {
        "arch": arch,
        "dtype": "float32",
        "prompt_tokens": prompt_tokens,
        "max_new": max_new,
        "cache_tile": cache,
        "chunk": chunk,
        "chunk_calls": chunked["stats"].get("chunk_calls", 0),
        "identical_tokens": identical,
        "whole": {k: whole[k] for k in
                  ("compile_s", "n_executables", "request_ms",
                   "prefill_p50_ms")},
        "chunked": {k: chunked[k] for k in
                    ("compile_s", "n_executables", "request_ms",
                     "prefill_p50_ms")},
    }
    doc["prefill_ratio_chunked_vs_whole"] = round(
        chunked["prefill_p50_ms"] / max(1e-9, whole["prefill_p50_ms"]), 3
    )
    doc["compile_ratio_chunked_vs_whole"] = round(
        chunked["compile_s"] / max(1e-9, whole["compile_s"]), 3
    )
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json-out", default=None,
                    help="destination (default {repo}/BENCH_r08.json)")
    ap.add_argument("--arch", default="gpt_nano")
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--speculative", action="store_true",
                    help="A/B target-only vs draft-K speculative decode "
                         "→ BENCH_r11.json (lm_spec_* series)")
    ap.add_argument("--draft-arch", default="gpt_nano")
    ap.add_argument("--target-arch", default="gpt_nano_moe")
    ap.add_argument("--train-steps", type=int, default=150)
    ap.add_argument("--long-context", action="store_true",
                    help="dp2·sp4 train step + chunked-vs-whole prefill "
                         "A/B at --pack-len → BENCH_r12.json "
                         "(lm_longctx_* series; needs XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8)")
    ap.add_argument("--pack-len", type=int, default=4096)
    ap.add_argument("--chunk", type=int, default=256)
    args = ap.parse_args(argv)

    import jax

    from distribuuuu_tpu import config

    config.reset_cfg()
    from distribuuuu_tpu.config import cfg

    from distribuuuu_tpu.asyncplane import compile_cache

    compile_cache.setup_from_cfg(cfg)  # on the chip: warm across processes
    cfg.TELEMETRY.ENABLED = False  # bench times raw dispatch
    platform = jax.devices()[0].platform
    if args.long_context:
        ab = bench_chunked_prefill_ab(
            args.arch, args.pack_len, args.chunk,
        )
        print(f"# prefill A/B @ {args.pack_len} tokens: whole p50 "
              f"{ab['whole']['prefill_p50_ms']} ms "
              f"({ab['whole']['n_executables']} executables, "
              f"{ab['whole']['compile_s']}s compile) vs chunked p50 "
              f"{ab['chunked']['prefill_p50_ms']} ms in "
              f"{ab['chunk_calls'] // len(ab['whole']['request_ms'])} "
              f"x{args.chunk} chunks "
              f"({ab['chunked']['n_executables']} executables, "
              f"{ab['chunked']['compile_s']}s compile); identical="
              f"{ab['identical_tokens']}", flush=True)
        config.reset_cfg()
        cfg.TELEMETRY.ENABLED = False
        train = bench_long_context_train(
            args.arch, args.pack_len, args.steps, args.batch
        )
        print(f"# dp2.sp4 train @ pack_len {args.pack_len}: "
              f"{train['tokens_per_s']} tokens/s "
              f"({train['step_ms']} ms/step x {train['batch_seqs']} seqs)",
              flush=True)
        doc = {
            "schema": 1,
            "generated_by": "tools/lm_bench.py --long-context",
            "platform": platform,
            "cpu_count": os.cpu_count(),
            "note": (
                "CPU container numbers — long-context trajectory data "
                "for the LM plane, never an img/s reference (series "
                "names avoid the throughput-gate patterns)"
            ),
            "lm_long_context": {"train": train, "prefill_ab": ab},
        }
        out = args.json_out or os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "BENCH_r12.json",
        )
        with open(out, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"# wrote {out}")
        return 0
    if args.speculative:
        spec = bench_speculative(
            args.target_arch, args.draft_arch, args.seq_len,
            train_steps=args.train_steps,
        )
        for r in spec["rows"]:
            print(f"# k={r['k']}: {r['tokens_per_s']} tokens/s"
                  + (f", acceptance {r['acceptance_ratio']}, "
                     f"{r['accepted_per_round']} tok/round"
                     if r["k"] else " (target-only baseline)"),
                  flush=True)
        doc = {
            "schema": 1,
            "generated_by": "tools/lm_bench.py --speculative",
            "platform": platform,
            "cpu_count": os.cpu_count(),
            "note": (
                "CPU container numbers — trajectory data for the LM "
                "plane, never an img/s reference (series names avoid "
                "the throughput-gate patterns)"
            ),
            "lm_speculative": spec,
        }
        out = args.json_out or os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "BENCH_r11.json",
        )
        with open(out, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"# wrote {out}")
        return 0
    train = bench_train(args.arch, args.seq_len, args.steps, args.batch)
    print(f"# train: {train['tokens_per_s']} tokens/s "
          f"({train['step_ms']} ms/step x {train['batch_seqs']} seqs)",
          flush=True)
    gen = bench_generate(args.arch, args.seq_len)
    print(f"# generate: {gen['tokens_per_s']} tokens/s e2e, decode p50 "
          f"{gen['decode_p50_ms']} ms", flush=True)
    doc = {
        "schema": 1,
        "generated_by": "tools/lm_bench.py",
        "platform": platform,
        "note": (
            "CPU container numbers (1 physical core) — trajectory data "
            "for the LM plane, never an img/s reference (series names "
            "avoid the throughput-gate patterns)"
        ),
        "lm": {"train": train, "generate": gen},
    }
    out = args.json_out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_r08.json",
    )
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# wrote {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
