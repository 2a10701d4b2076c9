"""The quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user would call —
``train_net.main``, ``serve_net.main`` (image batch mode and the LM
socket), ``lm.service.engine_from_cfg`` — at the full width of the
shipped configs, on however many chips are attached, in ONE process (a
chip belongs to one process at a time), and checks what comes out by the
repo's own means:

1. device        the backend is the TPU and its kind is in the peak table
2. train         ResNet-50 224² bf16, batch 128 per chip, dummy input: one
                 epoch of steps, the end-of-epoch eval, a committed
                 checkpoint; the fused optimizer update and the eval conv
                 epilogue ran as the kernels ``auto`` promises
3. image_serve   ``serve_net.py --batch-input`` on that checkpoint; served
                 logits agree with the eval forward on the same inputs
4. lm_serve      ``serve_net.py --cfg config/gpt_nano.yaml`` over its
                 socket: concurrent generate requests stream and retire;
                 greedy tokens equal the ``KERNELS.DECODE_ATTN xla``
                 engine's (or part only where the reference model ties),
                 kernel-vs-reference logits within the pinned tolerance
5. flash         flash attention fwd+bwd, causal: at [2, 4, 1024, 64] float32
                 against the dense reference, and at the shape the
                 benchmark's LM cell runs ([4, 16, 4096, 128] bfloat16,
                 default blocks) against ``blockwise_attention``, so that a
                 Mosaic that stops taking the kernel fails here first

Every phase is reported by name with pass/fail and wall seconds split into
compile and run; a ``kernel.fallback`` record anywhere is a failure. It
prints no rate and nothing under a metric name. Without a TPU it exits
non-zero and says so — it never carries on on the CPU. The last line of
standard output is one JSON object naming the device as jax reports it.

    python chip_smoke.py            # on the chip (through the chip tool)

``run_phases(TOY, ...)`` is the same code at toy size (resnet18, 32²) for a
CPU dry run — tests/test_chip_smoke.py.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import shutil
import signal
import sys
import threading
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_ROOT = os.path.join(ROOT, "chiprun_out")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What is cut for a dry run; FULL cuts nothing but depth of training
    (one epoch of dummy input)."""

    image_cfg: str
    lm_cfg: str
    im_size: int
    image_opts: tuple  # KEY VALUE overrides shared by train and image_serve
    train_opts: tuple
    lm_opts: tuple
    n_images: int
    max_new: int
    flash_shape: tuple
    flash_blk: int
    flash_cell_shape: tuple  # the benchmark's LM cell: bf16, default blocks


FULL = Sizes(
    image_cfg="config/resnet50.yaml",
    lm_cfg="config/gpt_nano.yaml",
    im_size=224,
    image_opts=(),
    train_opts=("TRAIN.BATCH_SIZE", "128"),
    lm_opts=(),
    n_images=5,
    max_new=24,
    flash_shape=(2, 4, 1024, 64),
    flash_blk=512,
    flash_cell_shape=(4, 16, 4096, 128),  # olmoe_1b_7b.train_seq4096
)

TOY = Sizes(
    image_cfg="config/resnet18.yaml",
    lm_cfg="config/gpt_nano.yaml",
    im_size=32,
    image_opts=(
        "MODEL.NUM_CLASSES", "10", "TRAIN.IM_SIZE", "32",
        "TEST.IM_SIZE", "32", "DEVICE.COMPUTE_DTYPE", "float32",
        "SERVE.MAX_BATCH", "2",
    ),
    train_opts=("TRAIN.BATCH_SIZE", "2", "TEST.BATCH_SIZE", "16"),
    lm_opts=(
        "LM.SEQ_LEN", "32", "GENERATE.PROMPT_LEN", "8",
        "GENERATE.MAX_NEW_TOKENS", "6", "GENERATE.BATCH_TILES", "[2]",
        "GENERATE.CACHE_TILES", "[16]", "KERNELS.DECODE_BLOCK", "16",
    ),
    n_images=3,
    max_new=6,
    flash_shape=(1, 2, 256, 32),
    flash_blk=128,
    flash_cell_shape=(1, 2, 256, 32),
)


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def _check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _records(out_dir: str) -> list[dict]:
    """Every telemetry record under ``out_dir`` (all ranks, all phases)."""
    recs = []
    pattern = os.path.join(out_dir, "**", "telemetry", "rank*.jsonl")
    for path in sorted(glob.glob(pattern, recursive=True)):
        with open(path) as f:
            recs += [json.loads(ln) for ln in f if ln.strip()]
    return recs


def _selected(recs: list[dict], op: str) -> set[tuple[str, str]]:
    return {
        (r["impl"], r["requested"]) for r in recs
        if r["kind"] == "kernel.select" and r["op"] == op
    }


def _check_selected(recs: list[dict], op: str, impl: str) -> None:
    _check(
        (impl, "auto") in _selected(recs, op),
        f"kernel.select shows {op} as {sorted(_selected(recs, op))}, "
        f"expected impl={impl} requested=auto",
    )


def _auto_impl() -> str:
    """What ``KERNELS.* auto`` resolves to on the live backend."""
    import jax

    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _fresh_cfg():
    import distribuuuu_tpu.config as config
    from distribuuuu_tpu.ops import pallas as kernel_tier

    config.reset_cfg()
    # each phase's sink gets its own kernel.select records
    kernel_tier.reset_selection()


# ------------------------------------------------------------------ phases


def phase_device() -> dict:
    import importlib.metadata as md

    import jax

    from distribuuuu_tpu.telemetry import costmodel

    dev = jax.devices()
    info = {
        "platform": dev[0].platform,
        "kind": dev[0].device_kind,
        "count": len(dev),
    }
    versions = {p: md.version(p) for p in ("jax", "jaxlib", "libtpu")}
    print(
        f"chip_smoke: platform={info['platform']} "
        f"device_kind={info['kind']} devices={info['count']} "
        + " ".join(f"{k}={v}" for k, v in versions.items()),
        flush=True,
    )
    _check(
        info["kind"] in costmodel.DEVICE_PEAKS,
        f"device_kind {info['kind']!r} is not in telemetry/costmodel."
        f"DEVICE_PEAKS {sorted(costmodel.DEVICE_PEAKS)} — add its peaks "
        "with their source before measuring on it",
    )
    return {"device": info, "versions": versions}


def phase_train(sizes: Sizes, out_dir: str, extra: tuple = ()) -> dict:
    """``train_net.main`` for one epoch; returns the committed checkpoint.
    ``extra`` overrides run a variant of the phase (the four-chip ZeRO-3
    check: ``extra=("MESH.ZERO", "3")``)."""
    import jax
    import numpy as np

    import train_net
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.parallel import mesh as mesh_lib
    from distribuuuu_tpu.parallel import sharding as sharding_lib
    from distribuuuu_tpu.utils import checkpoint as ckpt

    _fresh_cfg()
    run_dir = os.path.join(out_dir, "train")
    argv = [
        "train_net.py", "--cfg", os.path.join(ROOT, sizes.image_cfg),
        "MODEL.DUMMY_INPUT", "True", "OPTIM.MAX_EPOCH", "1",
        "RNG_SEED", "0", "OUT_DIR", run_dir,
        *sizes.image_opts, *sizes.train_opts, *extra,
    ]
    saved, sys.argv = sys.argv, argv
    try:
        train_net.main()
    finally:
        sys.argv = saved

    recs = _records(run_dir)
    train_loss = [r["loss"] for r in recs if r["kind"] == "train"]
    eval_loss = [r["loss"] for r in recs if r["kind"] == "eval"]
    _check(train_loss, "no train record: the epoch's steps did not run")
    _check(eval_loss, "no eval record: the end-of-epoch eval did not run")
    _check(
        bool(np.isfinite(train_loss + eval_loss).all()),
        f"non-finite loss: train {train_loss} eval {eval_loss}",
    )
    # the eval step's conv epilogue has no shard_map of its own: on a dp
    # mesh of several chips ``auto`` means xla there (ops/pallas), and
    # the kernel is checked in the one-device serving engine instead
    eval_impl = _auto_impl() if jax.device_count() == 1 else "xla"
    _check_selected(recs, "opt_update", _auto_impl())
    _check_selected(recs, "conv_epilogue", eval_impl)
    if jax.default_backend() != "cpu":
        # the HBM ledger must survive the compile cache being on
        # (telemetry/costmodel._memory_analysis)
        _check(
            any(r["kind"] == "cost.memory" for r in recs),
            "no cost.memory record: the HBM ledger was dropped",
        )
        # every local device holds state: the epoch's memstats sample
        used = {
            r["device"] for r in recs
            if r["kind"] == "memstats" and r["peak_bytes_in_use"] > 0
        }
        _check(
            used == set(range(jax.local_device_count())),
            f"memstats shows bytes on devices {sorted(used)} of "
            f"{jax.local_device_count()}",
        )
    last = ckpt.find_last_valid_checkpoint()  # cfg still holds OUT_DIR

    # placement, through the constructors train_model used: parameters,
    # optimizer state and a batch each have shards on every device
    mesh = mesh_lib.mesh_from_cfg(cfg)
    model = trainer.build_model_from_cfg()
    state = trainer.create_train_state(
        model, jax.random.key(0), mesh, cfg.TRAIN.IM_SIZE,
        layout=trainer._state_layout(model, mesh, cfg.TRAIN.IM_SIZE),
    )
    n = cfg.TRAIN.BATCH_SIZE * jax.local_device_count()
    batch = sharding_lib.shard_batch(mesh, {
        "image": np.zeros((n, cfg.TRAIN.IM_SIZE, cfg.TRAIN.IM_SIZE, 3),
                          np.uint8),
        "label": np.zeros((n,), np.int32),
        "mask": np.ones((n,), np.float32),
    })
    placed = {
        # ZeRO: leaves whose local shard is smaller than the leaf
        "param_leaves_sharded": sum(
            leaf.addressable_shards[0].data.size < leaf.size
            for leaf in jax.tree.leaves(state.params)
        ),
    }
    for name, tree in (("params", state.params),
                       ("opt_state", state.opt_state), ("batch", batch)):
        devs = {
            s.device.id for leaf in jax.tree.leaves(tree)
            for s in leaf.addressable_shards
        }
        placed[name] = len(devs)
        _check(
            len(devs) == len(jax.devices()),
            f"{name} shards sit on {len(devs)} device(s) {sorted(devs)}, "
            f"not on all {len(jax.devices())}",
        )
    return {
        "checkpoint": last,
        "mesh": {k: int(v) for k, v in mesh.shape.items()},
        "steps_logged": len(train_loss),
        "shard_devices": placed,
    }


def phase_image_serve(sizes: Sizes, out_dir: str, checkpoint: str) -> dict:
    """``serve_net.py --batch-input`` against the eval forward."""
    import jax
    import numpy as np

    import serve_net
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.data.transforms import normalize_in_graph
    from distribuuuu_tpu.parallel import mesh as mesh_lib

    _fresh_cfg()
    run_dir = os.path.join(out_dir, "image_serve")
    os.makedirs(run_dir, exist_ok=True)
    rng = np.random.default_rng(0)
    images = rng.integers(
        0, 256, (sizes.n_images, sizes.im_size, sizes.im_size, 3),
        dtype=np.uint8)
    in_path = os.path.join(run_dir, "images.npy")
    out_path = os.path.join(run_dir, "logits.npy")
    np.save(in_path, images)
    serve_net.main([
        "--cfg", os.path.join(ROOT, sizes.image_cfg),
        "--batch-input", in_path, "--batch-output", out_path,
        "MODEL.WEIGHTS", checkpoint, "RNG_SEED", "0", "OUT_DIR", run_dir,
        *sizes.image_opts,
    ])
    served = np.load(out_path)
    _check_selected(_records(run_dir), "conv_epilogue", _auto_impl())

    # the eval forward validate()/test_model() computes, same weights
    cfg.defrost()
    mesh = mesh_lib.build_mesh(data=1, model=1, seq=1, pipe=1,
                               devices=jax.devices()[:1])
    model = trainer.build_model_from_cfg()
    state = trainer.create_train_state(
        model, jax.random.key(0), mesh, cfg.TRAIN.IM_SIZE
    )
    state = trainer._with_restored_weights(state, checkpoint, model)
    variables = {"params": state.params, "batch_stats": state.batch_stats}
    ref = np.asarray(jax.jit(
        lambda v, x: model.apply(v, normalize_in_graph(x), train=False)
    )(variables, images), np.float32)
    _check(served.shape == ref.shape, f"served {served.shape} vs {ref.shape}")
    _check(bool(np.isfinite(served).all()), "served logits are not finite")
    # bf16 activations: the bucket-padded and the natural batch shape may
    # round differently; fp32 (the dry run) agrees to summation order
    tol = (0.05 if cfg.DEVICE.COMPUTE_DTYPE == "bfloat16" else 1e-4) * max(
        1.0, float(np.abs(ref).max()))
    diff = float(np.abs(served - ref).max())
    _check(diff <= tol, f"served vs eval logits differ by {diff} > {tol}")
    return {"requests": int(served.shape[0]), "max_abs_diff": diff,
            "tolerance": tol}


def _generate_clients(port: int, prompts, max_new: int, box: dict,
                      server_gone: threading.Event) -> None:
    """Client side of the LM phase: wait for the listener, stream every
    prompt concurrently, then deliver the SIGTERM that drains the server
    (serve_net.main returns) — also when a request fails, so the phase
    ends. No signal is sent unless the listener answered: serve_net
    installs its drain handler before it listens."""
    import socket

    from distribuuuu_tpu.lm import service as lm_service

    while not server_gone.is_set():
        try:
            socket.create_connection(("127.0.0.1", port), 1.0).close()
            break
        except OSError:
            time.sleep(0.25)
    else:
        return  # serve_net.main raised before listening; it reports
    try:
        results = [None] * len(prompts)

        def one(i):
            frames = list(lm_service.generate_request(
                "127.0.0.1", port, tokens=prompts[i], max_new_tokens=max_new,
            ))
            results[i] = frames

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        box["frames"] = results
    except Exception as e:  # noqa: BLE001 — reported by the phase
        box["error"] = e
    finally:
        os.kill(os.getpid(), signal.SIGTERM)


def _divergence_margins(engine, prompts, toks_a, toks_b) -> list:
    """Where two greedy streams part, how far apart the reference model
    (teacher-forced ``models/gpt.GPT`` on the shared prefix) puts the two
    candidate tokens: ``[(prompt#, position, |logit_a - logit_b|)]``.
    With random weights and bf16 the top two logits often tie; a flip at a
    tie is rounding, a flip across a wide margin is a wrong kernel."""
    import jax.numpy as jnp

    out = []
    for n, (prompt, a, b) in enumerate(zip(prompts, toks_a, toks_b)):
        i = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)
        if i is None:
            continue
        prefix = jnp.asarray([list(prompt) + list(a[:i])], jnp.int32)
        logits = engine.model.apply(engine._variables, prefix, train=False)
        last = logits[0, -1].astype(jnp.float32)
        out.append((n, i, abs(float(last[a[i]]) - float(last[b[i]]))))
    return out


def phase_lm_serve(sizes: Sizes, out_dir: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import distribuuuu_tpu.config as config
    import serve_net
    from distribuuuu_tpu import telemetry, trainer
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.lm import generate as gen
    from distribuuuu_tpu.lm import service as lm_service
    from distribuuuu_tpu.ops import pallas as kernel_tier
    from distribuuuu_tpu.serve import admission
    from distribuuuu_tpu.serve.fleet.pool import free_port
    from distribuuuu_tpu.utils import preempt

    _fresh_cfg()
    run_dir = os.path.join(out_dir, "lm_serve")
    prompts = [[72, 101, 108, 108, 111], [1, 2, 3], [200, 100, 50, 25, 12, 6]]
    port = free_port()
    box: dict = {}
    server_gone = threading.Event()
    client = threading.Thread(
        target=_generate_clients,
        args=(port, prompts, sizes.max_new, box, server_gone),
    )
    client.start()
    common = ["RNG_SEED", "0", "GENERATE.EOS_ID", "-1", *sizes.lm_opts]
    try:
        serve_net.main([
            "--cfg", os.path.join(ROOT, sizes.lm_cfg),
            "SERVE.PORT", str(port), "OUT_DIR", run_dir, *common,
        ])
    finally:
        server_gone.set()
        client.join(timeout=900)
        admission.reset_drain()
        preempt.reset()  # the train phase's handler chains on SIGTERM
    if "error" in box:
        raise box["error"]
    streamed = []
    for frames in box["frames"]:
        _check(frames and frames[-1].get("stream") == "done",
               f"a request did not retire: {frames and frames[-1]}")
        toks = [f["token"] for f in frames[:-1]]
        _check(toks == frames[-1]["tokens"] and len(toks) == sizes.max_new,
               f"streamed {len(toks)} tokens, done frame says "
               f"{frames[-1]['n']}, asked {sizes.max_new}")
        streamed.append(toks)
    impl = _auto_impl()
    _check_selected(_records(run_dir), "decode_attn", impl)

    # the same engine with the dense reference decode step. Greedy tokens
    # must be equal — or part only where the reference model itself ties
    # (which batch tile a step ran in depends on arrival timing, tiles
    # round differently in bf16, and random weights tie often)
    config.reset_cfg()
    config.merge_from_file(os.path.join(ROOT, sizes.lm_cfg))
    cfg.merge_from_list(
        [*common, "OUT_DIR", run_dir, "KERNELS.DECODE_ATTN", "xla"])
    telemetry.setup_from_cfg(cfg)
    with lm_service.engine_from_cfg() as ref_engine:
        futs = [ref_engine.submit(p, sizes.max_new) for p in prompts]
        ref_tokens = [f.result(timeout=600) for f in futs]
    margins = _divergence_margins(ref_engine, prompts, streamed, ref_tokens)
    _check(all(m <= 0.1 for _, _, m in margins),
           f"greedy tokens differ beyond a tie (prompt, position, margin) "
           f"{margins}: kernel {streamed} vs xla {ref_tokens}")

    # kernel-vs-reference logits through the real decoder at the largest
    # decode tile (tests/test_pallas_kernels.py pins 0.05 in interpret
    # mode; here the kernel is whatever auto selects on this backend)
    model = trainer.build_model_from_cfg()
    variables = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False
    )
    dec = gen.decoder_for(model)
    b, c = ref_engine.batch_tiles[-1], ref_engine.cache_tiles[-1]
    hh, dh = model.num_heads, model.dim // model.num_heads
    rng = np.random.default_rng(9)
    cache = {
        k: jnp.asarray(
            rng.standard_normal((model.depth, b, hh, c, dh)) * 0.3,
            model.dtype)
        for k in ("k", "v")
    }
    lens = jnp.asarray(rng.integers(0, c - 1, size=b), jnp.int32)
    toks = jnp.asarray(rng.integers(0, 256, size=(b, 1)), jnp.int32)

    def logits(knob):
        cfg.KERNELS.DECODE_ATTN = knob
        with kernel_tier.single_device_program():  # as the engine traces it
            return np.asarray(
                jax.jit(lambda v, t, l, k: dec.apply(v, t, l, k)[0])(
                    variables, toks, lens, cache), np.float32)

    lo_ref = logits("xla")
    lo_kernel = logits("auto" if impl == "pallas" else "pallas")
    _check(
        any(i == "pallas" for i, _ in _selected(_records(run_dir),
                                                "decode_attn")),
        "the logits comparison did not run the kernel",
    )
    diff = float(np.abs(lo_ref - lo_kernel).max())
    _check(diff <= 0.05, f"kernel vs reference logits differ by {diff}")
    return {"requests": len(prompts), "tokens_each": sizes.max_new,
            "n_executables": ref_engine.n_compiles,
            "tokens_equal_xla": not margins, "tie_divergences": margins,
            "logits_max_abs_diff": diff}


def phase_flash(sizes: Sizes, out_dir: str) -> dict:
    """Flash attention fwd+bwd, compiled (interpret only off the TPU),
    against ops/attention's dense softmax under a causal bias."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distribuuuu_tpu import telemetry
    from distribuuuu_tpu.ops import attention as dense
    from distribuuuu_tpu.ops import flash_attention as fa
    from distribuuuu_tpu.ops import pallas as kernel_tier

    _fresh_cfg()
    telemetry.setup_telemetry(os.path.join(out_dir, "flash", "telemetry"))
    b, h, L, d = sizes.flash_shape
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.standard_normal((b, h, L, d)), jnp.float32)
               for _ in range(3))
    w = jnp.asarray(rng.standard_normal((d,)), jnp.float32)
    scale = d ** -0.5
    bias = jnp.where(jnp.tril(jnp.ones((L, L), bool)), 0.0, -1e30)[None, None]
    interpret = kernel_tier.interpret_mode()

    def flash(q, k, v):
        return fa.flash_attention(
            q, k, v, causal=True, interpret=interpret,
            blk_q=sizes.flash_blk, blk_k=sizes.flash_blk)

    def ref(q, k, v):
        return dense.mhsa_2d(q, k, v, bias, scale)

    def run(fn):
        loss = lambda q, k, v: jnp.sum(fn(q, k, v) * w)  # noqa: E731
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)

    def compare(prefix, got, want, tol, out):
        (lf, gf), (lr, gr) = got, want
        out[f"{prefix}loss_rel_diff"] = abs(float(lf) - float(lr)) / abs(float(lr))
        _check(out[f"{prefix}loss_rel_diff"] <= tol, f"flash loss off by {out}")
        for name, a, r in zip(("dq", "dk", "dv"), gf, gr):
            diff = float(jnp.abs(a.astype(jnp.float32) - r.astype(jnp.float32)).max())
            out[f"{prefix}{name}_max_abs_diff"] = diff
            _check(bool(jnp.isfinite(a).all()), f"flash {prefix}{name} is not finite")
            _check(diff <= tol * max(1.0, float(jnp.abs(r).max())),
                   f"flash {prefix}{name} differs from its reference by {diff}")
        return out

    # fp32 inputs: the MXU's default precision rounds both sides' matmul
    # operands to bf16 on the TPU, in different places
    out = compare("", run(flash), run(ref), 5e-5 if interpret else 0.05, {})

    # the LM cell's shape and dtype, the blocks the model gets; the dense
    # reference would hold 4 GB of scores there, the blockwise scan does not
    from distribuuuu_tpu.ops.ring_attention import blockwise_attention

    b, h, L, d = sizes.flash_cell_shape
    q, k, v = (jnp.asarray(rng.standard_normal((b, h, L, d)), jnp.bfloat16)
               for _ in range(3))
    w = jnp.asarray(rng.standard_normal((d,)), jnp.float32)
    return compare(
        "cell_",
        run(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, interpret=interpret).astype(jnp.float32)),
        run(lambda q, k, v: blockwise_attention(
            q, k, v, causal=True).astype(jnp.float32)),
        0.05, out,
    )


# ------------------------------------------------------------------ driver


def _counters() -> dict:
    from distribuuuu_tpu.telemetry import registry

    snap = registry.get_registry().snapshot()["counters"]
    return {k: snap.get(k, 0.0) for k in (
        "jit.compiles", "jit.compile_s", "jit.cache_hits",
        "jit.cache_misses", "jit.cache_hit_s")}


def run_phases(sizes: Sizes, out_dir: str) -> dict:
    """Run every phase in turn; a failed phase is recorded and the next
    one still runs (one chip call shows every failure). Returns the
    summary; ``summary["ok"]`` is False if any phase failed."""
    from distribuuuu_tpu import telemetry
    from distribuuuu_tpu.telemetry import runtime

    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    runtime.install_compile_listener()
    # compile counters only move while a sink is open; phases that set up
    # their own (train_net, serve_net) re-point it under their OUT_DIR
    telemetry.setup_telemetry(os.path.join(out_dir, "device", "telemetry"))
    summary: dict = {"ok": True, "phases": {}}

    def phase(name, fn, *args):
        before, t0 = _counters(), time.perf_counter()
        row: dict = {"ok": False}
        try:
            row.update(fn(*args) or {})
            row["ok"] = True
        except Exception as e:  # noqa: BLE001 — the phase's verdict
            traceback.print_exc()
            row["error"] = f"{type(e).__name__}: {e}"
            summary["ok"] = False
        wall = time.perf_counter() - t0
        after = _counters()
        delta = {k: after[k] - before[k] for k in after}
        compile_s = delta["jit.compile_s"] + delta["jit.cache_hit_s"]
        row.update(
            wall_s=round(wall, 2), compile_s=round(compile_s, 2),
            run_s=round(wall - compile_s, 2),
            compiles=int(delta["jit.compiles"]),
            cache_hits=int(delta["jit.cache_hits"]),
            cache_misses=int(delta["jit.cache_misses"]),
        )
        summary["phases"][name] = row
        print(
            f"chip_smoke: phase {name} {'PASS' if row['ok'] else 'FAIL'} "
            f"wall_s={row['wall_s']} compile_s={row['compile_s']} "
            f"run_s={row['run_s']} compiles={row['compiles']} "
            f"cache_hits={row['cache_hits']}"
            + (f" error={row['error']}" if not row["ok"] else ""),
            flush=True,
        )
        return row

    dev = phase("device", phase_device)
    summary.update({k: dev[k] for k in ("device", "versions") if k in dev})
    trained = phase("train", phase_train, sizes, out_dir)
    if trained["ok"]:
        phase("image_serve", phase_image_serve, sizes, out_dir,
              trained["checkpoint"])
    else:
        summary["phases"]["image_serve"] = {
            "ok": False, "error": "skipped: no checkpoint from train"}
        print("chip_smoke: phase image_serve FAIL (no checkpoint)", flush=True)
    phase("lm_serve", phase_lm_serve, sizes, out_dir)
    phase("flash", phase_flash, sizes, out_dir)
    telemetry.close_telemetry()

    fallbacks = [r for r in _records(out_dir) if r["kind"] == "kernel.fallback"]
    if fallbacks:
        summary["ok"] = False
        summary["kernel_fallbacks"] = fallbacks
        print(f"chip_smoke: FAIL kernel.fallback records: {fallbacks}",
              flush=True)
    return summary


def main() -> int:
    try:
        import jax

        backend = jax.default_backend()
    except Exception as e:  # noqa: BLE001 — no backend at all
        print(f"chip_smoke: jax could not initialize a backend: {e}",
              file=sys.stderr)
        return 2
    if backend != "tpu":
        print(
            f"chip_smoke: no TPU — jax.default_backend() is {backend!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}). "
            "This check runs on the chip only (chiprun -- python "
            "chip_smoke.py); it does not fall back to the CPU.",
            file=sys.stderr,
        )
        return 2

    from distribuuuu_tpu.asyncplane import compile_cache
    from distribuuuu_tpu.config import cfg

    cache_dir = compile_cache.setup_from_cfg(cfg)
    print(f"chip_smoke: compile cache {cache_dir}", flush=True)
    out_dir = os.path.join(OUT_ROOT, "chip_smoke")
    summary = run_phases(FULL, out_dir)
    summary["compile_cache"] = cache_dir
    # the chip tool brings back 64 MiB: keep logs and telemetry, not weights
    shutil.rmtree(os.path.join(out_dir, "train", "checkpoints"),
                  ignore_errors=True)
    with open(os.path.join(OUT_ROOT, "chip_smoke_runs.jsonl"), "a") as f:
        f.write(json.dumps(summary) + "\n")
    if not summary["ok"]:
        failed = [n for n, r in summary["phases"].items() if not r["ok"]]
        print(f"chip_smoke: FAILED phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
