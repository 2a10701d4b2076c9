"""The kernel tier's A/B matrix (ISSUE 13): per-kernel XLA-vs-Pallas
roofline ledger → ``BENCH_r09.json`` (indexed by tools/bench_history.py
as ``kernel_*`` series — names deliberately outside the img/s gate
patterns, the PR 8 lesson).

Two layers of evidence per kernel:

* **micro A/B** — the isolated region program, both arms compiled and
  run: XLA-measured flops/bytes from ``cost_analysis`` of the lowered
  reference, the kernel's DMA-model bytes (exactly what its BlockSpecs
  transfer on TPU), wall-time medians over interleaved rounds, and the
  max|Δ| exactness check.
* **step A/B** — the kernel in its real program (efficientnet_b0
  train/eval step, the gen_decode tile): the whole-step bytes with the
  replaced region's XLA bytes swapped for the kernel's DMA bytes, i.e.
  ``step_bytes_kernel = step_bytes_xla − region_bytes_xla +
  region_bytes_kernel`` — transparent ledger arithmetic, every term
  recorded.

**The recorded caveat** (cost_analysis vs custom calls): on TPU,
``cost_analysis`` cannot price the inside of a Pallas custom call at
all; on this CPU container the interpret-mode lowering is visible but
measures the *interpreter* (grid loops and block copies), not Mosaic's
DMA schedule. The pallas arm's byte counts here are therefore the
kernel's block-transfer model — the traffic ``pallas_call`` issues by
construction — with the interpret-measured number recorded alongside
for honesty, never used for the roofline verdict.

    python tools/kernel_bench.py --out BENCH_r09.json [--quick]
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import time

import _path  # noqa: F401  (repo root onto sys.path)

BENCH_SCHEMA = 1

CAVEAT = (
    "pallas-arm bytes are the kernel's BlockSpec DMA model (what the "
    "call transfers on TPU): XLA cost_analysis cannot see inside a "
    "custom call, and on CPU the interpret lowering measures the "
    "interpreter, not the kernel (recorded as bytes_interpret_measured "
    "for honesty). xla-arm numbers are cost_analysis of the lowered "
    "reference program."
)


def _med_ms(fn, args, rounds: int, iters: int) -> float:
    import jax

    fn(*args)  # warm/compile
    samples = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        samples.append((time.perf_counter() - t0) / iters * 1e3)
    return round(statistics.median(samples), 3)


def _cost(fn, args) -> dict:
    from distribuuuu_tpu.telemetry import costmodel

    c = costmodel.normalize_cost(fn.lower(*args).cost_analysis())
    return c or {}


def _arm(flops, bytes_, peaks) -> dict:
    out = {
        "flops": flops,
        "bytes_accessed": bytes_,
        "intensity": round(flops / bytes_, 4) if flops and bytes_ else None,
    }
    if out["intensity"] and peaks:
        ridge = peaks["flops"] / peaks["bytes_per_s"]
        out["bound"] = "compute" if out["intensity"] >= ridge else "memory"
    return out


def bench_opt_update(kind: str, n: int, rounds: int, iters: int,
                     peaks) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import distribuuuu_tpu.config as config
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.ops.pallas import opt_update as ou
    from distribuuuu_tpu.utils.optim import construct_optimizer

    config.reset_cfg()
    cfg.defrost()
    cfg.OPTIM.OPTIMIZER = kind
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.standard_normal(n), jnp.float32)}
    grads = {"w": jnp.asarray(rng.standard_normal(n) * 0.1, jnp.float32)}
    opt = construct_optimizer()
    st = opt.init(params)

    @jax.jit
    def xla_step(p, g, s):
        u, s2 = opt.update(g, s, p)
        return optax.apply_updates(p, u), s2

    @jax.jit
    def pallas_step(p, g, s):
        return ou.fused_optimizer_update(
            p, g, s, kind=kind, wd=float(cfg.OPTIM.WEIGHT_DECAY),
            mom=float(cfg.OPTIM.MOMENTUM),
            nesterov=bool(cfg.OPTIM.NESTEROV),
            b1=float(cfg.OPTIM.BETA1), b2=float(cfg.OPTIM.BETA2),
            eps=1e-8, interpret=True,
        )

    cx = _cost(xla_step, (params, grads, st))
    cp = _cost(pallas_step, (params, grads, st))
    p1, s1 = xla_step(params, grads, st)
    p2, s2 = pallas_step(params, grads, st)
    diff = float(jnp.abs(p1["w"] - p2["w"]).max())
    moments = 2 if kind == "adamw" else 1
    model_bytes = ou.leaf_pass_bytes(params, kind)
    xla_arm = _arm(cx.get("flops"), cx.get("bytes_accessed"), peaks)
    pallas_arm = _arm(cx.get("flops"), model_bytes, peaks)
    pallas_arm["bytes_interpret_measured"] = cp.get("bytes_accessed")
    pallas_arm["bytes_model"] = model_bytes
    return {
        "shape": f"{n} fp32 params, {moments} moment tree(s)",
        "xla": {**xla_arm, "wall_ms": _med_ms(
            xla_step, (params, grads, st), rounds, iters)},
        "pallas": {**pallas_arm, "wall_ms": _med_ms(
            pallas_step, (params, grads, st), rounds, iters)},
        "max_abs_diff": diff,
        "bit_exact": diff == 0.0,
        "bytes_ratio_xla_over_pallas": round(
            cx["bytes_accessed"] / model_bytes, 2
        ) if cx.get("bytes_accessed") else None,
    }


def bench_conv_epilogue(rounds: int, iters: int, peaks) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distribuuuu_tpu.ops.pallas import conv_epilogue as ce

    # efficientnet_b0 head-ish shape: the widest pointwise chain
    B, H, W, cin, cout = 8, 7, 7, 320, 1280
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((B, H, W, cin)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 1, cin, cout)) * 0.05,
                    jnp.float32)
    mean = jnp.asarray(rng.standard_normal(cout) * 0.1, jnp.float32)
    var = jnp.asarray(rng.random(cout) + 0.5, jnp.float32)
    scale = jnp.asarray(rng.standard_normal(cout) * 0.2 + 1.0, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(cout) * 0.1, jnp.float32)
    inv = jax.lax.rsqrt(var + 1e-3) * scale
    a, c = inv, bias - mean * inv

    @jax.jit
    def xla_chain(x):
        o = jax.lax.conv_general_dilated(
            x, k.astype(jnp.bfloat16), (1, 1), [(0, 0), (0, 0)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        y = (o.astype(jnp.float32) - mean) * inv + bias
        return jax.nn.silu(y).astype(jnp.bfloat16)

    @jax.jit
    def pallas_chain(x):
        return ce.conv1x1_bn_act(
            x, k.astype(jnp.bfloat16), a, c, "silu", interpret=True
        )

    cx = _cost(xla_chain, (x,))
    cp = _cost(pallas_chain, (x,))
    r1, r2 = xla_chain(x), pallas_chain(x)
    diff = float(jnp.abs(
        r1.astype(jnp.float32) - r2.astype(jnp.float32)
    ).max())
    model_bytes = ce.pass_bytes(B * H * W, cin, cout, jnp.bfloat16,
                                jnp.bfloat16)
    xla_arm = _arm(cx.get("flops"), cx.get("bytes_accessed"), peaks)
    pallas_arm = _arm(cx.get("flops"), model_bytes, peaks)
    pallas_arm["bytes_interpret_measured"] = cp.get("bytes_accessed")
    pallas_arm["bytes_model"] = model_bytes
    return {
        "shape": f"[{B},{H},{W},{cin}]->[{cout}] 1x1 conv+BN+silu (bf16)",
        "xla": {**xla_arm, "wall_ms": _med_ms(xla_chain, (x,), rounds,
                                              iters)},
        "pallas": {**pallas_arm, "wall_ms": _med_ms(pallas_chain, (x,),
                                                    rounds, iters)},
        "max_abs_diff": diff,
        "tolerance": 0.0625,  # bf16 output rounding (fused keeps fp32 acc)
        "bytes_ratio_xla_over_pallas": round(
            cx["bytes_accessed"] / model_bytes, 2
        ) if cx.get("bytes_accessed") else None,
    }


def bench_decode_attn(rounds: int, iters: int, peaks) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distribuuuu_tpu.ops.pallas import decode_attn as da

    B, H, C, D = 4, 6, 256, 64
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
    ck = jnp.asarray(rng.standard_normal((B, H, C, D)), jnp.bfloat16)
    cv = jnp.asarray(rng.standard_normal((B, H, C, D)), jnp.bfloat16)
    lens = jnp.asarray(rng.integers(0, C - 1, (B,)), jnp.int32)
    sc = D ** -0.5

    @jax.jit
    def xla_dense(q, ck, cv, lens):
        s = jnp.einsum("bhd,bhcd->bhc", q.astype(jnp.float32),
                       ck.astype(jnp.float32)) * sc
        vis = jnp.arange(C)[None, None, :] <= lens[:, None, None]
        s = jnp.where(vis, s, jnp.float32(-1e30))
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhc,bhcd->bhd", w, cv.astype(jnp.float32))

    @jax.jit
    def pallas_fused(q, ck, cv, lens):
        return da.decode_attention(q, ck, cv, lens, scale=sc,
                                   interpret=True)

    cx = _cost(xla_dense, (q, ck, cv, lens))
    cp = _cost(pallas_fused, (q, ck, cv, lens))
    o1 = xla_dense(q, ck, cv, lens)
    o2 = pallas_fused(q, ck, cv, lens)
    diff = float(jnp.abs(o1 - o2).max())
    model_bytes = da.pass_bytes(B, H, C, D, jnp.bfloat16)
    xla_arm = _arm(cx.get("flops"), cx.get("bytes_accessed"), peaks)
    pallas_arm = _arm(cx.get("flops"), model_bytes, peaks)
    pallas_arm["bytes_interpret_measured"] = cp.get("bytes_accessed")
    pallas_arm["bytes_model"] = model_bytes
    return {
        "shape": f"q[{B},{H},{D}] vs cache[{B},{H},{C},{D}] bf16, ragged",
        "xla": {**xla_arm, "wall_ms": _med_ms(
            xla_dense, (q, ck, cv, lens), rounds, iters)},
        "pallas": {**pallas_arm, "wall_ms": _med_ms(
            pallas_fused, (q, ck, cv, lens), rounds, iters)},
        "max_abs_diff": diff,
        "tolerance": 1e-5,  # fp32 online-softmax summation order
        "bytes_ratio_xla_over_pallas": round(
            cx["bytes_accessed"] / model_bytes, 2
        ) if cx.get("bytes_accessed") else None,
    }


# ------------------------------------------------- in-context step ledgers



MOE_ROWS_SHAPES = {  # T tokens a step; k = 4 of 64 experts, 8 held, d 2048
    "glm_4_7_flash.train_seq8192": 8192,
    "lfm2_24b_a2b.train_seq8192": 16384,
}
MOE_ROWS_SHARES = (0.125, 0.25, 1.0)


def bench_moe_rows(rounds: int, iters: int, shapes=None,
                   shares=MOE_ROWS_SHARES, d: int = 2048) -> dict:
    """The held mixtures' row movements ALONE, XLA's gathers against the
    row movers (``ops/pallas/moe_rows.py``), at the two cells' shapes and
    three shares of the (token, slot) rows on held experts: ``take`` (token
    -> sorted buffer), ``combine`` (buffer -> token, weighted, summed), and
    both with their backward (the four movements of a mixture's step, no
    expert between them), in ms and in ns a (token, slot) row of the
    buffer's bound; beside them the movers' parts. Off the TPU: a toy
    shape through the interpreter, which rehearses the path and times
    nothing worth keeping."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distribuuuu_tpu.ops import moe as moe_ops
    from distribuuuu_tpu.ops.pallas import moe_gmm, moe_rows

    on_chip = jax.default_backend() == "tpu"
    interpret = not on_chip
    if shapes is None:
        shapes = MOE_ROWS_SHAPES if on_chip else {"toy": 512}
    if on_chip:  # a call is under a millisecond: amortise its dispatch
        iters = max(iters, 20)
    k, held, total, tm = 4, 8, 64, moe_gmm.ROW_TILE if on_chip else 128
    dtype = jnp.bfloat16
    table = {}
    for cell, T in shapes.items():
        for share in shares:
            rng = np.random.default_rng(T + int(share * 1000))
            present = rng.random((T, k)) < share
            indices = jnp.asarray(np.where(
                present, rng.integers(0, held, (T, k)),
                rng.integers(held, total, (T, k))), jnp.int32)
            w = jnp.asarray(rng.random((T, k)), jnp.float32)
            lay = jax.jit(lambda i, w: moe_ops._sorted_layout(
                i, held, 0, total, tm, w))(indices, w)
            height = lay.src.shape[0]
            x = jnp.asarray(rng.standard_normal((T, d)), dtype)
            y = jnp.asarray(rng.standard_normal((height, d)), dtype)
            g = jnp.asarray(rng.standard_normal((T, d)), dtype)
            live = int(lay.n_live[0]) * tm

            def arms(fn):  # XLA's gathers, and the movers with their tables
                return {name: jax.jit(lambda *a, lay, mover=mover: fn(
                    *a, lay, moe_ops._mover_tables(lay, k) if mover else None))
                    for name, mover in (("xla", False), ("movers", True))}

            def both(x, w, lay, tables):  # the four movements of a step
                out, vjp = jax.vjp(lambda x, w: moe_ops._rows_out(
                    moe_ops._rows_in(x, lay, k, tables, interpret), w, lay,
                    tables, interpret), x, w)
                return (out, *vjp(g))

            timed = {
                "take": (arms(lambda x, lay, tables: moe_ops._rows_in(
                    x, lay, k, tables, interpret)), (x,)),
                "combine": (arms(lambda y, w, lay, tables: moe_ops._rows_out(
                    y, w, lay, tables, interpret)), (y, w)),
                "take_combine_and_back": (arms(both), (x, w)),
            }
            row = {"tokens": T, "rows_bound": T * k, "buffer_rows": height,
                   "live_rows": live, "share": share}
            for name, (fns, args) in timed.items():
                fns = {arm: functools.partial(fn, lay=lay) for arm, fn in fns.items()}
                outs = {arm: fn(*args) for arm, fn in fns.items()}
                worst = 0.0
                for a, b in zip(jax.tree.leaves(outs["xla"]),
                                jax.tree.leaves(outs["movers"])):
                    a, b = (np.asarray(t, np.float32) for t in (a, b))
                    if a.shape[0] == height:  # dead tiles: never written
                        a, b = a[:live], b[:live]
                    worst = max(worst, float(np.abs(a - b).max()
                                             / max(np.abs(a).max(), 1e-9)))
                row[name] = {"max_rel_diff": worst}
                for arm, fn in fns.items():
                    ms = _med_ms(fn, args, rounds, iters)
                    row[name][f"{arm}_ms"] = ms
                    row[name][f"{arm}_ns_a_row"] = round(ms * 1e6 / (T * k), 2)
            n_live = lay.n_live
            src2, _, bounds, _, _ = moe_ops._mover_tables(lay, k)
            xw = moe_rows.pack(x, tm=moe_rows.TOKEN_TILE, interpret=interpret)
            yw = moe_rows.pack(y, n_live, tm=tm, interpret=interpret)
            parts = {
                "pack_tokens": (lambda x: moe_rows.pack(
                    x, tm=moe_rows.TOKEN_TILE, interpret=interpret), (x,)),
                "pack_live_tiles": (lambda y, n: moe_rows.pack(
                    y, n, tm=tm, interpret=interpret), (y, n_live)),
                "take_packed": (lambda xw, s, n: moe_rows._take(
                    xw, s // k, n, tokens=T, d=d, dtype=dtype,
                    interpret=interpret), (xw, src2, n_live)),
                "combine_packed": (lambda yw, src, w, bounds: moe_rows._combine(
                    yw, src, w, bounds, experts=held, d=d, dtype=dtype,
                    interpret=interpret), (yw, src2, w, bounds)),
                "gather_of_floats": (lambda w, s: w.reshape(-1)[
                    jnp.minimum(s, T * k - 1)], (w, lay.src)),
            }
            row["parts_ms"] = {name: _med_ms(jax.jit(fn), args, rounds, iters)
                               for name, (fn, args) in parts.items()}
            table[f"{cell}@{share}"] = row
            print(f"moe_rows {cell} share {share}: " + "  ".join(
                f"{name} {row[name]['xla_ms']} -> {row[name]['movers_ms']} ms "
                f"({row[name]['xla_ns_a_row']} -> {row[name]['movers_ns_a_row']} "
                f"ns a row, diff {row[name]['max_rel_diff']:.1e})"
                for name in timed) + f"  parts {row['parts_ms']}", flush=True)
    return {"dtype": "bfloat16", "d": d, "top_k": k, "experts_held": held,
            "experts_total": total, "row_tile": tm, "on_chip": on_chip,
            "cells": table}


SHORT_CONV_SHAPE = (2, 8192, 2048, 3)  # LFM2's cell: N, S, H channels, L taps


def _short_conv_f64(bcu, w, dy):
    """y, dB, dC, du and dw [H, L] of the gated short convolution in float64
    numpy, from the inputs as the device holds them."""
    import numpy as np

    b, c, u = np.split(np.asarray(bcu, np.float64), 3, axis=-1)
    w, dy = np.asarray(w, np.float64), np.asarray(dy, np.float64)
    taps = w.shape[1]

    def moved(x, k):  # x read k positions back (k < 0: ahead), zeros entering
        out = np.zeros_like(x)
        if k >= 0:
            out[:, k:] = x[:, :x.shape[1] - k]
        else:
            out[:, :k] = x[:, -k:]
        return out

    g, e = b * u, dy * c
    conv = sum(w[:, j] * moved(g, taps - 1 - j) for j in range(taps))
    dg = sum(w[:, j] * moved(e, -(taps - 1 - j)) for j in range(taps))
    dw = np.stack([(e * moved(g, taps - 1 - j)).sum((0, 1)) for j in range(taps)], -1)
    return {"y": c * conv, "dB": dg * u, "dC": dy * conv, "du": dg * b, "dw": dw}


def bench_short_conv(rounds: int, iters: int) -> dict:
    """LFM2's gated short convolution ALONE at the cell's shape (``[2, 8192,
    6144]`` bf16, L = 3), the ``jax.numpy`` path against the two Pallas calls
    (``ops/pallas/short_conv.py``): forward and backward in ms and as a share
    of the HBM's bandwidth on the bytes a perfect fusion moves (forward 4 H,
    backward 7 H a token), and each path's y, dB, dC, du and dw a tap against
    float64 numpy on the same bfloat16 inputs (largest error over the
    reference's largest value). Off the TPU: a toy shape through the
    interpreter, which rehearses the path and times nothing worth keeping."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distribuuuu_tpu.ops import short_conv as op
    from distribuuuu_tpu.ops.pallas import short_conv as kernel
    from distribuuuu_tpu.telemetry import costmodel

    on_chip = jax.default_backend() == "tpu"
    N, S, H, taps = SHORT_CONV_SHAPE if on_chip else (1, 64, 256, 3)
    if on_chip:  # a call is under a millisecond: amortise its dispatch
        iters = max(iters, 20)
    dtype = jnp.bfloat16
    keys = jax.random.split(jax.random.key(0), 3)
    bcu = jax.random.normal(keys[0], (N, S, 3 * H)).astype(dtype)
    w = jax.random.normal(keys[1], (H, taps)) * 0.5
    dy = jax.random.normal(keys[2], (N, S, H)).astype(dtype)
    paths = {
        "xla": (jax.jit(op.forward_xla), jax.jit(op.backward_xla)),
        "kernel": (functools.partial(kernel.forward, interpret=not on_chip),
                   functools.partial(kernel.backward, interpret=not on_chip)),
    }
    peaks = costmodel.peaks_for()
    itemsize = jnp.dtype(dtype).itemsize
    moved = {"fwd": 4 * H * itemsize * N * S, "bwd": 7 * H * itemsize * N * S}
    want = _short_conv_f64(bcu, w, dy)
    scale = {name: np.abs(value).max(axis=0 if name == "dw" else None)
             for name, value in want.items()}
    ts = kernel.seq_block(S, H, taps, dtype)
    out = {"shape": [N, S, 3 * H], "taps": taps, "dtype": "bfloat16",
           "on_chip": on_chip, "seq_block": ts,
           "chunk": list(kernel.chunks(ts, H, dtype)), "ideal_bytes": moved}
    for name, (fwd, bwd) in paths.items():
        y, (dbcu, dw) = fwd(bcu, w), bwd(bcu, w, dy)
        db, dc, du = np.split(np.asarray(dbcu, np.float64), 3, axis=-1)
        got = {"y": np.asarray(y, np.float64), "dB": db, "dC": dc, "du": du,
               "dw": np.asarray(dw, np.float64)}
        row = {"max_err_over_max": {
            k: float(np.abs(got[k] - want[k]).max() / scale[k].max())
            for k in ("y", "dB", "dC", "du")}}
        # a tap of the filter's gradient on its own
        row["max_err_over_max"]["dw_by_tap"] = [
            float(np.abs(got["dw"][:, j] - want["dw"][:, j]).max() / scale["dw"][j])
            for j in range(taps)]
        for part, fn, args in (("fwd", fwd, (bcu, w)), ("bwd", bwd, (bcu, w, dy))):
            ms = _med_ms(fn, args, rounds, iters)
            row[f"{part}_ms"] = ms
            if on_chip and peaks:
                row[f"{part}_hbm_share"] = round(
                    moved[part] / peaks["bytes_per_s"] / (ms / 1e3), 4)
        out[name] = row
        print(f"short_conv {name}: " + "  ".join(
            f"{part} {row[f'{part}_ms']} ms ({row.get(f'{part}_hbm_share')})"
            for part in ("fwd", "bwd")) + f"  against float64 {row['max_err_over_max']}",
            flush=True)
    return out


# (B, S, n, rotary): q and k of SDAR's cell (one sequence, a noised and a
# clean copy: 16,384 rows at the positions 0..8191 twice) and of
# Trinity-Mini's (two sequences; a sliding layer has a rotary, the full one
# none); heads of 128
HEAD_PROLOGUE_SHAPES = {
    "sdar_q": (1, 16384, 32, True), "sdar_k": (1, 16384, 4, True),
    "trinity_q": (2, 8192, 32, True), "trinity_k": (2, 8192, 4, True),
    "trinity_q_full": (2, 8192, 32, False), "trinity_k_full": (2, 8192, 4, False),
}
HEAD_PROLOGUE_BLOCKS = (128, 256, 512, 1024)


def _head_prologue_f64(t, scale, dy, positions, heads, eps, theta):
    """y, dt and dscale of the per-head norm and rotary in float64 numpy,
    from the inputs as the device holds them."""
    import numpy as np

    B, S, width = t.shape
    D, half = width // heads, width // heads // 2
    x = np.asarray(t, np.float64).reshape(B, S, heads, D).transpose(0, 2, 1, 3)
    w, g = np.asarray(scale, np.float64), np.asarray(dy, np.float64)
    inv = 1.0 / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps)
    xhat = x * inv
    y = xhat * w
    if theta is not None:
        angles = np.asarray(positions, np.float64)[:, None] * theta ** (
            -np.arange(half, dtype=np.float64) / half)
        cos, sin = (np.concatenate([f(angles)] * 2, -1) for f in (np.cos, np.sin))

        def rotate_half(v, sign):  # [-b, a]; its transpose [b, -a]
            return np.concatenate([-sign * v[..., half:], sign * v[..., :half]], -1)

        y = y * cos + rotate_half(y, 1.0) * sin
        g = g * cos + rotate_half(g * sin, -1.0)
    dscale = (g * xhat).sum((0, 1, 2))
    g = g * w
    dx = inv * (g - xhat * np.mean(g * xhat, -1, keepdims=True))
    return {"y": y, "dt": dx.transpose(0, 2, 1, 3).reshape(B, S, width), "dscale": dscale}


def bench_head_prologue(rounds: int, iters: int) -> dict:
    """A q or k projection's way to the flash kernels ALONE (per-head norm,
    rotary, heads-major layout) at SDAR's and Trinity-Mini's shapes in
    bfloat16, the ``jax.numpy`` lines (``models/lfm2_moe.HeadNorm.xla`` under
    autodiff) against the two Pallas calls (``ops/pallas/head_prologue.py``):
    forward and forward + backward in ms and as a share of the HBM's
    bandwidth on the bytes a perfect fusion moves (forward ``t`` in and the
    heads out; backward ``dy`` and ``t`` in and ``dt`` out), each path's y,
    dt and dscale against float64 numpy on the same inputs (largest error
    over the reference's largest value), and the kernel at every row block
    that fits. Off the TPU: a toy shape through the interpreter, which
    rehearses the path and times nothing worth keeping."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distribuuuu_tpu.models.lfm2_moe import HeadNorm
    from distribuuuu_tpu.ops import head_prologue as op
    from distribuuuu_tpu.ops.pallas import head_prologue as kernel
    from distribuuuu_tpu.telemetry import costmodel

    on_chip = jax.default_backend() == "tpu"
    shapes = HEAD_PROLOGUE_SHAPES if on_chip else {
        "toy_q": (2, 64, 4, True), "toy_k_full": (2, 64, 2, False)}
    if on_chip:  # a call is under a millisecond: amortise its dispatch
        iters = max(iters, 20)
    D, eps, dtype = 128, 1e-6, jnp.bfloat16
    peaks = costmodel.peaks_for()
    itemsize = jnp.dtype(dtype).itemsize
    out = {"head_dim": D, "dtype": "bfloat16", "on_chip": on_chip, "shapes": {}}
    for label, (B, S, n, rotary) in shapes.items():
        theta = 1e6 if rotary else None
        keys = jax.random.split(jax.random.key(n + rotary), 3)
        t = (2.0 * jax.random.normal(keys[0], (B, S, n * D))).astype(dtype)
        scale = 3.0 + 0.5 * jax.random.normal(keys[1], (D,))
        dy = jax.random.normal(keys[2], (B, n, S, D)).astype(dtype)
        positions = jnp.tile(jnp.arange(S // 2, dtype=jnp.int32), 2)

        def both_ways(forward):
            def fwd_bwd(t, scale, dy):
                y, vjp = jax.vjp(forward, t, scale)
                return y, vjp(dy)

            return jax.jit(forward), jax.jit(fwd_bwd)

        def xla(t, scale):
            return HeadNorm.xla(t, scale, positions, n, eps, theta)

        def calls(t, scale):  # the tables are part of the op: XLA makes them
            return op.head_prologue(t, scale, positions, heads=n, eps=eps, theta=theta,
                                    interpret=not on_chip)

        rows = B * S * n * D * itemsize
        moved = {"fwd": 2 * rows, "fwd_bwd": 5 * rows}
        want = _head_prologue_f64(t, scale, dy, positions, n, eps, theta)
        row = {"shape": [B, S, n * D], "rotary": rotary, "ideal_bytes": moved,
               "row_block": kernel.row_block(S, n, D, dtype, rotary)}
        for name, forward in (("xla", xla), ("kernel", calls)):
            fwd, fwd_bwd = both_ways(forward)
            y, (dt, dscale) = fwd_bwd(t, scale, dy)
            got = {"y": y, "dt": dt, "dscale": dscale}
            arm = {"max_err_over_max": {
                k: float(np.abs(np.asarray(got[k], np.float64) - want[k]).max()
                         / np.abs(want[k]).max()) for k in want}}
            for part, fn, args in (("fwd", fwd, (t, scale)),
                                   ("fwd_bwd", fwd_bwd, (t, scale, dy))):
                arm[f"{part}_ms"] = ms = _med_ms(fn, args, rounds, iters)
                if on_chip and peaks:
                    arm[f"{part}_hbm_share"] = round(
                        moved[part] / peaks["bytes_per_s"] / (ms / 1e3), 4)
            row[name] = arm
            print(f"head_prologue {label} {name}: " + "  ".join(
                f"{part} {arm[f'{part}_ms']} ms ({arm.get(f'{part}_hbm_share')})"
                for part in ("fwd", "fwd_bwd"))
                + f"  against float64 {arm['max_err_over_max']}", flush=True)
        row["xla_over_kernel"] = {
            part: round(row["xla"][f"{part}_ms"] / row["kernel"][f"{part}_ms"], 2)
            for part in ("fwd", "fwd_bwd")}
        # the forward call alone at every row block that divides S and fits
        tables = op.rotary_tables(positions, D, theta) if rotary else ()
        row["fwd_ms_by_block"] = {
            str(blk): _med_ms(functools.partial(
                kernel.forward, heads=n, eps=eps, block=blk, interpret=not on_chip),
                (t, scale, *tables), rounds, iters)
            for blk in HEAD_PROLOGUE_BLOCKS
            if S % blk == 0 and kernel._block_bytes(
                blk, n, D, dtype, rotary, False) <= kernel._VMEM_BUDGET}
        print(f"head_prologue {label}: xla over kernel {row['xla_over_kernel']}  "
              f"forward by row block {row['fwd_ms_by_block']}", flush=True)
        out["shapes"][label] = row
    return out


# B, S, H, P, G, N: the Mamba-2 heads Nemotron-3-Super's cell holds
SSD_SHAPE = (1, 8192, 16, 64, 1, 128)


def bench_ssd(rounds: int, iters: int) -> dict:
    """Mamba-2's chunked scan ALONE at the cell's shape (``[1, 8192, 16, 64]``
    bfloat16 on one group of a 128 state, chunks of 128), ``ops/ssd.py``'s
    ``jax.numpy`` body under autodiff against the two Pallas calls
    (``ops/pallas/ssd.py``): forward and forward + backward in ms and as a
    share of the HBM's bandwidth on the bytes the calls' own operands and
    results are (``x``, ``dt``, ``b``, ``c`` in and ``y`` out; backward those,
    ``dy`` and the four gradients), and each path's y, last state and six
    gradients against the body in float32 at full precision on the same
    bfloat16 inputs (largest error over the reference's largest value). Off
    the TPU: two chunks through the interpreter, which rehearses the path and
    times nothing worth keeping."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distribuuuu_tpu.ops import ssd as op
    from distribuuuu_tpu.telemetry import costmodel

    on_chip = jax.default_backend() == "tpu"
    B, S, H, P, G, N = SSD_SHAPE if on_chip else (1, 256, 16, 64, 1, 128)
    if on_chip:  # a call is under a millisecond: amortise its dispatch
        iters = max(iters, 20)
    dtype, f32 = jnp.bfloat16, jnp.float32
    keys = jax.random.split(jax.random.key(0), 8)
    args = (
        jax.random.normal(keys[0], (B, S, H, P)).astype(dtype),
        jnp.exp(jax.random.uniform(keys[1], (B, S, H), minval=np.log(1e-3),
                                   maxval=np.log(0.1))),
        -jax.random.uniform(keys[2], (H,), minval=1.0, maxval=16.0),
        jax.random.normal(keys[3], (B, S, G, N)).astype(dtype),
        jax.random.normal(keys[4], (B, S, G, N)).astype(dtype),
        jax.random.normal(keys[5], (H,)),
    )
    cotangents = (jax.random.normal(keys[6], (B, S, H, P)),
                  jax.random.normal(keys[7], (B, H, P, N)))

    def both_ways(forward):
        def fwd_bwd(args, cotangents):
            out, vjp = jax.vjp(forward, *args)
            return out, vjp(cotangents)

        return jax.jit(forward), jax.jit(fwd_bwd)

    def body(*args):
        return op._body(*args, op.CHUNK)

    def calls(*args):
        return op.ssd(*args, interpret=not on_chip)

    with jax.default_matmul_precision("highest"):
        want = both_ways(body)[1](tuple(t.astype(f32) for t in args), cotangents)
    names = ("y", "last", "dx", "ddt", "da", "db", "dc", "dd")
    want = dict(zip(names, (np.asarray(t, np.float64) for t in (*want[0], *want[1]))))
    item = jnp.dtype(dtype).itemsize
    rows = B * S * (H * P * item + 2 * G * N * item + H * 4)
    moved = {"fwd": rows + B * S * H * P * 4, "fwd_bwd": 2 * (rows + B * S * H * P * 4)}
    peaks = costmodel.peaks_for()
    out = {"shape": [B, S, H, P], "groups": G, "state": N, "chunk": op.CHUNK,
           "dtype": "bfloat16", "on_chip": on_chip, "ideal_bytes": moved}
    for name, forward in (("body", body), ("kernel", calls)):
        fwd, fwd_bwd = both_ways(forward)
        got = fwd_bwd(args, cotangents)
        got = dict(zip(names, (*got[0], *got[1])))
        arm = {"max_err_over_max": {
            k: float(np.abs(np.asarray(got[k], np.float64) - want[k]).max()
                     / np.abs(want[k]).max()) for k in names}}
        for part, fn, operands in (("fwd", fwd, args),
                                   ("fwd_bwd", fwd_bwd, (args, cotangents))):
            arm[f"{part}_ms"] = ms = _med_ms(fn, operands, rounds, iters)
            if on_chip and peaks:
                arm[f"{part}_hbm_share"] = round(
                    moved[part] / peaks["bytes_per_s"] / (ms / 1e3), 4)
        out[name] = arm
        print(f"ssd {name}: " + "  ".join(
            f"{part} {arm[f'{part}_ms']} ms ({arm.get(f'{part}_hbm_share')})"
            for part in ("fwd", "fwd_bwd"))
            + f"  against float32 {arm['max_err_over_max']}", flush=True)
    out["body_over_kernel"] = {
        part: round(out["body"][f"{part}_ms"] / out["kernel"][f"{part}_ms"], 2)
        for part in ("fwd", "fwd_bwd")}
    print(f"ssd: body over kernel {out['body_over_kernel']}", flush=True)
    return out


def _ledger_swap(step_bytes_xla, region_bytes_xla, region_bytes_kernel,
                 flops, peaks) -> dict:
    """The transparent swap arithmetic: whole-step bytes with the
    replaced region's XLA traffic exchanged for the kernel's DMA bytes."""
    swapped = step_bytes_xla - region_bytes_xla + region_bytes_kernel
    ridge = peaks["flops"] / peaks["bytes_per_s"] if peaks else None
    out = {
        "step_bytes_xla": step_bytes_xla,
        "region_bytes_xla": region_bytes_xla,
        "region_bytes_kernel": region_bytes_kernel,
        "step_bytes_with_kernel": swapped,
        "flops": flops,
        "intensity_xla": round(flops / step_bytes_xla, 4),
        "intensity_with_kernel": round(flops / swapped, 4),
        "ridge_intensity": round(ridge, 4) if ridge else None,
    }
    if ridge:
        out["bound_xla"] = (
            "compute" if out["intensity_xla"] >= ridge else "memory"
        )
        out["bound_with_kernel"] = (
            "compute" if out["intensity_with_kernel"] >= ridge else "memory"
        )
    return out


def step_ab_efficientnet(batch: int, peaks) -> dict:
    """efficientnet_b0 train step: the fused optimizer update in context.
    Region = the isolated optax update over the real param tree."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    import distribuuuu_tpu.config as config
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.ops.pallas import opt_update as ou
    from distribuuuu_tpu.parallel import mesh as mesh_lib, sharding
    from distribuuuu_tpu.utils.optim import construct_optimizer

    config.reset_cfg()
    cfg.merge_from_file(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "config", "efficientnet_b0.yaml",
    ))
    cfg.defrost()
    im = cfg.TRAIN.IM_SIZE
    mesh = mesh_lib.build_mesh()
    model = trainer.build_model_from_cfg()
    state = trainer.create_train_state(model, jax.random.key(0), mesh, im)
    optimizer = construct_optimizer()
    step = trainer.make_train_step(model, optimizer,
                                   topk=trainer.effective_topk())
    rng = np.random.default_rng(0)
    batch_tree = sharding.shard_batch(mesh, {
        "image": rng.standard_normal((batch, im, im, 3)).astype(np.float32),
        "label": rng.integers(0, cfg.MODEL.NUM_CLASSES,
                              (batch,)).astype(np.int32),
        "mask": np.ones((batch,), np.float32),
    })
    cstep = _cost(step, (state, batch_tree))

    @jax.jit
    def opt_region(p, g, s):
        u, s2 = optimizer.update(g, s, p)
        return optax.apply_updates(p, u), s2

    grads = jax.tree.map(jnp.zeros_like, state.params)
    cregion = _cost(opt_region, (state.params, grads, state.opt_state))
    kernel_bytes = ou.leaf_pass_bytes(state.params, str(cfg.OPTIM.OPTIMIZER))
    return {
        "arch": "efficientnet_b0",
        "phase": "train",
        "kernel": "opt_update",
        "batch": batch,
        **_ledger_swap(
            cstep["bytes_accessed"], cregion["bytes_accessed"],
            kernel_bytes, cstep["flops"], peaks,
        ),
    }


def step_ab_gen_decode(peaks) -> dict:
    """gen_decode tile (b=4, c=256): the fused decode attention in the
    real GPTDecoder program. Region = the per-layer dense attention math
    over the cache tile."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import distribuuuu_tpu.config as config
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.lm import generate as gen
    from distribuuuu_tpu.ops.pallas import decode_attn as da

    config.reset_cfg()
    cfg.merge_from_file(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "config", "gpt_nano.yaml",
    ))
    cfg.defrost()
    model = trainer.build_model_from_cfg()
    dec = gen.decoder_for(model)
    b, c = 4, 256
    hh, dh = model.num_heads, model.dim // model.num_heads
    variables = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32), train=False
    )
    cache = {
        "k": jnp.zeros((model.depth, b, hh, c, dh), model.dtype),
        "v": jnp.zeros((model.depth, b, hh, c, dh), model.dtype),
    }
    toks = jnp.zeros((b, 1), jnp.int32)
    lens = jnp.zeros((b,), jnp.int32)

    def decode_fn(variables, tokens, lengths, cache):
        logits, cache = dec.apply(variables, tokens, lengths, cache)
        return logits[:, 0], cache

    cstep = _cost(jax.jit(decode_fn), (variables, toks, lens, cache))

    sc = dh ** -0.5
    q1 = jnp.zeros((b, hh, dh), model.dtype)
    k1 = jnp.zeros((b, hh, c, dh), model.dtype)

    @jax.jit
    def region(q, ck, cv, lens):
        s = jnp.einsum("bhd,bhcd->bhc", q.astype(jnp.float32),
                       ck.astype(jnp.float32)) * sc
        vis = jnp.arange(c)[None, None, :] <= lens[:, None, None]
        s = jnp.where(vis, s, jnp.float32(-1e30))
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhc,bhcd->bhd", w, cv.astype(jnp.float32))

    cregion = _cost(region, (q1, k1, k1, lens))
    kernel_bytes = da.pass_bytes(b, hh, c, dh, model.dtype)
    return {
        "arch": cfg.MODEL.ARCH,
        "phase": "generate",
        "kernel": "decode_attn",
        "tile": [b, c],
        "layers": model.depth,
        **_ledger_swap(
            cstep["bytes_accessed"],
            cregion["bytes_accessed"] * model.depth,
            kernel_bytes * model.depth,
            cstep["flops"], peaks,
        ),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap.add_argument("--out", default=os.path.join(repo, "BENCH_r09.json"))
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--opt-params", type=int, default=2_000_000,
                    help="synthetic param count for the opt-update micro A/B")
    ap.add_argument("--only", choices=["moe_rows", "short_conv", "head_prologue", "ssd"],
                    default=None,
                    help="run one entry alone and write it to --out as it "
                         "is (moe_rows: the held mixtures' row movers "
                         "against XLA's gathers, PERF.md section 6, PR 42; "
                         "short_conv: LFM2's gated short convolution, the "
                         "jax.numpy path against the two Pallas calls and "
                         "both against float64, PR 44; head_prologue: q's "
                         "and k's per-head norm, rotary and heads-major "
                         "layout at SDAR's and Trinity-Mini's shapes, the "
                         "same comparison, PR 51; ssd: Mamba-2's chunked "
                         "scan at Nemotron-3-Super's shape, ops/ssd.py's "
                         "body against the two Pallas calls and both "
                         "against the body in float32, PR 54)")
    ap.add_argument("--quick", action="store_true",
                    help="skip the in-context step ledgers (traces of the "
                         "full efficientnet/gpt programs)")
    args = ap.parse_args(argv)

    import jax

    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.telemetry import costmodel

    from distribuuuu_tpu.asyncplane import compile_cache

    compile_cache.setup_from_cfg(cfg)  # on the chip: warm across processes
    if args.only:
        bench = {"moe_rows": bench_moe_rows, "short_conv": bench_short_conv,
                 "head_prologue": bench_head_prologue, "ssd": bench_ssd}[args.only]
        doc = {"bench": BENCH_SCHEMA, "generated_by": "tools/kernel_bench.py",
               "backend": jax.default_backend(),
               args.only: bench(args.rounds, args.iters)}
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"{args.only} -> {args.out}")
        return 0
    peaks = costmodel.peaks_for()
    doc = {
        "bench": BENCH_SCHEMA,
        "generated_by": "tools/kernel_bench.py",
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "device_kind": peaks["kind"] if peaks else None,
        "nominal_peaks": bool(peaks.get("nominal")) if peaks else None,
        "caveat": CAVEAT,
        "kernels": {},
        "step_ab": {},
    }
    for name, fn in (
        ("opt_update_sgd", lambda: bench_opt_update(
            "sgd", args.opt_params, args.rounds, args.iters, peaks)),
        ("opt_update_adamw", lambda: bench_opt_update(
            "adamw", args.opt_params, args.rounds, args.iters, peaks)),
        ("conv_epilogue", lambda: bench_conv_epilogue(
            args.rounds, args.iters, peaks)),
        ("decode_attn", lambda: bench_decode_attn(
            args.rounds, args.iters, peaks)),
    ):
        t0 = time.perf_counter()
        row = fn()
        doc["kernels"][name] = row
        xi = row["xla"].get("intensity")
        pi = row["pallas"].get("intensity")
        print(f"{name:<18} bytes xla/pallas "
              f"{row['bytes_ratio_xla_over_pallas']}x  intensity "
              f"{xi} -> {pi}  max|d| {row['max_abs_diff']:.2e}  "
              f"({time.perf_counter() - t0:.1f}s)")
    if not args.quick:
        for label, fn in (
            ("efficientnet_b0_train_opt_update",
             lambda: step_ab_efficientnet(8, peaks)),
            ("gen_decode_b4_c256", lambda: step_ab_gen_decode(peaks)),
        ):
            t0 = time.perf_counter()
            row = fn()
            doc["step_ab"][label] = row
            print(f"{label:<34} intensity {row['intensity_xla']} -> "
                  f"{row['intensity_with_kernel']} (ridge "
                  f"{row['ridge_intensity']}; {row.get('bound_xla')} -> "
                  f"{row.get('bound_with_kernel')})  "
                  f"({time.perf_counter() - t0:.1f}s)")
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"kernel A/B matrix -> {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
