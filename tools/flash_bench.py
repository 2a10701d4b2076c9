"""Reproduce the flash-attention performance comparison (PERF.md).

Benchmarks the three long-sequence attention paths at a chosen shape —
the hand-tiled Pallas flash kernel (ops/flash_attention.py), the lax.scan
blockwise path (ops/ring_attention.blockwise_attention), and dense XLA —
forward and forward+backward.

Methodology (both hazards burned earlier rounds):

1. **Dispatch amortization**: N applications folded inside ONE jit via
   lax.scan with output feedback; per-call timing measures the host's
   dispatch floor, not the kernel. The fwd+bwd
   feedback MUST depend on all three grads — feeding back only dq lets
   XLA dead-code-eliminate the dK/dV backward (a separable pallas_call on
   the flash path).
2. **Interleaved paired rounds**: load on a shared machine drifts the
   absolute ms within and between sessions, so timing path A
   in one block of windows and path B in another measures the drift, not
   the kernels. Every round times one window of EVERY path back-to-back;
   the reported ratio is the MEDIAN of per-round ratios (paired samples),
   with per-path median ± [min, max] spread printed alongside.

Usage (defaults are the ViT-Ti/1024px shape [4, 3, 4096, 64], non-causal;
the token decoders' is ``--heads 16 --dim 128 --causal``, [4, 16, 4096, 128]
for OLMoE's cell and ``--batch 1`` for Ouro's):

    python tools/flash_bench.py [--batch 4] [--heads 3] [--seq 4096]
        [--dim 64] [--causal] [--iters 20] [--rounds 5] [--skip-dense]
        [--blk-q N] [--blk-k N] [--sweep] [--baseline FILE] [--kv-heads N]
        [--window W] [--diffusion-block B]

``--blk-q``/``--blk-k`` default to what ``flash_attention.choose_blocks``
picks for the shape (printed). ``--sweep`` times the flash path alone at
every pair of {256, 512, 1024}, forward and forward+backward, beside the
chosen pair: what ``choose_blocks`` was set from. ``--baseline FILE`` loads
another checkout's ``ops/flash_attention.py`` as path ``base`` (its own
default blocks) into the same interleaved rounds.

``--kv-heads N`` gives k and v N heads where q has ``--heads`` (grouped
queries; LFM2's attention is ``--batch 2 --heads 32 --kv-heads 8 --seq 8192
--dim 64 --causal``): path ``flash`` hands the kernels k and v as they are,
path ``repeat`` repeats them to q's heads in HBM first (what a caller had to
do before the kernels took a group; its backward sums dK and dV over the
group in autodiff's transpose of the repeat), and the scan and dense paths
read the repeated heads.

``--window W`` (with ``--causal``) gives the flash, repeat, scan and sweep
paths a sliding window of W keys (Trinity-Mini's window layers are ``--batch
2 --heads 32 --kv-heads 4 --seq 8192 --dim 128 --causal --window 2048``);
the useful work is then the (query, key) pairs the window keeps. Path
``base`` (another checkout's kernels, which may know no window) and the
dense path stay plain causal: beside them the window's skip shows.

``--diffusion-block B`` (with ``--causal``) reads ``--seq`` as the 2S rows of
a noised and a clean copy of S tokens under the block-diffusion mask (SDAR's
layers are ``--batch 1 --heads 32 --kv-heads 4 --seq 16384 --dim 128 --causal
--diffusion-block 4``); the useful work is the S (S + B) pairs the mask
keeps, and path ``causal`` is the same kernels on the same rows under the
plain causal mask: beside it the two-range walk's skip shows (288 tiles
against 528 at that shape).

``--kernel decode`` (ISSUE 13) switches the harness to the kernel
tier's fused decode attention (ops/pallas/decode_attn.py) vs the dense
XLA reference of lm/generate.CachedAttention's T=1 step: --seq becomes
the cache tile, --batch the live rows (ragged lengths drawn per row),
same interleaved paired-round methodology.
"""

from __future__ import annotations

import argparse
import statistics
import time

import _path  # noqa: F401  (repo root onto sys.path)
import numpy as np


def make_fwd_runner(fn, q, k, v, iters: int):
    """One jitted callable folding ``iters`` applications; returns a timing
    closure that runs one window and fences on a scalar of the result."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(q, k, v):
        def body(c, _):
            o = fn(c, k, v)
            return o.astype(c.dtype), ()  # feedback defeats DCE

        out, _ = jax.lax.scan(body, q, None, length=iters)
        return out

    def window():
        t0 = time.perf_counter()
        o = run(q, k, v)
        float(jnp.sum(o.astype(jnp.float32)))  # fence: fetch a value
        return (time.perf_counter() - t0) / iters

    window()  # compile + warm
    return window


def make_bwd_runner(fn, q, k, v, iters: int):
    import jax
    import jax.numpy as jnp

    grad = jax.grad(
        lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)),
        argnums=(0, 1, 2),
    )

    @jax.jit
    def run(q, k, v):
        def body(c, _):
            dq, dk, dv = grad(c, k, v)
            # feedback must depend on ALL grads (hazard 1 in the docstring);
            # grouped k and v have fewer heads than dq: their sum, scaled to
            # nothing, one small reduction
            if dk.shape == dq.shape:
                return (dq + dk + dv).astype(c.dtype), ()
            rest = (dk + dv).astype(jnp.float32).sum() * 1e-30
            return (dq + rest.astype(dq.dtype)).astype(c.dtype), ()

        out, _ = jax.lax.scan(body, q, None, length=iters)
        return out

    def window():
        t0 = time.perf_counter()
        o = run(q, k, v)
        float(jnp.sum(o.astype(jnp.float32)))
        return (time.perf_counter() - t0) / iters

    window()
    return window


def interleaved(runners: dict, rounds: int) -> dict:
    """rounds × one window per path, adjacent in time. → {name: [s, ...]}"""
    times = {name: [] for name in runners}
    for _ in range(rounds):
        for name, window in runners.items():
            times[name].append(window())
    return times


def report(tag: str, times: dict, flops: float | None = None):
    med = {n: statistics.median(ts) for n, ts in times.items()}
    for name, ts in times.items():
        extra = (
            f" ({flops / med[name] / 1e12:5.1f} TFLOP/s)" if flops else ""
        )
        print(
            f"{tag} {name:9s}: median {med[name] * 1e3:7.3f} ms "
            f"[{min(ts) * 1e3:.3f}, {max(ts) * 1e3:.3f}]{extra}"
        )
    for other in ("scan", "dense", "base", "repeat"):
        if "flash" in times and other in times:
            ratios = sorted(
                o / f for o, f in zip(times[other], times["flash"])
            )
            print(
                f"{tag} flash-vs-{other} per-round ratios: "
                f"median {statistics.median(ratios):.2f}x "
                f"[{ratios[0]:.2f}, {ratios[-1]:.2f}]"
            )
    return med


def run_decode(args):
    """The --kernel decode arm: fused decode attention vs the dense
    reference at one (batch, cache, heads, dim) tile."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distribuuuu_tpu.ops.pallas import decode_attn as da

    B, H, C, D = args.batch, args.heads, args.seq, args.dim
    print(f"backend={jax.default_backend()} decode tile "
          f"q[{B},{H},{D}] cache[{B},{H},{C},{D}] iters={args.iters} "
          f"rounds={args.rounds}")
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
    ck = jnp.asarray(rng.standard_normal((B, H, C, D)), jnp.bfloat16)
    cv = jnp.asarray(rng.standard_normal((B, H, C, D)), jnp.bfloat16)
    lens = jnp.asarray(rng.integers(0, C - 1, (B,)), jnp.int32)
    sc = D ** -0.5
    interp = jax.default_backend() != "tpu"

    def dense(q, ck, cv):
        s = jnp.einsum("bhd,bhcd->bhc", q.astype(jnp.float32),
                       ck.astype(jnp.float32)) * sc
        vis = jnp.arange(C)[None, None, :] <= lens[:, None, None]
        s = jnp.where(vis, s, jnp.float32(-1e30))
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhc,bhcd->bhd", w, cv.astype(jnp.float32))

    def fused(q, ck, cv):
        return da.decode_attention(q, ck, cv, lens, scale=sc,
                                   blk_k=args.blk_k or 128,
                                   interpret=interp)

    paths = {"pallas": fused, "dense": dense}
    runners = {}
    for name, fn in paths.items():
        @jax.jit
        def run(q, ck, cv, fn=fn):
            def body(c, _):
                o = fn(c.astype(jnp.bfloat16), ck, cv)
                return o, ()  # output feedback defeats DCE (hazard 1)

            out, _ = jax.lax.scan(body, q.astype(jnp.float32), None,
                                  length=args.iters)
            return out

        def window(run=run):
            t0 = time.perf_counter()
            o = run(q, ck, cv)
            float(jnp.sum(o.astype(jnp.float32)))
            return (time.perf_counter() - t0) / args.iters

        window()
        runners[name] = window
    times = interleaved(runners, args.rounds)
    report("decode ", times)
    err = float(jnp.abs(
        paths["pallas"](q, ck, cv) - paths["dense"](q, ck, cv)
    ).max())
    print(f"decode  pallas-vs-dense max|d|: {err:.2e}")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--kernel", default="flash",
                    choices=["flash", "decode"],
                    help="which tier kernel to benchmark: the flash "
                         "attention paths (default) or the fused decode "
                         "attention (--seq = cache tile)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=3)
    ap.add_argument("--kv-heads", type=int, default=None,
                    help="heads of k and v where fewer than q's (grouped "
                         "queries); adds path 'repeat'")
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--iters", type=int, default=20,
                    help="applications folded per window (≥20: shorter "
                         "windows under-amortize the dispatch floor)")
    ap.add_argument("--rounds", type=int, default=5,
                    help="interleaved timing rounds (paired ratios)")
    ap.add_argument("--blk-q", type=int, default=None,
                    help="default: what choose_blocks picks for the shape")
    ap.add_argument("--blk-k", type=int, default=None)
    ap.add_argument("--sweep", action="store_true",
                    help="the flash path alone over every pair of "
                         "{256, 512, 1024}, beside the chosen pair")
    ap.add_argument("--baseline", default=None, metavar="FILE",
                    help="another checkout's ops/flash_attention.py, timed "
                         "as path 'base' in the same rounds")
    ap.add_argument("--skip-dense", action="store_true",
                    help="skip the O(L²)-memory dense baseline")
    ap.add_argument("--causal", action="store_true",
                    help="the causal paths: the kernels' diagonal walk "
                         "against the causal scan and dense")
    ap.add_argument("--window", type=int, default=None,
                    help="a sliding window of this many keys (with --causal)")
    ap.add_argument("--skip-scan", action="store_true",
                    help="leave the lax.scan path and the check against it "
                         "out (its backward does not fit the chip at 16,384 "
                         "rows x 32 heads)")
    ap.add_argument("--diffusion-block", type=int, default=None,
                    help="the block-diffusion mask over a noised and a clean "
                         "copy, --seq rows in all (with --causal)")
    args = ap.parse_args()

    from distribuuuu_tpu.config import cfg

    from distribuuuu_tpu.asyncplane import compile_cache

    compile_cache.setup_from_cfg(cfg)  # on the chip: warm across processes
    if args.kernel == "decode":
        if args.seq == 4096:
            args.seq = 256  # decode default: the gen_decode cache tile
        return run_decode(args)

    import jax
    import jax.numpy as jnp

    from distribuuuu_tpu.ops import flash_attention as fa
    from distribuuuu_tpu.ops import ring_attention as ra

    B, H, L, D = args.batch, args.heads, args.seq, args.dim
    print(f"backend={jax.default_backend()} "
          f"device={jax.devices()[0].device_kind} shape=[{B},{H},{L},{D}] "
          f"kv_heads={args.kv_heads or H} "
          f"iters={args.iters} rounds={args.rounds}")
    rng = np.random.default_rng(0)
    kv_heads = args.kv_heads or H
    group = H // kv_heads
    q, k, v = (
        jnp.asarray(rng.standard_normal((B, heads, L, D)), jnp.bfloat16)
        for heads in (H, kv_heads, kv_heads)
    )

    def repeated(fn):
        """``fn`` on k and v repeated to q's heads (a no-op at group 1)."""
        if group == 1:
            return fn
        return lambda q, k, v: fn(
            q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1))

    # causal touches only the lower triangle — half the score/PV work; a
    # window the pairs it keeps: the first W rows' triangle, then W a row
    pairs = L * L * (0.5 if args.causal else 1.0)
    windowed = {}
    if args.window:
        w = min(args.window, L)
        pairs = w * (w + 1) / 2 + (L - w) * w
        windowed = {"window": args.window}
        print(f"window: {args.window} keys, {pairs / L:.1f} a query on average; "
              f"tiles visited and crossed at the chosen blocks are below")
    if args.diffusion_block:
        pairs = (L // 2) * (L // 2 + args.diffusion_block)
        windowed = {"diffusion_block": args.diffusion_block}
    flops = 2 * 2 * B * H * pairs * D

    chosen = fa.choose_blocks(L, D, args.causal)
    blk_q, blk_k = args.blk_q or chosen[0], args.blk_k or chosen[1]
    print(f"blocks: choose_blocks({L}, {D}, causal={args.causal}) = {chosen}; "
          f"running blk_q={blk_q} blk_k={blk_k}, resolved "
          f"{fa._resolve_blocks(L, blk_q, blk_k)[:2]}")

    if args.window:
        rq, rk, _ = fa._resolve_blocks(L, blk_q, blk_k)
        print(f"tiles: visited, crossed = "
              f"{fa.tile_counts(L, rq, rk, args.causal, args.window)} with the "
              f"window, {fa.tile_counts(L, rq, rk, args.causal)} without")

    if args.diffusion_block:
        rq, rk, *_ = fa._geometry(L, blk_q, blk_k, args.diffusion_block)
        print(f"tiles: visited, crossed = "
              f"{fa.tile_counts(L, rq, rk, True, None, args.diffusion_block)} "
              f"under the block-diffusion mask, {fa.tile_counts(L, rq, rk, True)} "
              f"causal; {pairs / (L // 2):.0f} keys a data token")

    def flash(blk_q, blk_k, module=fa):
        return lambda q, k, v: module.flash_attention(
            q, k, v, causal=args.causal, blk_q=blk_q, blk_k=blk_k, **windowed)

    scan = repeated(lambda q, k, v: ra.blockwise_attention(
        q, k, v, causal=args.causal, **windowed))

    paths = {"flash": flash(blk_q, blk_k)}
    if group > 1:
        paths["repeat"] = repeated(flash(blk_q, blk_k))
    if args.diffusion_block:
        paths["causal"] = lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, blk_q=blk_q, blk_k=blk_k)
    if args.baseline:
        import importlib.util

        spec = importlib.util.spec_from_file_location("flash_base", args.baseline)
        base = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(base)
        paths["base"] = repeated(lambda q, k, v: base.flash_attention(
            q, k, v, causal=args.causal))
    if args.sweep:
        sizes = (256, 512, 1024)
        paths.update({f"{a}x{b}": flash(a, b) for a in sizes for b in sizes})
    elif not args.skip_scan:
        paths["scan"] = scan
        if not args.skip_dense:
            paths["dense"] = repeated(lambda q, k, v: ra.reference_attention(
                q, k, v, causal=args.causal
            ))

    # the kernels against the scan on this device, once: output and all
    # three gradients (bf16 in, so ~1e-2 of the largest value is rounding)
    def out_and_grads(fn):
        loss = lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)  # noqa: E731
        return (fn(q, k, v), *jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))

    for name, a, b in () if args.skip_scan else zip(
            ("o", "dq", "dk", "dv"), out_and_grads(paths["flash"]), out_and_grads(scan)):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        print(f"check   flash-vs-scan {name}: max|d| {float(jnp.abs(a - b).max()):.3e} "
              f"of max|ref| {float(jnp.abs(b).max()):.3e}")

    fwd_runners = {
        n: make_fwd_runner(fn, q, k, v, args.iters)
        for n, fn in paths.items()
    }
    report("fwd    ", interleaved(fwd_runners, args.rounds), flops)
    del fwd_runners
    bwd_runners = {
        n: make_bwd_runner(fn, q, k, v, args.iters)
        for n, fn in paths.items()
    }
    # useful work: the forward's two matmuls and the backward's four
    report("fwd+bwd", interleaved(bwd_runners, args.rounds), 3 * flops)


if __name__ == "__main__":
    main()
