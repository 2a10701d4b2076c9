"""Grouped-conv formulation microbench (VERDICT r4 #2).

Compares, on the RegNet grouped 3×3 shapes, fwd and fwd+bwd time of:

  fused     lax.conv_general_dilated with feature_group_count=G
            (XLA's native lowering — channel-retiling copies, PERF.md)
  unrolled  G per-group convs over slices of one canonical kernel
            (models/layers.UnrolledGroupConv, the r1 workaround)
  shifted   9 shift-strided BATCHED matmuls accumulated:
            out[...,g,f] = Σ_{dy,dx} x_pad[b, si+dy, sj+dx, g, :] @ W[dy,dx,g]
            — one [G, B·Ho·Wo, c] @ [G, c, f] dot per tap, G in the dot's
            batch dims: few large MXU ops instead of G small convs.

All three compute the SAME canonical-kernel math; exactness is asserted
at fp32 on every shape before timing.

    python tools/group_conv_bench.py [--iters 30] [--rounds 3]
"""

from __future__ import annotations

import argparse
import functools
import statistics
import time

import _path  # noqa: F401

import jax
import jax.numpy as jnp
import numpy as np

# (label, B, H, W, C, G, stride) — the grouped 3×3 convs of regnety_160
# (stages 1-4) and regnetx_160's stage-3, batch 64, plus the stride-2
# stage entries.
SHAPES = [
    ("y160-s1", 64, 56, 56, 224, 2, 1),
    ("y160-s2", 64, 28, 28, 448, 4, 1),
    ("y160-s3", 64, 14, 14, 1232, 11, 1),
    ("y160-s3/s2", 64, 28, 28, 1232, 11, 2),
    ("y160-s4", 64, 7, 7, 3024, 27, 1),
    ("x160-s3", 64, 14, 14, 896, 7, 1),
]


def conv_fused(x, k, stride, groups):
    return jax.lax.conv_general_dilated(
        x, k, (stride, stride), [(1, 1), (1, 1)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
    )


def conv_unrolled(x, k, stride, groups):
    # the exactness oracle IS the library formulation — one source of truth
    from distribuuuu_tpu.ops.group_conv import _xla_unrolled

    return _xla_unrolled(x, k, stride, groups)


def conv_shifted(x, k, stride, groups):
    b, h, w, c_all = x.shape
    kh, kw, cg, f_all = k.shape
    fg = f_all // groups
    ho = (h + 2 - kh) // stride + 1
    wo = (w + 2 - kw) // stride + 1
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    xp = xp.reshape(b, h + 2, w + 2, groups, cg)
    # canonical HWIO kernel: features axis is G-major → [kh,kw,G,cg,fg]
    kg = k.reshape(kh, kw, cg, groups, fg).transpose(0, 1, 3, 2, 4)
    out = None
    for dy in range(kh):
        for dx in range(kw):
            xs = xp[:, dy:dy + stride * ho:stride,
                    dx:dx + stride * wo:stride]
            t = jnp.einsum(
                "bhwgc,gcf->bhwgf", xs, kg[dy, dx],
                preferred_element_type=jnp.float32,
            )
            out = t if out is None else out + t
    return out.astype(x.dtype).reshape(b, ho, wo, f_all)


IMPLS = {
    "fused": conv_fused,
    "unrolled": conv_unrolled,
    "shifted": conv_shifted,
}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args()
    dtype = jnp.dtype(args.dtype)

    rng = np.random.default_rng(0)
    for label, b, h, w, c, groups, stride in SHAPES:
        cg = c // groups
        x = jnp.asarray(
            rng.standard_normal((b, h, w, c)) * 0.1, dtype)
        k = jnp.asarray(
            rng.standard_normal((3, 3, cg, c)) * 0.05, dtype)

        # exactness at fp32 before timing
        xf, kf = x.astype(jnp.float32), k.astype(jnp.float32)
        ref = conv_fused(xf, kf, stride, groups)
        for name, fn in IMPLS.items():
            if name == "fused":
                continue
            got = fn(xf, kf, stride, groups)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(ref), rtol=2e-4, atol=2e-4,
                err_msg=f"{label} {name}",
            )

        flops = 2 * b * ((h // stride) * (w // stride)) * 9 * cg * c
        print(f"== {label}: x[{b},{h},{w},{c}] G={groups} s={stride} "
              f"({flops/1e9:.1f} GFLOP fwd)", flush=True)

        # Timing fences on a value fetch of a scalar derived from the
        # output. Iterations dispatch asynchronously against constant
        # inputs and the final scalar fetch drains the in-order device
        # queue — these are pipelined-throughput figures that sit on the
        # host's per-call dispatch floor; the LOAD-BEARING comparisons
        # use the marginal-cost harness instead.
        scalar = jax.jit(lambda o: jnp.sum(o.astype(jnp.float32)))

        fns = {}
        for name, fn in IMPLS.items():
            fwd = jax.jit(functools.partial(fn, stride=stride, groups=groups))

            def loss(xx, kk, _fn=fn):
                return jnp.sum(
                    _fn(xx, kk, stride, groups).astype(jnp.float32) ** 2
                )

            gr = jax.jit(jax.grad(loss, argnums=(0, 1)))
            float(scalar(fwd(x, k)))
            float(scalar(gr(x, k)[1]))
            fns[name] = (fwd, gr)

        for mode in ("fwd", "fwd+bwd"):
            meds = {}
            times = {n: [] for n in fns}
            for _ in range(args.rounds):
                for name, (fwd, gr) in fns.items():
                    t0 = time.perf_counter()
                    if mode == "fwd":
                        for _ in range(args.iters):
                            o = fwd(x, k)
                        float(scalar(o))  # drains the in-order queue
                    else:
                        for _ in range(args.iters):
                            g = gr(x, k)
                        float(scalar(g[1]))
                    times[name].append(
                        (time.perf_counter() - t0) / args.iters * 1e3
                    )
            for name, ts in times.items():
                meds[name] = statistics.median(ts)
            base = meds["fused"]
            line = "  ".join(
                f"{n} {m:7.3f} ms ({base/m:4.2f}× vs fused)"
                for n, m in meds.items()
            )
            print(f"  {mode:7s}: {line}", flush=True)


if __name__ == "__main__":
    main()
