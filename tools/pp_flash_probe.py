"""Probe: pallas flash kernel inside shard_map + lax.scan + ppermute on TPU.

The exact program structure PipelinedViT's pipeline produces (pp.pipelined:
shard_map over the mesh, lax.scan over schedule ticks, ppermute hops), with
the flash pallas_call in the stage body. 1 real chip => pipe axis size 1
(ppermute is an identity hop, but the collective + custom-call coexistence
is what Mosaic/XLA must accept).

RESULT (v5e, 2026-07-31, VERDICT r2 #7): compiles and runs, forward AND
backward — max fwd err vs the exact-attention oracle 4.9e-4, finite grads.
The r2 refusal of flash inside pipeline stages was conservative, not a
Mosaic limitation; PipelinedViT now accepts attn_impl='flash'/'blockwise'
(models/vit.py), with the CPU-mesh composition test in
tests/test_pp_ep_trainer.py::test_pipe_with_flash_attention. Multi-chip
ppermute (pipe axis > 1) remains hardware-unverified in this 1-chip
environment — the driver's 8-device CPU dryrun covers the multi-stage
schedule with the scan fallback.
"""
# run on the real chip: python tools/pp_flash_probe.py [--kernel decode]
#
# --kernel decode (ISSUE 13): the SAME shard_map + lax.scan + ppermute
# structure with the kernel tier's fused decode attention
# (ops/pallas/decode_attn.py) as the stage body — proves the
# collective + decode-custom-call coexistence the tier needs before a
# pipelined decode server can exist. Off-TPU the kernel runs in
# interpret mode (this probe is then a structure check, not a perf one).
import argparse

import _path  # noqa: F401  (repo root onto sys.path)
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

ap = argparse.ArgumentParser()
ap.add_argument("--kernel", default="flash", choices=["flash", "decode"],
                help="which tier kernel to probe inside the PP structure")
args = ap.parse_args()

mesh = Mesh(np.array(jax.devices()[:1]), ("pipe",))
rng = np.random.default_rng(0)

if args.kernel == "decode":
    from distribuuuu_tpu.ops.pallas import decode_attn as da

    B, H, C, D = 2, 3, 256, 64
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
    ck = jnp.asarray(rng.standard_normal((B, H, C, D)), jnp.bfloat16)
    cv = jnp.asarray(rng.standard_normal((B, H, C, D)), jnp.bfloat16)
    lens = jnp.asarray([5, C - 2], jnp.int32)
    sc = D ** -0.5
    interp = jax.default_backend() != "tpu"

    def per_device(q, ck, cv):
        def tick(carry, t):
            o = da.decode_attention(carry.astype(jnp.bfloat16), ck, cv,
                                    lens, scale=sc, interpret=interp)
            o = jax.lax.ppermute(
                o, "pipe", [(i, (i + 1) % 1) for i in range(1)]
            )
            return o, ()

        out, _ = jax.lax.scan(tick, q.astype(jnp.float32), jnp.arange(2))
        return out

    f = jax.jit(jax.shard_map(per_device, mesh=mesh,
                              in_specs=(P(), P(), P()), out_specs=P(),
                              check_vma=False))
    got = np.asarray(f(q, ck, cv), np.float32)

    def dense(q):
        s = jnp.einsum("bhd,bhcd->bhc", q.astype(jnp.float32),
                       ck.astype(jnp.float32)) * sc
        vis = jnp.arange(C)[None, None, :] <= lens[:, None, None]
        s = jnp.where(vis, s, jnp.float32(-1e30))
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhc,bhcd->bhd", w, cv.astype(jnp.float32))

    want = dense(dense(q).astype(jnp.bfloat16))
    err = np.abs(got - np.asarray(want, np.float32)).max()
    print("PP-structure decode probe: max err", err)
    assert err < 0.05, err
    print("decode kernel + ppermute coexistence: ok")
    raise SystemExit(0)

from distribuuuu_tpu.ops.flash_attention import flash_attention
from distribuuuu_tpu.ops.ring_attention import reference_attention

q, k, v = (jnp.asarray(rng.standard_normal((2, 3, 2048, 64)), jnp.bfloat16)
           for _ in range(3))

def per_device(q, k, v):
    def tick(carry, t):
        o = flash_attention(carry, k, v)
        o = jax.lax.ppermute(o, "pipe", [(i, (i + 1) % 1) for i in range(1)])
        return o.astype(carry.dtype), ()
    out, _ = jax.lax.scan(tick, q, jnp.arange(2))
    return out

f = jax.jit(jax.shard_map(per_device, mesh=mesh,
                          in_specs=(P(), P(), P()), out_specs=P(),
                          check_vma=False))
got = np.asarray(f(q, k, v), np.float32)

# oracle: two sequential applications of exact attention
want = reference_attention(reference_attention(q, k, v).astype(q.dtype), k, v)
err = np.abs(got - np.asarray(want, np.float32)).max()
print("PP-structure flash probe: max err", err)
assert err < 0.05, err
# grad through the same structure (the training path)
g = jax.jit(jax.grad(lambda q: jnp.sum(f(q, k, v).astype(jnp.float32))))(q)
assert bool(jnp.isfinite(g.astype(jnp.float32)).all()), "non-finite grads"
print("grad ok: True")
