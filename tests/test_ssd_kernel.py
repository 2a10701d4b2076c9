"""Mamba-2's chunked scan as two Pallas calls (``ops/pallas/ssd.py``), run by
the interpreter at the cell's head geometry (16 heads of 64 on one group of a
128 state, chunks of 128): ``y``, the last state and the gradients of all six
inputs against ``ops/ssd.py``'s ``jax.numpy`` body AND ``tests/test_ssd.py``'s
token-by-token recurrence, float32 at 1e-5 and bfloat16 operands at the body's
own distance from the float32 recurrence; one, two and three chunks, a
sequence the chunk does not divide, two groups; a state dropped between chunks
reads wrong; what ``kernel.select`` / ``kernel.fallback`` say; the lowered
Nemotron step holds both calls under ``ssm_scan``; and nothing names a knob."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_ssd import close, recurrence

from distribuuuu_tpu.ops import pallas as tier
from distribuuuu_tpu.ops import ssd as op
from distribuuuu_tpu.ops.pallas import ssd as kernel

CHUNK, WIDTH, STATE = 128, 64, 128
TOL = 1e-5
NAMES = ("x", "dt", "a", "b", "c", "d")


def inputs(seq, groups, per, dtype=jnp.float32, seed=0, batch=1):
    heads = groups * per
    ks = jax.random.split(jax.random.key(seed), 8)
    return {
        "x": jax.random.normal(ks[0], (batch, seq, heads, WIDTH)).astype(dtype),
        # steps of 0.001 .. 0.5 and rates of 1 .. 16, as tests/test_ssd.py's
        "dt": jnp.exp(jax.random.uniform(
            ks[1], (batch, seq, heads), minval=np.log(1e-3), maxval=np.log(0.5))),
        "a": -jax.random.uniform(ks[2], (heads,), minval=1.0, maxval=16.0),
        "b": jax.random.normal(ks[3], (batch, seq, groups, STATE)).astype(dtype),
        "c": jax.random.normal(ks[4], (batch, seq, groups, STATE)).astype(dtype),
        "d": jax.random.normal(ks[5], (heads,)),
    }, (jax.random.normal(ks[6], (batch, seq, heads, WIDTH)),
        jax.random.normal(ks[7], (batch, heads, WIDTH, STATE)))


FORMS = {
    "kernel": lambda kw: op.ssd(*(kw[k] for k in NAMES), chunk=CHUNK, interpret=True),
    "body": lambda kw: op._body(*(kw[k] for k in NAMES), CHUNK),
    "recurrence": lambda kw: recurrence(**kw),
}


def both_ways(form, args, probes):
    """``(y, last)`` and the gradients of ``<y, probe> + <last, probe>``."""
    def scalar(kw):
        y, last = FORMS[form](kw)
        return (y * probes[0]).sum() + (last * probes[1]).sum()

    with jax.default_matmul_precision("highest"):
        return jax.jit(FORMS[form])(args), jax.jit(jax.grad(scalar))(args)


# the cell's 16 heads on one group over 1, 2 and 3 chunks, a sequence the
# chunk does not divide (padded, not refused), and two groups of 8
GEOMETRIES = {"one_chunk": (CHUNK, 1, 16), "two_chunks": (2 * CHUNK, 1, 16),
              "three_chunks": (3 * CHUNK, 1, 16), "undivided": (2 * CHUNK + 44, 1, 16),
              "two_groups": (2 * CHUNK, 2, 8)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_the_two_calls_are_the_body_and_the_recurrence(geometry, dtype):
    seq, groups, per = GEOMETRIES[geometry]
    args, probes = inputs(seq, groups, per, jnp.dtype(dtype), seed=seq + per)
    out, grads = both_ways("kernel", args, probes)
    assert out[0].shape == args["x"].shape and out[0].dtype == jnp.float32
    assert out[1].shape == probes[1].shape and out[1].dtype == jnp.float32
    assert all(grads[k].dtype == args[k].dtype and grads[k].shape == args[k].shape
               for k in NAMES)
    body = both_ways("body", args, probes)
    if dtype == "float32":
        # the recurrence is the yardstick (1e-7 from float64 at these sizes);
        # the body is held to it too, and the calls to the body by what is left
        exact = both_ways("recurrence", args, probes)
        for name, got, theirs, truth in (
                ("y", out[0], body[0][0], exact[0][0]),
                ("last", out[1], body[0][1], exact[0][1]),
                *((leaf, grads[leaf], body[1][leaf], exact[1][leaf]) for leaf in NAMES)):
            # da: every position, lane and state entry summed into one float32
            # a head (the body reads 7.5e-6 over three chunks, the calls 1.02e-5)
            limit = 2 * TOL if name == "a" else TOL
            assert close(got, truth) < limit, ("recurrence", name)
            assert close(got, theirs) < limit + close(theirs, truth), ("body", name)
        return
    # bfloat16 operands: the body's own distance from the recurrence in
    # float32 on the same (rounded) inputs, with the room two roundings of one
    # sum take from each other (and dy, which the calls hand the MXU in
    # bfloat16 as the TPU's default precision does and the CPU's does not)
    exact = {k: v.astype(jnp.float32) for k, v in args.items()}
    want, want_grads = both_ways("recurrence", exact, probes)
    f32 = lambda t: t.astype(jnp.float32)
    for name, got, theirs, truth in (
            ("y", out[0], body[0][0], want[0]), ("last", out[1], body[0][1], want[1]),
            *((leaf, f32(grads[leaf]), f32(body[1][leaf]), want_grads[leaf])
              for leaf in NAMES)):
        assert close(got, truth) < 3.0 * close(theirs, truth) + 1e-6, name


def test_a_state_dropped_between_chunks_reads_wrong(monkeypatch):
    """The planted fault: every chunk starts from nothing (the body's test
    drops the ``associative_scan``; here the state's block is zeroed a
    chunk, not a sequence)."""
    args, _ = inputs(3 * CHUNK, 1, 16, seed=3)
    args["dt"] = args["dt"] / 64  # 128 positions must not forget the state by themselves
    want_y, want_last = recurrence(**args)
    real = kernel.pl.program_id
    monkeypatch.setattr(kernel.pl, "program_id", lambda axis: 0 * real(axis))
    jax.clear_caches()
    try:
        y, last = FORMS["kernel"](args)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert close(y[:, :CHUNK], want_y[:, :CHUNK]) < TOL  # the first chunk carries nothing
    assert close(y, want_y) > 1e-2
    assert close(last, want_last) > 1e-2
    y, last = FORMS["kernel"](args)
    assert close(y, want_y) < TOL and close(last, want_last) < TOL


def _records(path, kind):
    from distribuuuu_tpu.telemetry import schema

    records = [json.loads(line) for line in open(path)]
    for record in records:
        if record.get("kind", "").startswith("kernel."):
            schema.validate_record(record)
    return [r for r in records if r.get("kind") == kind and r["op"] == "ssd"]


def test_select_and_fallback_say_which_path_ran_and_why(tmp_path, monkeypatch):
    from distribuuuu_tpu.telemetry import spans

    def trace(seq=2 * CHUNK + 3, heads=16, width=WIDTH, state=STATE, chunk=CHUNK,
              interpret=True):
        x = jax.ShapeDtypeStruct((1, seq, heads, width), jnp.bfloat16)
        dt = jax.ShapeDtypeStruct((1, seq, heads), jnp.float32)
        a = jax.ShapeDtypeStruct((heads,), jnp.float32)
        b = jax.ShapeDtypeStruct((1, seq, 1, state), jnp.bfloat16)
        jax.eval_shape(lambda *t: op.ssd(*t, chunk=chunk, interpret=interpret),
                       x, dt, a, b, b, a)

    tier.reset_selection()
    path = spans.setup_telemetry(str(tmp_path), rank=0)
    try:
        trace(interpret=None)  # the CPU: the interpreter is the tests' path
        trace()                # forced: the kernel
        trace()                # once a traced shape
        trace(state=6)         # forced, a state off the lanes
        trace(chunk=32)        # forced, chunks that are not the lanes
        trace(width=48)        # forced, heads that share no lane tile
        trace(heads=4)         # forced, fewer heads than a register's rows
        # as on a TPU host of several chips, outside any shard_map
        monkeypatch.setattr(tier, "interpret_mode", lambda: False)
        assert jax.device_count() > 1
        trace(interpret=None)
    finally:
        spans.close_telemetry()
        tier.reset_selection()
    selected = _records(path, "kernel.select")
    assert [(r["impl"], r["requested"]) for r in selected] == [
        ("xla", "auto"), ("pallas", "pallas"), ("xla", "pallas"), ("xla", "pallas"),
        ("xla", "pallas"), ("xla", "pallas")]
    # the chunking is the operation's, said whichever path runs
    assert {k: selected[1][k] for k in (
        "chunk", "chunks_a_sequence", "heads", "groups", "state", "head_dim")} == {
        "chunk": CHUNK, "chunks_a_sequence": 3, "heads": 16, "groups": 1,
        "state": STATE, "head_dim": WIDTH}
    assert selected[2]["state"] == 6 and selected[0]["chunks_a_sequence"] == 3
    reasons = [r["reason"] for r in _records(path, "kernel.fallback")]
    assert len(reasons) == 6
    assert "platform cpu" in reasons[0]
    assert "a state of 6: no multiple of the 128 lanes" in reasons[1]
    assert "chunks of 32: not the 128 lanes" in reasons[2]
    assert "heads 48 wide: no divisor of the 128 lanes" in reasons[3]
    assert "4 heads of 64 a group: no whole 128-lane tiles" not in reasons[4]
    assert "4 heads: no multiple of the 8 sublanes" in reasons[4]
    assert "may span several devices" in reasons[5]


def test_the_vmem_asked_for_follows_the_blocks():
    # the cell: 16 heads of 64 on a 128 state in bf16
    for backward in (False, True):
        params = kernel._params(16, WIDTH, 1, STATE, CHUNK, jnp.bfloat16, backward)
        blocks = kernel._block_bytes(16, WIDTH, 1, STATE, CHUNK, jnp.bfloat16, backward)
        assert params.vmem_limit_bytes == blocks + kernel._VMEM_SLACK
        assert blocks <= kernel._VMEM_BUDGET < 128 * 2 ** 20
    # the whole mixer on one chip (128 heads on 8 groups) passes it
    assert "pass the VMEM's budget" in kernel.unsupported(
        CHUNK, 128, 8, STATE, WIDTH, jnp.bfloat16)
    assert not kernel.unsupported(CHUNK, 16, 1, STATE, WIDTH, jnp.bfloat16)
    assert not kernel.unsupported(CHUNK, 32, 2, STATE, WIDTH, jnp.float32)


def test_the_scan_has_no_knob():
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.telemetry import schema

    assert "ssd" in tier.KNOBLESS and "ssd" not in tier.KNOBS
    assert "ssd" in tier._NO_SHARD_MAP
    assert kernel.NAME in schema.KERNEL_NAMES
    assert not [key for key in cfg.KERNELS if "SSD" in key or "SCAN" in key]
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for name in ("ops/ssd.py", "ops/pallas/ssd.py"):
        text = open(os.path.join(here, "distribuuuu_tpu", name)).read()
        assert "environ" not in text and "cfg." not in text, name


def _calls_by_scope(jaxpr, outer=""):
    """``(kernel name, the whole name stack it was traced under)`` of every
    ``pallas_call`` in a jaxpr and the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        stack = f"{outer}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"], stack
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _calls_by_scope(inner, stack)


def test_the_nemotron_step_holds_both_calls_under_ssm_scan(monkeypatch):
    """The tiny preset with Mamba-2 heads the calls take (8 heads of 64 held
    on one group of a 128 state, one chunk of 128), the kernel forced
    interpreted: the step's gradient holds ``dtpu_ssd_fwd`` (the forward, and
    again with the entering states in each recomputed layer) and
    ``dtpu_ssd_bwd``, every one under ``ssm`` and ``ssm_scan``, which is where
    ``models.ssm_scan_ms_per_step`` and ``kernels.ssm_scan_roofline`` read
    them; and the step is the body's to float32's rounding."""
    import functools

    import flax

    from distribuuuu_tpu import models
    from distribuuuu_tpu.models import nemotron_h

    model = models.build_model(
        "nemotron_h_tiny", dtype=jnp.float32, mamba_heads=16, mamba_head_dim=WIDTH,
        state=STATE, chunk=CHUNK)
    keys = jax.random.split(jax.random.key(0), 2)
    ids = model.vocab_first + jax.random.randint(keys[0], (1, 129), 0, model.vocab_held)
    tokens, labels = ids[:, :-1], ids[:, 1:]
    state = flax.linen.meta.unbox(model.init(keys[1], tokens))

    def total(p):
        out = model.apply({"params": p, "batch_stats": state["batch_stats"]}, tokens,
                          hidden_only=True, train=False)
        return model.head_loss(out, model.head_kernel(p), labels, topk=(1, 5))[0]

    step = jax.value_and_grad(total)
    want = jax.jit(step)(state["params"])
    monkeypatch.setattr(nemotron_h, "ssd", functools.partial(op.ssd, interpret=True))
    jax.clear_caches()  # a recomputed block's trace is kept by function and shapes
    calls = list(_calls_by_scope(jax.make_jaxpr(step)(state["params"]).jaxpr))
    mamba_layers = model.layer_kinds.count("M")
    assert mamba_layers == 3
    names = [name for name, _ in calls if name.startswith(kernel.NAME)]
    assert sorted(set(names)) == [f"{kernel.NAME}_bwd", f"{kernel.NAME}_fwd"]
    assert names.count(f"{kernel.NAME}_bwd") == mamba_layers
    assert names.count(f"{kernel.NAME}_fwd") == 2 * mamba_layers  # forward, and again
    for name, stack in calls:
        if name.startswith(kernel.NAME):
            assert "ssm_scan" in stack and "ssm/" in stack.replace("ssm_scan", ""), (
                name, stack)
    got = jax.jit(step)(state["params"])
    jax.clear_caches()
    assert abs(float(got[0]) - float(want[0])) < 1e-5 * abs(float(want[0]))
    worst = max(jax.tree.leaves(jax.tree.map(
        lambda g, w: float(jnp.linalg.norm(g - w) / jnp.maximum(jnp.linalg.norm(w), 1e-30)),
        got[1], want[1])))
    assert worst < 1e-4, worst
