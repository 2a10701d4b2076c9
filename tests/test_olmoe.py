"""OLMoE on the normal path, against the benchmark's plain reference
(``benchmark/reference/olmoe.py``), at sizes the CPU runs: hidden 64, 4 heads
of 16, 8 experts of width 32 with 2 a token (and 16 with 8), vocab 512, 128
tokens and a length that is no multiple of the loss chunk. The contracts it
answers are ``tests/decoder_contract.py``'s; below them, what only OLMoE has
or was first to test: every expert held and none dropped, the chunked head
(``ops/token_head.py``), the step with and without chunks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_contract as contract
from decoder_contract import CHUNK, VOCAB, olmoe_terms, olmoe_total, walk, wide_matmuls
from distribuuuu_tpu import models
from distribuuuu_tpu.ops import moe as moe_ops
from distribuuuu_tpu.ops import token_head
from distribuuuu_tpu.parallel import mesh as mesh_lib

ROW = contract.ROWS["olmoe"]
reference = ROW.reference
SIZES = {name: kw for name, (kw, _) in ROW.gradient_cases.items()}  # (experts, per token)


def build(size="top2of8", **kw):
    return contract.build(ROW, **SIZES[size], **kw)


def seeded(model, batch=2, seq=128, seed=0):
    """(params, tokens, labels) of the contract's ``seeded``, 128 tokens long."""
    params, _, tokens, labels = contract.seeded(model, batch, seq, seed)
    return params, tokens, labels


def _agreement(model, got, want):
    """Share of the (token, slot) pairs whose expert is in the other set."""
    got, want = (np.asarray(x).reshape(-1, model.top_k) for x in (got, want))
    return float((got[:, :, None] == want[:, None, :]).any(-1).mean())


class TestOLMoE(contract.Decoder, contract.RecomputesNothingInItsCell,
                contract.ComputesInBfloat16):
    row = ROW

    def bfloat16_of_its_own(self, model16, params, tokens, labels, got, want, arch,
                            monkeypatch):
        """What each tolerance catches. Readings at this size (4 x 128 tokens):

        * cross-entropy, relative: program 4e-6; the float32 program 1e-7 (so it
          passes 2e-4 1000x inside); the reference run in bfloat16 THROUGHOUT
          (router, norms, softmaxes, loss too) 8e-4. A log-sum-exp over 512
          logits near 6.2 cannot be held in 8 bits. 2e-4 separates them.
        * experts chosen, share of (token, slot) pairs whose expert the
          reference chose too: program 0.9985 (the matmuls upstream read
          bfloat16, so a near-tie at the k-th place can fall the other way); the
          same program with a bfloat16 ROUTER 0.984. 0.995 separates them; the
          float32 program scores 1.0.
        * logits: rms error under 1 % of their spread (program 0.6 %, float32
          program 2e-5 %). This one does NOT tell a bfloat16 router or bfloat16
          scores apart (0.8 % for the all-bfloat16 reference): matmul input
          rounding dominates a logit. The two checks above are the ones with
          teeth."""
        assert _agreement(model16, got.extra["experts"], want["experts"]) >= 0.995
        monkeypatch.setattr(
            moe_ops, "gating_probs",
            lambda x, w: jax.nn.softmax(
                x.astype(jnp.bfloat16) @ w.astype(jnp.bfloat16), axis=-1
            ).astype(jnp.float32),
        )
        routed_low = olmoe_terms(model16, params, tokens, labels)
        assert _agreement(model16, routed_low["experts"], want["experts"]) < 0.995
        monkeypatch.undo()
        logits = model16.apply({"params": params}, tokens)
        assert logits.dtype == jnp.float32  # the head accumulates and returns float32
        plain = reference.logits(params, tokens, architecture=arch)
        assert float(jnp.sqrt(jnp.mean((logits - plain) ** 2))) < 0.01 * float(plain.std())


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("seq", [128, 100])
def test_float32_logits_and_experts_equal_the_reference(size, seq):
    model = build(size)
    params, tokens, labels = seeded(model, seq=seq)
    got = model.apply({"params": params}, tokens)
    want, _, chosen = reference.forward(
        params, tokens, architecture=ROW.architecture(model))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    terms = olmoe_terms(model, params, tokens, labels)
    # the SET of experts of every token, layer by layer
    assert np.array_equal(
        np.sort(terms["experts"].reshape(model.depth, -1, model.top_k), -1),
        np.sort(np.stack(chosen), -1),
    )
    assert float(terms["dropped"]) == 0.0


def _head_inputs(dtype=jnp.float32):
    """100 tokens a sequence: chunks of 48 leave a ragged last chunk of 4."""
    k = jax.random.split(jax.random.key(3), 4)
    hidden = jax.random.normal(k[0], (3, 100, 64)).astype(dtype)
    kernel = jax.random.normal(k[1], (64, VOCAB)) * 0.3
    labels = jax.random.randint(k[2], (3, 100), 0, VOCAB)
    return hidden, kernel, labels, k[3]


def test_chunked_head_equals_the_whole_head():
    """Loss, hits and gradients of head and hidden state: chunks of 48 of a
    100-token sequence (padded to 144) against one block."""
    hidden, kernel, labels, _ = _head_inputs()
    mean = jnp.full(labels.shape, 1.0 / labels.size)

    def loss(hidden, kernel, chunk):
        loss, (_, rank) = token_head.weighted_loss(hidden, kernel, labels, mean, chunk=chunk)
        return loss, rank

    (whole, rank_whole), g_whole = jax.value_and_grad(loss, (0, 1), has_aux=True)(hidden, kernel, 0)
    (parts, rank_parts), g_parts = jax.value_and_grad(loss, (0, 1), has_aux=True)(hidden, kernel, CHUNK)
    np.testing.assert_allclose(parts, whole, rtol=1e-6)
    assert np.array_equal(rank_parts, rank_whole)
    for a, b in zip(g_parts, g_whole):
        np.testing.assert_allclose(a, b, atol=1e-6)
    # evaluation's walk gives the same statistics
    nll, rank = token_head.head_stats(hidden, kernel, labels, chunk=CHUNK)
    np.testing.assert_allclose(nll.mean(), whole, rtol=1e-6)
    assert np.array_equal(rank, rank_whole)
    # the rank is lax.top_k's order: hits equal utils.metrics.accuracy's
    from distribuuuu_tpu.utils.metrics import accuracy, cross_entropy

    logits = hidden @ kernel
    acc1, acc5 = accuracy(logits, labels, topk=(1, 5))
    assert float((rank_whole < 1).mean() * 100) == pytest.approx(float(acc1))
    assert float((rank_whole < 5).mean() * 100) == pytest.approx(float(acc5))
    np.testing.assert_allclose(whole, cross_entropy(logits, labels), rtol=1e-6)


def _vocabulary_wide_matmuls(jaxpr) -> int:
    """``dot_general``s with the vocabulary among their dimensions."""
    return len(wide_matmuls(jaxpr, VOCAB))


@pytest.mark.parametrize("chunk,chunks", [(CHUNK, 3), (0, 1)])
def test_each_chunks_logits_are_computed_once(chunk, chunks):
    """From the traced programs: the differentiated head has three
    vocabulary-wide matmuls a chunk (logits, dX, dW; a recomputing backward
    has four) and the evaluated head one, on either entry."""
    hidden, kernel, labels, _ = _head_inputs()
    mean = jnp.full(labels.shape, 1.0 / labels.size)

    def loss(hidden, kernel):
        return token_head.weighted_loss(hidden, kernel, labels, mean, chunk=chunk)[0]

    def stats(hidden, kernel):
        return token_head.head_stats(hidden, kernel, labels, chunk=chunk)

    differentiated = jax.make_jaxpr(jax.value_and_grad(loss, (0, 1)))(hidden, kernel)
    assert _vocabulary_wide_matmuls(differentiated.jaxpr) == 3 * chunks
    for evaluated in (loss, stats):
        assert _vocabulary_wide_matmuls(
            jax.make_jaxpr(evaluated)(hidden, kernel).jaxpr) == chunks


def _weights(kind, key, shape):
    if kind == "mask":  # a 0/1 mask over the count it keeps
        mask = jax.random.bernoulli(key, 0.7, shape).astype(jnp.float32)
        return mask / mask.sum()
    if kind == "arbitrary":  # either sign, as a per-token cotangent may be
        return jax.random.normal(key, shape)
    return jnp.full(shape, 1.0 / (shape[0] * shape[1]))


@pytest.mark.parametrize("dtype,tolerance", [("float32", 2e-6), ("bfloat16", 8e-3)])
@pytest.mark.parametrize("kind,cotangent", [
    ("uniform", 1.0), ("mask", 1.0), ("arbitrary", 1.0), ("uniform", 3.0),
])
def test_weighted_loss_equals_autodiff_on_the_full_logits(kind, cotangent, dtype, tolerance):
    """Value and both gradients against plain autodiff through
    ``utils.metrics.cross_entropy`` on the full logits, with a ragged last
    chunk. bfloat16's tolerance is the rounding of the logits' cotangent to
    8 bits, relative to the gradient's largest entry."""
    from distribuuuu_tpu.utils.metrics import cross_entropy

    hidden, kernel, labels, key = _head_inputs(jnp.dtype(dtype))
    weights = _weights(kind, key, labels.shape)

    def program(hidden, kernel):
        loss, (nll, _) = token_head.weighted_loss(
            hidden, kernel, labels, weights, chunk=CHUNK)
        # nll is a statistic: adding it moves the value, not the gradient
        return cotangent * loss + nll.sum(), nll.sum()

    def plain(hidden, kernel):
        logits = jnp.einsum("bsd,dv->bsv", hidden, kernel.astype(hidden.dtype),
                            preferred_element_type=jnp.float32)
        nll = jax.vmap(jax.vmap(
            lambda row, label: cross_entropy(row[None], label[None])))(logits, labels)
        return cotangent * (nll * weights).sum()

    (got, added), got_grads = jax.value_and_grad(program, (0, 1), has_aux=True)(hidden, kernel)
    want, want_grads = jax.value_and_grad(plain, (0, 1))(hidden, kernel)
    # (the subtraction rounds at float32's step at the sum's size)
    np.testing.assert_allclose(got - added, want, rtol=1e-5, atol=1e-6 * float(added))
    for g, w in zip(got_grads, want_grads):
        assert g.dtype == w.dtype and g.shape == w.shape
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(g - w).max() <= tolerance * np.abs(w).max()


def test_rank_breaks_ties_as_top_k_does():
    hidden = jnp.ones((1, 2, 1))
    kernel = jnp.asarray([[1.0, 2.0, 2.0, 2.0, 0.5]])
    _, rank = token_head.head_stats(hidden, kernel, jnp.asarray([[2, 0]]), chunk=0)
    assert rank.tolist() == [[1, 3]]  # one equal logit at a lower index; three larger


def test_the_step_chunked_equals_the_step_unchunked_and_the_reference():
    """Through ``lowering.lower`` on the 8-device data mesh: the same metrics
    keys and values and the same updated parameters with ``head_chunk`` 48 and 0;
    the step's loss is ce + 0.01 aux + 0.001 z of the reference."""
    out = {}
    ids = np.random.default_rng(1).integers(0, VOCAB, (8, 101)).astype(np.int32)
    host = {"image": ids[:, :-1], "label": ids[:, 1:], "mask": np.ones(8, np.float32)}
    for chunk in (CHUNK, 0):
        low = contract.lowered(ROW, chunk)
        state = low.init_state(jax.random.key(0), 32)
        params = jax.device_get(state.params)
        batch = low.put_batch(host)
        # the step holds three vocabulary-wide matmuls a chunk, evaluation one
        chunks = -(-100 // chunk) if chunk else 1
        assert _vocabulary_wide_matmuls(jax.make_jaxpr(low.train_step)(
            state, {k: batch[k] for k in ("image", "label")}).jaxpr) == 3 * chunks
        assert _vocabulary_wide_matmuls(
            jax.make_jaxpr(low.eval_step)(state, batch).jaxpr) == chunks
        evaluated = jax.device_get(low.eval_step(state, batch))
        state, metrics = low.train_step(state, {k: batch[k] for k in ("image", "label")})
        out[chunk] = jax.device_get((metrics, state.params, evaluated))
    (m_parts, p_parts, e_parts), (m_whole, p_whole, e_whole) = out[CHUNK], out[0]
    assert set(m_parts) == set(m_whole) >= {
        "loss", "top1", "topk", "ce", "moe_aux", "moe_z", "moe_dropped",
        "moe_load_max_over_mean",
    }
    for key in m_parts:
        np.testing.assert_allclose(m_parts[key], m_whole[key], rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(p_parts), jax.tree.leaves(p_whole)):
        np.testing.assert_allclose(a, b, atol=2e-6)
    for key in e_parts:
        np.testing.assert_allclose(e_parts[key], e_whole[key], rtol=1e-5)
    assert float(e_parts["count"]) == 8 * 100
    assert float(m_parts["moe_dropped"]) == 0.0
    want = reference.loss(params, host["image"], host["label"],
                          architecture=ROW.architecture(build(seq_len=100)))
    np.testing.assert_allclose(m_parts["ce"], want["ce"], rtol=1e-5)
    np.testing.assert_allclose(m_parts["moe_aux"], want["load_balance"], rtol=1e-5)
    np.testing.assert_allclose(m_parts["moe_z"], want["router_z"], rtol=1e-5)
    np.testing.assert_allclose(m_parts["loss"], olmoe_total(want), rtol=1e-5)


def test_nothing_is_dropped_when_one_expert_takes_half_of_all_assignments():
    """A router biased so that every token's first choice is expert 3: it
    receives T of the 2T assignments, 4x the mean of 8 experts, and the
    output still equals the reference's, which has no capacity to overflow."""
    model = build()
    params, tokens, labels = seeded(model)
    # a constant feature every token carries (8 after the norm: it dwarfs
    # the 0.02-wide rest), and a router column that reads it
    params["tok_embed"]["embedding"] = params["tok_embed"]["embedding"].at[:, 1].set(1.0)
    for i in range(model.depth):
        block = params[f"Block_{i}"]
        block["moe_norm"]["scale"] = block["moe_norm"]["scale"].at[1].set(1.0)
        block["moe"]["router"] = block["moe"]["router"].at[1, 3].set(20.0)
    terms = olmoe_terms(model, params, tokens, labels)
    experts = np.asarray(terms["experts"]).reshape(model.depth, -1, model.top_k)
    assert (experts == 3).any(axis=-1).all()  # every token, every layer
    counts = moe_ops.expert_counts(jnp.asarray(experts[0]), model.num_experts)
    assert int(counts[3]) == experts.shape[1] == int(counts.sum()) // 2
    assert float(moe_ops.load_max_over_mean(counts)) == pytest.approx(4.0)
    assert float(terms["dropped"]) == 0.0
    want = reference.loss(params, tokens, labels, architecture=ROW.architecture(model))
    np.testing.assert_allclose(terms["ce"], want["ce"], rtol=1e-5)
    np.testing.assert_allclose(
        model.apply({"params": params}, tokens),
        reference.logits(params, tokens, architecture=ROW.architecture(model)),
        atol=2e-5,
    )


def test_expert_matmuls_take_top_k_rows_a_token_not_one_an_expert():
    """From the traced program, forward and backward: every grouped matmul
    takes tokens x top_k rows, and no value anywhere has a tokens x experts x
    width shape (what 'every expert on every token' would build)."""
    model = build("top8of16")
    params, tokens, labels = seeded(model, batch=2, seq=128)
    T, k, E = 2 * 128, model.top_k, model.num_experts

    def loss(p):
        return olmoe_total(olmoe_terms(model, p, tokens, labels))

    eqns = list(walk(jax.make_jaxpr(jax.grad(loss))(params).jaxpr))
    grouped = [e for e in eqns if "ragged_dot" in e.primitive.name]
    assert len(grouped) >= 3 * 3 * model.depth  # three matmuls, fwd + two bwd
    for eqn in grouped:
        rows = {v.aval.shape[0] for v in list(eqn.invars[:2]) + list(eqn.outvars)
                if v.aval.ndim == 2}
        assert rows == {T * k}, (eqn.primitive.name, rows)
    widths = (model.expert_hidden, model.dim)
    for eqn in eqns:
        for var in eqn.outvars:
            shape = tuple(getattr(var.aval, "shape", ()))
            assert not (len(shape) >= 3 and shape[-3:-1] == (T, E)
                        and shape[-1] in widths), (eqn.primitive.name, shape)
            assert not (len(shape) >= 3 and shape[-3] == E and shape[-2] == T
                        and shape[-1] in widths), (eqn.primitive.name, shape)


def test_sorted_experts_alone_match_a_dense_loop():
    k = jax.random.split(jax.random.key(5), 5)
    d, f, E, top = 32, 24, 8, 2
    params = {
        "router": jax.random.normal(k[0], (d, E)) * 0.5,
        "w_gate": jax.random.normal(k[1], (E, d, f)) * 0.2,
        "w_up": jax.random.normal(k[2], (E, d, f)) * 0.2,
        "w_down": jax.random.normal(k[3], (E, f, d)) * 0.2,
    }
    x = jax.random.normal(k[4], (2, 16, d))
    out, route = moe_ops.moe_ffn_sorted(params, x, top_k=top)
    weights, indices = moe_ops.top_k_as_is(route["probs"], top)
    assert float(weights.sum(-1).max()) < 1.0  # as they are: not renormalized
    assert int(route["counts"].sum()) == 2 * 16 * top
    flat = x.reshape(-1, d)
    want = sum(
        ((jax.nn.silu(flat @ params["w_gate"][e]) * (flat @ params["w_up"][e]))
         @ params["w_down"][e]) * (weights * (indices == e)).sum(-1)[:, None]
        for e in range(E)
    )
    np.testing.assert_allclose(out.reshape(-1, d), want, atol=1e-6)


def test_the_generation_plane_refuses_the_arch_too():
    """``serve_net.py``'s refusal is the contract's; the engine under it
    (``lm/service.py``) says which archs it mirrors."""
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.lm import service

    cfg.MODEL.ARCH = "olmoe_tiny"
    with pytest.raises(ValueError, match="gpt_\\* archs"):
        service.engine_from_cfg()


@pytest.mark.parametrize("arch,want", [
    ("gpt_nano_moe", dict(token_batch=True, batch_norm=False, mesh_axes=None)),
    ("vit_tiny", dict(token_batch=False, batch_norm=False, mesh_axes=None)),
    ("resnet50", dict(token_batch=False, batch_norm=True, mesh_axes=None)),
    ("an_arch_the_zoo_lacks", dict(token_batch=False, batch_norm=True, mesh_axes=None)),
])
def test_an_arch_that_declares_nothing_gets_its_familys_traits(arch, want):
    """``models.traits`` beside the decoders (whose own declarations the
    contract reads): an arch of the older families declares nothing and is
    read by what it is."""
    from distribuuuu_tpu.parallel.partition import specs

    got = models.traits(arch)
    assert {k: getattr(got, k) for k in want} == want
    assert specs.is_token_arch(arch) is want["token_batch"]
    assert not got.serve_refusal and got.kwargs_from_cfg is None


def test_flash_attention_runs_per_data_rank_on_a_mesh():
    """With the caller's mesh the entry shards the batch over ``data`` and
    runs per rank (here, off the TPU, each rank takes the scan); values and
    gradients equal the unsharded call's. That the per-rank program holds
    the Mosaic kernel is ``tests/test_tpu_lowering.py``'s."""
    from distribuuuu_tpu.ops import flash_attention as fa

    mesh = mesh_lib.build_mesh(data=8)
    q, k, v = (jax.random.normal(key, (8, 2, 64, 16))
               for key in jax.random.split(jax.random.key(3), 3))

    def loss(mesh):
        return lambda q, k, v: jnp.sum(
            fa.flash_attention(q, k, v, causal=True, mesh=mesh) ** 2)

    got = jax.jit(jax.value_and_grad(loss(mesh), argnums=(0, 1, 2)))(q, k, v)
    want = jax.value_and_grad(loss(None), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
