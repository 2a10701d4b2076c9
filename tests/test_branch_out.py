"""What a recomputed block keeps of its branches (``models/ouro.recomputed``,
``branch_out``): the output of each branch that the backward reads again, so
the second forward stops short of that branch's last matmul. One policy for
the four decoders that recompute: Ouro's and Trinity-Mini's sandwich-normed
blocks keep both branches' outputs (the norm after a branch reads it), GLM's
and LFM2's pre-norm blocks the mixer's alone (the sum the second norm reads
is made of it; nothing reads the FFN's). Under no checkpoint the name lowers
to nothing. The compiled steps for the v5e are ``tests/test_tpu_lowering.py``'s.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

import test_afmoe
import test_glm_moe
import test_lfm2_moe
import test_ouro
from distribuuuu_tpu.models import glm_moe, ouro, share


def _ouro():
    model = test_ouro.build(depth=2)
    params, tokens, labels = test_ouro.seeded(model, seq=40)
    return model, params, tokens, lambda m, p: test_ouro.program_loss(m, p, tokens, labels)[0]


def _shared(module, **kw):
    def make():
        model = module.build(**kw)
        params, biases, tokens, labels = module.seeded(model, seq=40)
        return model, params, tokens, lambda m, p: module.program_loss(
            m, p, biases, tokens, labels)[0]

    return make


# arch -> (model, params, tokens, loss(model, params)), the blocks a step
# applies and the branches a recomputed block keeps
ARCHS = {
    "ouro": (_ouro, lambda m: m.depth * m.passes, 2),
    "afmoe": (_shared(test_afmoe, depth=4), lambda m: len(m.layer_kinds), 2),
    "glm_moe": (_shared(test_glm_moe), lambda m: m.depth + m.mtp_layers, 1),
    "lfm2_moe": (_shared(test_lfm2_moe, recompute=True), lambda m: len(m.layer_kinds), 1),
}


@pytest.mark.parametrize("arch", ARCHS)
def test_the_step_that_keeps_its_branches_equals_the_step_that_keeps_everything(arch):
    """Loss and every gradient leaf with every block recomputed (its branches'
    outputs kept) against the same model with ``recompute=False``, to the
    tolerance the models' own recompute tests hold."""
    make, _, _ = ARCHS[arch]
    model, params, _, loss = make()
    assert model.recompute
    got, want = (jax.jit(jax.value_and_grad(lambda p, m=m: loss(m, p)))(params)
                 for m in (model, model.clone(recompute=False)))
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
    test_ouro.assert_trees_close(got[1], want[1], 1e-5)


@pytest.mark.parametrize("arch", ARCHS)
def test_a_recomputed_block_keeps_the_branches_the_backward_reads_and_no_other(arch):
    """What ``jax.checkpoint`` holds under the name, read off the residuals
    of the loss: ``[B, S, dim]`` a kept branch, both of a sandwich-normed
    block and the mixer's alone of a pre-norm block, whose FFN output is named
    too and kept by nothing (no reader). With the policy keeping nothing, none."""
    make, blocks, branches = ARCHS[arch]
    model, params, tokens, loss = make()

    def named(policy=None):
        with pytest.MonkeyPatch.context() as patch:
            if policy is not None:
                patch.setattr(jax.checkpoint_policies, "save_only_these_names",
                              lambda *names: policy)
            return [tuple(aval.shape) for aval, why in
                    saved_residuals(lambda p: loss(model, p), params)
                    if f"({ouro.branch_out.__name__})" in why]

    assert named() == [(*tokens.shape, model.dim)] * branches * blocks(model)
    assert named(jax.checkpoint_policies.nothing_saveable) == []


@pytest.mark.parametrize("arch", ARCHS)
def test_the_plan_counts_the_bytes_of_the_branches_kept(arch, tmp_path):
    """``kept_branch_bytes`` of the plan record = branches x tokens x dim x
    the compute dtype's size, inside ``kept_bytes``; None with nothing
    recomputed."""
    from distribuuuu_tpu.telemetry import schema, spans

    make, blocks, branches = ARCHS[arch]
    model, params, tokens, loss = make()
    for module in (ouro, glm_moe, share):
        module._planned.clear()
    spans.setup_telemetry(str(tmp_path), 0)
    try:
        for m in (model, model.clone(recompute=False)):
            jax.eval_shape(lambda p, m=m: loss(m, p), params)
    finally:
        spans.close_telemetry()
    plans = [r for name in sorted(p.name for p in tmp_path.iterdir())
             for r in map(json.loads, open(tmp_path / name))
             if r.get("kind") in ("loop.plan", "share.plan")]
    assert len(plans) == 2
    for plan in plans:
        schema.validate_record(plan)
    kept, nothing = plans
    size = tokens.size * model.dim * jnp.dtype(model.dtype).itemsize
    assert kept["kept_branch_bytes"] == branches * blocks(model) * size
    assert kept["kept_bytes"] == (  # the CPU's scan path names nothing of flash
        blocks(model) * tokens.size * model.dim * 4 + kept["kept_branch_bytes"])
    assert "branches that are read again" in kept["recomputed"]
    assert (nothing["kept_branch_bytes"], nothing["kept_bytes"],
            nothing["recomputed"]) == (None, None, "nothing")


@pytest.mark.parametrize("yaml, overrides, batch", [
    ("lfm2_24b_a2b", {"FIRST_LAYER": 1, "LAYERS": 5, "SHARE_CHIPS": 8, "RECOMPUTE": False},
     (2, 8192)),
    ("olmoe_1b_7b", {"LAYERS": 1}, (4, 4096)),
], ids=["lfm2", "olmoe"])
def test_the_cells_that_recompute_nothing_lower_to_the_step_without_the_names(
        yaml, overrides, batch, monkeypatch):
    """``lfm2_24b_a2b.train_seq8192`` (the same ``share.Block``, every
    activation kept) and ``olmoe_1b_7b.train_seq4096`` (no checkpoint, no
    named branch) at their cells' sizes: ``checkpoint_name`` leaves a ``name``
    equation in the jaxpr and NOTHING in the lowered program, which is,
    character for character, the one lowered with the naming taken out."""
    import distribuuuu_tpu.config as config
    from distribuuuu_tpu import trainer
    from distribuuuu_tpu.config import cfg
    from distribuuuu_tpu.parallel import mesh as mesh_lib
    from distribuuuu_tpu.parallel.partition import lowering, topology
    from distribuuuu_tpu.utils.optim import construct_optimizer

    def lowered_text():
        config.reset_cfg()
        config.merge_from_file(f"config/{yaml}.yaml")
        for key, value in overrides.items():
            setattr(cfg.LM, key, value)
        cfg.MESH.DATA = 1
        try:
            layout = topology.from_cfg(cfg, n_devices=1)
            low = lowering.lower(
                trainer.build_model_from_cfg(layout), construct_optimizer(), 5,
                mesh=mesh_lib.build_mesh(data=1, devices=jax.devices()[:1]),
                topology=layout, im_size=cfg.TRAIN.IM_SIZE)
            state, avals = low.abstract_args(batch[0])
        finally:
            config.reset_cfg()
        avals = {k: jax.ShapeDtypeStruct(batch, v.dtype, sharding=v.sharding)
                 for k, v in avals.items()}
        traced = low.train_step.trace(state, avals)
        return str(traced.jaxpr).count("name=branch_out"), traced.lower().as_text()

    names, text = lowered_text()
    assert names == {"lfm2_24b_a2b": 2 * 5, "olmoe_1b_7b": 0}[yaml]
    for module in (ouro, glm_moe, share):
        monkeypatch.setattr(module, "branch_out", lambda x: x)
    bare_names, bare = lowered_text()
    assert bare_names == 0 and "branch_out" not in text
    assert text == bare
