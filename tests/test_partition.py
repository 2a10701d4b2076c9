"""Unit tier of the partition layer (parallel/partition/, ISSUE 9):
spec-table algebra, topology registry validation/classification, and the
generated-sweep containment of the legacy dryrun matrix."""

import os
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import distribuuuu_tpu.config as config
from distribuuuu_tpu.config import cfg
from distribuuuu_tpu import trainer
from distribuuuu_tpu.parallel import mesh as mesh_lib
from distribuuuu_tpu.parallel.partition import specs, topology

TOOLS = os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools")


# ------------------------------------------------------------- spec table


def test_spec_table_unknown_leaf_refused():
    table = specs.SpecTable(
        rules=(specs.SpecRule(r"kernel$", P(None, "model")),), strict=True
    )
    assert table.spec_for("/Dense_0/kernel") == P(None, "model")
    with pytest.raises(specs.UnknownLeafError, match="no spec rule covers"):
        table.spec_for("/Dense_0/bias")


def test_spec_table_default_when_not_strict():
    table = specs.SpecTable(
        rules=(specs.SpecRule(r"kernel$", P(None, "model")),), default=P()
    )
    assert table.spec_for("/whatever") == P()


def test_batch_table_covers_loader_keys_and_refuses_strangers():
    for key in ("image", "label", "mask"):
        assert specs.BATCH_TABLE.spec_for(f"['{key}']") == P("data")
    with pytest.raises(specs.UnknownLeafError):
        specs.BATCH_TABLE.spec_for("['surprise_key']")
    # accum stacking shifts the batch dim right
    assert specs.batch_spec("image", leading_dims=1) == P(None, "data")


def test_validate_leaf_spec_conflicting_axes():
    sizes = {"data": 4, "model": 2}
    # same axis on two dims
    with pytest.raises(specs.SpecConflictError, match="at most one dim"):
        specs.validate_leaf_spec(
            "/w", P("data", ("model", "data")), (8, 8), sizes
        )
    # more entries than dims
    with pytest.raises(specs.SpecConflictError, match="rank"):
        specs.validate_leaf_spec("/w", P("data", None, None), (8, 8), sizes)
    # unknown axis
    with pytest.raises(specs.SpecConflictError, match="does not exist"):
        specs.validate_leaf_spec("/w", P("bogus"), (8,), sizes)
    # clean specs pass; a non-divisible extent is LEGAL (GSPMD pads it —
    # e.g. a 10-class head kernel on a 4-way model axis)
    specs.validate_leaf_spec("/w", P(None, ("model", "data")), (3, 8), sizes)
    specs.validate_leaf_spec("/w", P("data"), (6, 8), sizes)


def test_collapse_unit_axes_to_replication():
    # a size-1 axis shards nothing: the TP annotation IS replication on a
    # dp-only mesh
    assert specs.collapse_unit_axes(
        P(None, "model"), {"model": 1, "data": 8}
    ) == P(None, None)
    assert specs.canonicalize(
        P(None, "model"), {"model": 1, "data": 8}
    ) == P()
    # mixed tuple entry: the unit axis drops out of the tuple
    assert specs.collapse_unit_axes(
        P(("model", "data")), {"model": 1, "data": 8}
    ) == P("data")
    # populated axes survive canonicalization
    assert specs.canonicalize(
        P("data", None, "model"), {"model": 2, "data": 4}
    ) == P("data", None, "model")


# -------------------------------------------------------- topology registry


def test_from_cfg_resolves_wildcards_and_classifies():
    config.reset_cfg()
    cfg.MODEL.ARCH = "resnet18"
    topo = topology.from_cfg(cfg, n_devices=8)
    assert topo.axes == {
        "data": 8, "model": 1, "seq": 1, "pipe": 1, "expert": 1
    }
    assert topo.class_name() == "dp8"
    cfg.MESH.DATA, cfg.MESH.MODEL, cfg.MESH.ZERO = -1, 2, 1
    topo = topology.from_cfg(cfg, n_devices=8)
    assert (topo.data, topo.model, topo.zero) == (4, 2, 1)
    assert topo.class_name() == "dp4·tp2·zero1"
    assert topo.describe()["features"] == ["dp", "tp", "zero1"]


def test_registry_refuses_invalid_stanzas():
    config.reset_cfg()
    cases = [
        # (overrides, error fragment)
        ({"MESH.ZERO": 2}, "stage 2 is"),
        ({"MODEL.ARCH": "resnet18", "MESH.PIPE": 2}, "uniform-stage"),
        ({"MODEL.ARCH": "resnet18", "MESH.SEQ": 2}, "MESH.SEQ"),
        ({"MODEL.ARCH": "vit_tiny", "MESH.PIPE": 2, "MESH.SEQ": 2},
         "does not compose with the pipe axis"),
        ({"MODEL.ARCH": "vit_tiny", "MESH.EXPERT": 2}, "only the \\*_moe"),
        ({"MODEL.ARCH": "vit_tiny_moe", "MESH.EXPERT": 8,
          "MODEL.MOE.NUM_EXPERTS": 6}, "must divide MODEL.MOE.NUM_EXPERTS"),
        ({"MODEL.ARCH": "vit_tiny", "MESH.PIPE": 8}, "not divisible by"),
    ]
    for overrides, frag in cases:
        config.reset_cfg()
        flat = [x for kv in overrides.items() for x in kv]
        cfg.merge_from_list(list(map(str, flat)))
        with pytest.raises(ValueError, match=frag):
            topology.from_cfg(cfg, n_devices=8)
    config.reset_cfg()


def test_zero3_under_pp_and_three_axis_ep_now_validate():
    """The ISSUE 9 acceptance stanzas — refused or pathless before r11 —
    must pass the registry."""
    config.reset_cfg()
    cfg.MODEL.ARCH = "vit_tiny"
    cfg.MESH.DATA, cfg.MESH.PIPE, cfg.MESH.ZERO = 2, 4, 3
    topo = topology.from_cfg(cfg, n_devices=8)
    assert set(topo.describe()["features"]) == {"dp", "pp", "zero3"}
    config.reset_cfg()
    cfg.MODEL.ARCH = "vit_tiny_moe"
    cfg.MESH.DATA = cfg.MESH.MODEL = cfg.MESH.EXPERT = 2
    cfg.MESH.ZERO = 1
    topo = topology.from_cfg(cfg, n_devices=8)
    assert set(topo.describe()["features"]) == {"dp", "tp", "ep", "zero1"}
    assert topo.moe_axis() == "expert"


def test_check_trainer_mesh_delegates_to_registry():
    config.reset_cfg()
    cfg.MODEL.ARCH = "resnet18"
    topo = trainer.check_trainer_mesh()
    assert topo.class_name() == "dp8"


def test_enumeration_contains_legacy_matrix():
    """Every case the pre-r11 dryrun hand-enumerated appears in the
    generated sweep (the ISSUE 9 satellite's containment contract)."""
    sys.path.insert(0, TOOLS)
    try:
        import mesh_sweep
    finally:
        sys.path.remove(TOOLS)

    cases = mesh_sweep.generate_cases(8)
    keys = {
        mesh_sweep._case_key(c["axes"], c["zero"], c["arch"]) for c in cases
    }
    for legacy in mesh_sweep.legacy_matrix(8):
        k = mesh_sweep._case_key(
            legacy["axes"], legacy["zero"], legacy["arch"]
        )
        assert k in keys, f"legacy case missing from generated set: {legacy}"
    # ... and the acceptance compositions ride as CORE cases
    core = {c["name"] for c in cases if c["tier"] == "core"}
    assert "dp2·pp4·zero3[vit_tiny]" in core
    assert "dp2·tp2·ep2·zero1[vit_tiny_moe]" in core
    # legacy ride-along variants survive as generated extras
    by_name = {c["name"]: c for c in cases}
    assert "accum" in by_name["dp4·tp2[resnet18]"]["extras"]
    assert "aux_check" in by_name["dp2·tp2·pp2[vit_tiny_moe]"]["extras"]
    assert "flash" in by_name["dp2·pp4[vit_tiny]"]["extras"]


def test_classify_transition_details_axis_moves():
    a = topology.Topology(data=4, model=2, zero=1).describe()
    b = topology.Topology(data=2, model=2, zero=1).describe()
    kind, detail = topology.classify_transition(a, b)
    assert kind == "reshardable"
    assert "data 4→2" in detail and "dp4·tp2·zero1→dp2·tp2·zero1" in detail
    assert topology.classify_transition(a, a) == ("exact", "")
    kind, detail = topology.classify_transition(
        topology.Topology(data=8).describe(),
        topology.Topology(data=8, zero=3).describe(),
    )
    assert kind == "reshardable" and "zero 0→3" in detail


# -------------------------------------------------- layout via the spec layer


def test_state_layout_matches_trainer_delegation():
    """trainer._state_layout IS the partition spec layer now — one
    resolver; the layouts agree leaf for leaf."""
    config.reset_cfg()
    cfg.MODEL.ARCH = "resnet18"
    cfg.MODEL.NUM_CLASSES = 10
    cfg.MESH.ZERO = 1
    mesh = mesh_lib.mesh_from_cfg(cfg)
    model = trainer.build_model_from_cfg()
    a = trainer._state_layout(model, mesh, 32)
    b = specs.state_layout(model, mesh, 32, zero_stage=1)
    for key in ("params", "opt", "grads"):
        la, lb = jax.tree.leaves(a[key]), jax.tree.leaves(b[key])
        assert len(la) == len(lb)
        assert all(x == y for x, y in zip(la, lb))
    # the ZeRO transform added exactly the data axis
    assert specs.added_axes(b) == ("data",)


def test_state_layout_validates_derived_specs():
    """A malformed derivation cannot reach GSPMD: validation raises with
    the leaf path."""
    config.reset_cfg()
    cfg.MODEL.ARCH = "resnet18"
    cfg.MODEL.NUM_CLASSES = 10
    mesh = mesh_lib.mesh_from_cfg(cfg)
    model = trainer.build_model_from_cfg()
    layout = specs.state_layout(model, mesh, 32, zero_stage=0)
    # sanity: the base layout is fully replicated over data at rest
    for leaf in jax.tree.leaves(layout["params"]):
        assert "data" not in specs.spec_axes(leaf.spec)


def test_mesh_expert_axis_exists_and_collapses():
    """The new expert axis is first-class on every mesh and inert at
    size 1 (axis-size-1 collapse: existing topologies see no change)."""
    mesh = mesh_lib.build_mesh()
    assert dict(mesh.shape)["expert"] == 1
    assert mesh_lib.MESH_AXES == ("data", "model", "seq", "pipe", "expert")
    sizes = mesh_lib.resolve_axis_sizes([-1, 2, 1, 1, 2], 8)
    assert sizes == [2, 2, 1, 1, 2]
    with pytest.raises(ValueError, match="do not divide"):
        mesh_lib.resolve_axis_sizes([3, 1, 1, 1, 1], 8)
