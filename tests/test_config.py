"""Config system tests (semantics per ref /root/reference/distribuuuu/config.py)."""

import glob
import os

import pytest
import yaml

from distribuuuu_tpu import config
from distribuuuu_tpu.config import CfgNode, cfg

CONFIG_DIR = os.path.join(os.path.dirname(__file__), "..", "config")


def _arch_yamls():
    """config/ also ships non-arch YAMLs (the monitor's alert rules —
    validated by tests/test_monitor.py instead); only files in the cfg
    schema (a MODEL node) go through the merge path here."""
    out = []
    for path in sorted(glob.glob(os.path.join(CONFIG_DIR, "*.yaml"))):
        with open(path) as f:
            doc = yaml.safe_load(f) or {}
        if "MODEL" in doc:
            out.append(path)
    return out


def test_defaults_tree():
    assert cfg.MODEL.ARCH == "resnet18"
    assert cfg.MODEL.NUM_CLASSES == 1000
    assert cfg.OPTIM.MOMENTUM == 0.9
    assert cfg.OPTIM.NESTEROV is True
    assert cfg.TRAIN.IM_SIZE == 224
    assert cfg.TEST.IM_SIZE == 256
    assert cfg.RNG_SEED is None


@pytest.mark.parametrize("path", _arch_yamls())
def test_all_shipped_yamls_parse(path):
    config.merge_from_file(path)
    stem = os.path.splitext(os.path.basename(path))[0]
    # a stanza is named for its arch, or is an {arch}_{mesh-variant}
    # recipe of the same arch (config/gpt_nano_sp.yaml — same model,
    # only the MESH stanza moves); either way OUT_DIR tracks the stem
    # so two shipped recipes never write into each other's run dir
    assert stem == cfg.MODEL.ARCH or stem.startswith(cfg.MODEL.ARCH + "_")
    assert cfg.OUT_DIR == f"./{stem}"


def test_reference_schema_parses_unchanged(tmp_path):
    """A YAML in the reference's exact schema (incl. CUDNN keys) must merge."""
    y = tmp_path / "ref.yaml"
    y.write_text(
        "CUDNN:\n  BENCHMARK: true\n  DETERMINISTIC: false\n"
        "MODEL:\n  ARCH: resnet50\n  WEIGHTS: null\n"
        "OPTIM:\n  BASE_LR: 0.2\n  STEPS: [30, 60, 90]\n"
        "RNG_SEED: null\n"
    )
    config.merge_from_file(str(y))
    assert cfg.MODEL.ARCH == "resnet50"
    assert cfg.CUDNN.BENCHMARK is True
    assert cfg.OPTIM.STEPS == [30, 60, 90]


def test_merge_from_list_typed():
    cfg.merge_from_list(["OPTIM.BASE_LR", "0.4", "TRAIN.BATCH_SIZE", "64"])
    assert cfg.OPTIM.BASE_LR == 0.4
    assert cfg.TRAIN.BATCH_SIZE == 64
    # None-slot accepts str and int
    cfg.merge_from_list(["MODEL.WEIGHTS", "w.ckpt", "RNG_SEED", "3"])
    assert cfg.MODEL.WEIGHTS == "w.ckpt"
    assert cfg.RNG_SEED == 3


@pytest.mark.parametrize("text", ["3e-05", "3e-5", "3.0e-05", str(3e-5)])
def test_merge_from_list_takes_a_float_written_with_an_exponent(text):
    """Python writes 0.00003 as ``3e-05`` and yaml 1.1 reads that as a string:
    a float slot takes it all the same (the benchmark's drivers hand a rate
    over as ``str(rate)``), and still refuses what is no number."""
    cfg.merge_from_list(["OPTIM.BASE_LR", text])
    assert cfg.OPTIM.BASE_LR == 3e-5
    with pytest.raises(ValueError):
        cfg.merge_from_list(["OPTIM.BASE_LR", "fast"])


def test_merge_rejects_unknown_key():
    with pytest.raises(KeyError):
        cfg.merge_from_list(["NOPE.KEY", "1"])


def test_merge_rejects_type_mismatch():
    with pytest.raises(ValueError):
        cfg.merge_from_list(["MODEL.ARCH", "[1,2]"])


def test_freeze_blocks_writes():
    cfg.freeze()
    with pytest.raises(AttributeError):
        cfg.MODEL.ARCH = "x"
    cfg.defrost()
    cfg.MODEL.ARCH = "resnet34"
    assert cfg.MODEL.ARCH == "resnet34"


def test_dump_roundtrip(tmp_path):
    cfg.defrost()
    cfg.OUT_DIR = str(tmp_path)
    cfg.OPTIM.BASE_LR = 0.8
    path = config.dump_cfg()
    fresh = CfgNode()
    import yaml

    loaded = yaml.safe_load(open(path))
    assert loaded["OPTIM"]["BASE_LR"] == 0.8


def test_load_cfg_fom_args(tmp_path):
    path = os.path.join(CONFIG_DIR, "resnet50.yaml")
    config.load_cfg_fom_args(argv=["--cfg", path, "OPTIM.MAX_EPOCH", "5"])
    assert cfg.MODEL.ARCH == "resnet50"
    assert cfg.OPTIM.MAX_EPOCH == 5


def test_reset_cfg():
    cfg.merge_from_list(["MODEL.ARCH", "resnet50"])
    config.reset_cfg()
    assert cfg.MODEL.ARCH == "resnet18"
