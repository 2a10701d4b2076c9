"""chip_smoke.py off the chip: it must refuse, naming the missing TPU —
and its phases must run end to end at toy size, so a change that breaks
a phase's plumbing shows here before it costs chip budget."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_without_a_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert out.returncode != 0
    assert "no TPU" in out.stderr and "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout  # no result line


@pytest.mark.slow
def test_phases_dry_run_at_toy_size(tmp_path):
    """resnet18 at 32² and a cut gpt_nano through the SAME phase code the
    chip runs (kernels resolve to what ``auto`` means on the CPU)."""
    sys.path.insert(0, REPO)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO)
    summary = chip_smoke.run_phases(chip_smoke.TOY, str(tmp_path / "smoke"))
    assert summary["ok"], json.dumps(summary, indent=1)
    assert list(summary["phases"]) == [
        "device", "train", "image_serve", "lm_serve", "flash"]
    assert summary["device"]["platform"] == "cpu"
